"""Energy/accuracy metering: attribute `core/energy_model` per-MAC
estimates to live serving traffic (DESIGN.md §15).

The dispatch frontends (`core/approx_gemm`) announce every GEMM / conv /
attention call — with its exact MAC count — to the installed obs sink.
Those announcements fire when the frontend *Python* runs: eager calls
and outer-jit traces, never jitted steady-state replays.  So live
attribution cannot count calls at serve time (the whole point of the
zero-retrace engine is that steady state replays executables); instead
the meter builds **per-executable MAC profiles once, abstractly**:

    jax.eval_shape(lm.decode_step, params, caches, tok, pos)

under a scoped `MacCapture` sink.  `eval_shape` re-runs the model's
Python with tracers — every frontend hook fires with its true shapes,
`obs_mac_scale` corrects for `lax.scan` bodies that trace once but
execute `n_periods` times — in milliseconds and with zero FLOPs.  At
serve time the engine then just counts *invocations* per pre-profiled
executable (decode rounds, (G, P)-bucket prefills, spec sub-rounds) and
multiplies.  Profiling happens inside `ServingEngine.warmup()` BEFORE
the steady-state retrace probe arms, so a telemetry-enabled engine
still reports ``steady_retraces() == 0``.

Energy = sum over captured (family, bits) of macs *
`energy_model.energy_per_mac_j` — the paper's Table II anchors, making
**estimated energy-per-token per tier** a first-class serving metric.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple


class MacCapture:
    """Dispatch sink that accumulates MAC counts by (family, bits) and
    by op kind; satisfies the full sink protocol so it can be installed
    anywhere a telemetry sink can."""

    def __init__(self):
        self.by_family: Dict[Tuple[str, int], float] = {}
        self.by_op: Dict[str, float] = {}
        self.total = 0.0

    def dispatch(self, op: str, family: str, mode: str, bits: int,
                 macs: float, cache_hit: bool, kernel: str = "") -> None:
        key = (family, int(bits))
        self.by_family[key] = self.by_family.get(key, 0.0) + macs
        self.by_op[op] = self.by_op.get(op, 0.0) + macs
        self.total += macs

    def retrace(self) -> None:
        pass

    def autotune(self, key: str, outcome: str) -> None:
        pass


@contextlib.contextmanager
def capture_macs():
    """Scoped MAC capture: installs a `MacCapture` as the dispatch sink
    and restores the previous sink on exit."""
    from repro.core import approx_gemm

    cap = MacCapture()
    prev = approx_gemm.set_obs_sink(cap)
    try:
        yield cap
    finally:
        approx_gemm.set_obs_sink(prev)


def profile_macs(fn, *args, **kwargs) -> MacCapture:
    """MAC profile of one abstract evaluation of `fn(*args, **kwargs)`
    (`jax.eval_shape`: no FLOPs, no device buffers, milliseconds)."""
    import jax

    with capture_macs() as cap:
        jax.eval_shape(fn, *args, **kwargs)
    return cap


def macs_to_energy_j(by_family: Dict[Tuple[str, int], float],
                     fallback_j_per_mac: Optional[float] = None) -> float:
    """Convert a (family, bits) -> macs profile to Joules via the
    paper's per-MAC anchors; families the energy model does not cover
    fall back to `fallback_j_per_mac` (or contribute 0)."""
    from repro.core import energy_model

    total = 0.0
    for (family, bits), macs in by_family.items():
        try:
            e = energy_model.energy_per_mac_j(family, bits)
        except (KeyError, ValueError):
            e = fallback_j_per_mac or 0.0
        total += macs * e
    return total


class LaneEnergyMeter:
    """Per-lane invocation counting over pre-built MAC profiles.

    `build(backend)` profiles the lane's steady-state executables
    (pool decode, every (G, P) prefill bucket, spec sub-rounds per
    draft depth) — call it from engine warmup, before the retrace probe
    arms.  The `on_*` hooks then cost a dict lookup + float adds per
    scheduler event and return the energy increment so the caller can
    attribute shares to live requests.
    """

    def __init__(self, name: str,
                 fallback_j_per_mac: Optional[float] = None):
        self.name = name
        self.fallback_j_per_mac = fallback_j_per_mac
        self.profiled = False
        self.macs = 0.0
        self.energy_j = 0.0
        self.tokens = 0
        self.n_decode_rounds = 0
        self.n_prefills = 0
        self.n_spec_subrounds = 0
        self._decode: Tuple[float, float] = (0.0, 0.0)   # (macs, J)
        self._prefill: Dict[Tuple[int, int], Tuple[float, float]] = {}
        self._spec: Dict[int, Tuple[float, float]] = {}
        self._g_buckets: Tuple[int, ...] = ()
        self._p_buckets: Tuple[int, ...] = ()

    # -- profile construction (warmup-time) --------------------------------
    def _cost(self, cap: MacCapture) -> Tuple[float, float]:
        return (cap.total, macs_to_energy_j(cap.by_family,
                                            self.fallback_j_per_mac))

    def build(self, backend) -> bool:
        """Profile an `LMLaneBackend`-shaped lane; returns False (meter
        stays inert) for backends without the LM surface (fake lanes)."""
        import numpy as np

        if not all(hasattr(backend, a) for a in
                   ("lm", "params", "caches", "prompt_buckets",
                    "group_buckets", "n_slots", "max_len")):
            return False
        lm, params, caches = backend.lm, backend.params, backend.caches
        b = backend.n_slots
        tok = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        with backend._ctx():
            self._decode = self._cost(
                profile_macs(lm.decode_step, params, caches, tok, pos))
            for g in backend.group_buckets:
                for p in backend.prompt_buckets:
                    def pre(par, t, ln):
                        return lm.prefill(par, {
                            "tokens": t, "lengths": ln,
                            "max_len": backend.max_len})

                    cap = profile_macs(
                        pre, params, np.zeros((g, p), np.int32),
                        np.full((g,), p, np.int32))
                    self._prefill[(g, p)] = self._cost(cap)
            for k in getattr(backend, "draft_ks", ()):
                # one spec sub-round = k drafter steps + one (k+1)-wide
                # batched verify (the while_loop chains sub-rounds, so
                # runtime counting is per executed sub-round)
                d = profile_macs(backend.drafter_lm.decode_step, params,
                                 caches, tok, pos)
                v = profile_macs(lm.decode_multi, params, caches,
                                 np.zeros((b, k + 1), np.int32), pos)
                self._spec[k] = (
                    k * d.total + v.total,
                    k * macs_to_energy_j(d.by_family,
                                         self.fallback_j_per_mac)
                    + macs_to_energy_j(v.by_family,
                                       self.fallback_j_per_mac))
        self._g_buckets = tuple(backend.group_buckets)
        self._p_buckets = tuple(backend.prompt_buckets)
        self.profiled = True
        return True

    # -- serve-time counting ------------------------------------------------
    @staticmethod
    def _bucket_up(v: int, buckets: Tuple[int, ...]) -> int:
        for b in buckets:
            if b >= v:
                return b
        return buckets[-1] if buckets else v

    def _add(self, cost: Tuple[float, float]) -> float:
        m, j = cost
        self.macs += m
        self.energy_j += j
        return j

    def on_decode(self) -> float:
        """One full-pool decode round; returns the Joule increment."""
        self.n_decode_rounds += 1
        return self._add(self._decode)

    def on_prefill(self, n_prompts: int, prompt_len: int) -> float:
        """One grouped prefill (bucketed to the profiled (G, P))."""
        self.n_prefills += 1
        g = self._bucket_up(n_prompts, self._g_buckets)
        p = self._bucket_up(prompt_len, self._p_buckets)
        return self._add(self._prefill.get((g, p), (0.0, 0.0)))

    def on_spec_rounds(self, k: int, n_subrounds: int) -> float:
        """`n_subrounds` executed draft+verify sub-rounds at depth k."""
        self.n_spec_subrounds += n_subrounds
        m, j = self._spec.get(k, (0.0, 0.0))
        self.macs += m * n_subrounds
        self.energy_j += j * n_subrounds
        return j * n_subrounds

    def add_tokens(self, n: int) -> None:
        self.tokens += n

    @property
    def energy_per_token_j(self) -> float:
        return self.energy_j / max(self.tokens, 1)
