"""Error characterization + the calibrated stochastic surrogate.

Two layers:

1. `characterize(spec)` — exhaustive (<=10-bit) or sampled error metrics
   of a multiplier: NMED, MRED, WCE, bias, one-sidedness.  These are the
   paper's Table-IV multiplier columns and are data-independent.

   Characterization is the DSE inner loop (`core/dse.enumerate_space`,
   `serving/tiers.build_tiers`), so it is cached and batched
   (DESIGN.md §16):

   * a **cross-process disk cache** (same hardening as
     `core/autotune.py`: env-var override, corrupt-JSON tolerance,
     atomic per-PID temp + `os.replace`, merge-on-save) means an engine
     build never re-pays Monte Carlo in steady state;
   * `characterize_batch(specs)` evaluates the *whole spec grid* as one
     jitted JAX program (the bit-exact emulators are written with
     numpy/jnp-shared operators, so they trace) — optionally
     `shard_map`-partitioned over the mesh data axis, the evaluation
     being embarrassingly parallel over samples.  The integer products
     are pulled back to the host and reduced by the SAME numpy routine
     as the serial path, so batched metrics are byte-identical to
     serial ones and the two paths share one cache.

2. `SurrogateModel` — the scale-out execution model.  A 671B-parameter
   model cannot gather 1e17 LUT entries per step, so production-scale
   approximate GEMM runs as `exact_dot + calibrated error`.  Per scalar
   product p = a*b (sign-magnitude: the error carries the product sign):

       e(a, b) = mu_rel * p + r,     E[r^2 | p] ~= c0_abs + c1_rel * p^2

   The affine variance law covers both regimes observed in the paper's
   families: Appro4-2's error is bounded by the approximated low columns
   (magnitude-independent -> c0 dominates) while Mitchell/Log-our errors
   are proportional to the product (c1 dominates).  Summed over a
   contraction of length K, per output element:

       out = (1 + mu_rel) * A@B
             + sqrt(c0_abs * K * s^2 + c1_rel * (A^2 @ B^2)) * eps

   with eps ~ N(0,1) and s the product of the quantization scales (the
   c0 term lives in integer units).  One extra GEMM for the variance
   term, zero for the bias.  (mu_rel, c0_abs, c1_rel) are fitted from the
   bit-exact emulator with *Gaussian-weighted* least squares (int
   operands ~ quantized N(0, sigma), the distribution a per-tensor-scaled
   activation actually has).  Tests validate the surrogate's first two
   moments against bit-exact LUT GEMM.

   This mirrors the paper's own observation (Sec. V-B) that Log-our
   errors act as zero-mean noise while Appro4-2's one-sided errors cause
   a systematic (bias) shift — exactly the two terms of the surrogate.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .luts import MAX_LUT_BITS, build_lut
from .multipliers import MultiplierSpec, multiply_unsigned

# reference integer operand distribution for surrogate fitting: per-tensor
# symmetric quantization of ~N(0,1) data maps sigma to roughly qmax/3.2
_GAUSS_SIGMA_FRAC = 1.0 / 3.2

# int32 is the widest dtype the jitted product evaluation can rely on
# with x64 disabled: unsigned products need 2*bits magnitude bits
_MAX_BATCHED_BITS = 15


@dataclasses.dataclass(frozen=True)
class ErrorMetrics:
    nmed: float          # mean |err| / max product           (uniform)
    mred: float          # mean |err| / |exact|, nonzero exact (uniform)
    wce: int             # max |err|
    bias: float          # mean signed err                     (uniform)
    mu_rel: float        # gaussian-weighted LS slope of err on product
    c0_abs: float        # residual variance floor (int^2 units)
    c1_rel: float        # residual variance slope on p^2
    one_sided: bool
    exhaustive: bool

    @property
    def sigma_rel(self) -> float:
        return float(np.sqrt(self.c1_rel))


def _spec_key(spec: MultiplierSpec) -> Tuple:
    # constructor order: MultiplierSpec(*_spec_key(spec)) round-trips
    return (spec.family, spec.bits, spec.signed, spec.compressor,
            spec.n_approx_cols)


def _operands(bits: int, n_samples: int, seed: int):
    """(a, b, exhaustive): the SAME operand stream for the serial and
    the batched path — exhaustive grid below the LUT cap, else the
    seeded MC draw (two `integers` calls off one fresh Generator, the
    order the serial path has always used)."""
    if bits <= MAX_LUT_BITS:
        n = 1 << bits
        a, b = np.meshgrid(np.arange(n, dtype=np.int64),
                           np.arange(n, dtype=np.int64), indexing="ij")
        return a.ravel(), b.ravel(), True
    rng = np.random.default_rng(seed)
    hi = 1 << bits
    a = rng.integers(0, hi, n_samples, dtype=np.int64)
    b = rng.integers(0, hi, n_samples, dtype=np.int64)
    return a, b, False


def _error_grid(spec: MultiplierSpec, n_samples: int, seed: int):
    a, b, exhaustive = _operands(spec.bits, n_samples, seed)
    if exhaustive:
        return a, b, build_lut(spec).astype(np.int64).ravel(), True
    p = np.asarray(multiply_unsigned(a, b, spec), dtype=np.int64)
    return a, b, p, False


def _gauss_weights(a: np.ndarray, bits: int) -> np.ndarray:
    """Folded-gaussian pmf over unsigned magnitudes (signed symmetric)."""
    sigma = ((1 << (bits - 1)) - 1) * _GAUSS_SIGMA_FRAC
    w = np.exp(-0.5 * (a / sigma) ** 2)
    return w


def _metrics_from_products(a: np.ndarray, b: np.ndarray, p: np.ndarray,
                           bits: int, exhaustive: bool) -> ErrorMetrics:
    """The single metric/fit reduction both paths share: identical
    float64 numpy ops on identical int64 inputs make batched results
    byte-identical to serial ones (the cache-coherence contract)."""
    exact = a * b
    err = (p - exact).astype(np.float64)
    maxp = float(((1 << bits) - 1) ** 2)
    nz = exact > 0
    rel = err[nz] / exact[nz].astype(np.float64)

    # --- gaussian-weighted surrogate fit (see module docstring) ---
    w = _gauss_weights(a, bits) * _gauss_weights(b, bits)
    w = w / w.sum()
    pf = exact.astype(np.float64)
    wp2 = float((w * pf * pf).sum())
    mu_rel = float((w * err * pf).sum() / max(wp2, 1e-30))
    r = err - mu_rel * pf
    r2 = r * r
    # weighted LS of r^2 on [1, p^2], clamped nonnegative
    p2 = pf * pf
    s1, sp2 = 1.0, float((w * p2).sum())
    sp4 = float((w * p2 * p2).sum())
    sr2 = float((w * r2).sum())
    sr2p2 = float((w * r2 * p2).sum())
    det = s1 * sp4 - sp2 * sp2
    if det > 1e-30:
        c0 = (sr2 * sp4 - sp2 * sr2p2) / det
        c1 = (s1 * sr2p2 - sp2 * sr2) / det
    else:
        c0, c1 = sr2, 0.0
    if c0 < 0.0:  # refit with c0 = 0
        c0, c1 = 0.0, sr2p2 / max(sp4, 1e-30)
    if c1 < 0.0:  # refit with c1 = 0
        c0, c1 = sr2, 0.0

    return ErrorMetrics(
        nmed=float(np.abs(err).mean() / maxp),
        mred=float(np.abs(rel).mean()),
        wce=int(np.abs(err).max()),
        bias=float(err.mean()),
        mu_rel=mu_rel,
        c0_abs=float(c0),
        c1_rel=float(c1),
        one_sided=bool((err <= 0).all() or (err >= 0).all()),
        exhaustive=exhaustive,
    )


# ---------------------------------------------------------------------------
# Characterization cache (memory + hardened cross-process disk)
# ---------------------------------------------------------------------------

_ENV_CACHE = "OPENACM_CHAR_CACHE"
_SCHEMA = "acm1"
_mem_cache: Dict[str, ErrorMetrics] = {}
_lock = threading.Lock()

_METRIC_FIELDS = tuple(f.name for f in dataclasses.fields(ErrorMetrics))


def cache_path() -> str:
    from repro import CHECKOUT

    return os.environ.get(
        _ENV_CACHE, os.path.join(CHECKOUT, ".cache", "characterize.json"))


def clear_memory_cache() -> None:
    with _lock:
        _mem_cache.clear()


def _cache_key(spec: MultiplierSpec, n_samples: int, seed: int) -> str:
    # below the LUT cap the metrics are exhaustive: independent of the
    # sample count and seed, so all (n, seed) requests share one row
    tail = ("exh" if spec.bits <= MAX_LUT_BITS
            else f"n{n_samples}:s{seed}")
    return (f"{_SCHEMA}:{spec.family}:b{spec.bits}:{spec.compressor}"
            f":c{spec.n_approx_cols}:sg{int(spec.signed)}:{tail}")


def _load_disk(path: str) -> Dict[str, ErrorMetrics]:
    """Parse the disk cache defensively (autotune.py hardening): a
    corrupt/truncated file, a non-dict payload or malformed rows are
    *ignored* (the next compute rewrites the file through _save_disk's
    merge), never fatal."""
    try:
        with open(path) as fh:
            raw = json.load(fh)
    except (OSError, ValueError):
        return {}
    if not isinstance(raw, dict):
        return {}
    out: Dict[str, ErrorMetrics] = {}
    for k, v in raw.items():
        if not (isinstance(k, str) and k.startswith(_SCHEMA + ":")
                and isinstance(v, dict)):
            continue
        try:
            m = ErrorMetrics(
                nmed=float(v["nmed"]), mred=float(v["mred"]),
                wce=int(v["wce"]), bias=float(v["bias"]),
                mu_rel=float(v["mu_rel"]), c0_abs=float(v["c0_abs"]),
                c1_rel=float(v["c1_rel"]), one_sided=bool(v["one_sided"]),
                exhaustive=bool(v["exhaustive"]))
        except (KeyError, TypeError, ValueError):
            continue
        out[k] = m
    return out


def _save_disk(path: str, table: Dict[str, ErrorMetrics]) -> None:
    """Atomic publish: per-PID temp + os.replace (see autotune.py for
    why a shared temp name would publish torn JSON under concurrent
    writers); read-only filesystems degrade to memory-only caching."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as fh:
            json.dump({k: dataclasses.asdict(v)
                       for k, v in sorted(table.items())}, fh, indent=1)
        os.replace(tmp, path)
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass


def _store(rows: Dict[str, ErrorMetrics], path: str) -> None:
    with _lock:
        _mem_cache.update(rows)
        merged = _load_disk(path)
        merged.update(rows)
        _save_disk(path, merged)


# Observability sink (obs/, DESIGN.md §15): notified once per resolved
# spec with the cache outcome ("mem_hit" | "disk_hit" | "serial" |
# "batched").  Guarded with getattr so sinks predating the hook (e.g.
# scoped MacCapture) keep working.
_OBS_SINK: List[Optional[object]] = [None]


def set_obs_sink(sink) -> Optional[object]:
    """Install the characterization telemetry sink (should expose
    ``char_cache(key, outcome)``); returns the previous one."""
    prev = _OBS_SINK[0]
    _OBS_SINK[0] = sink
    return prev


def _obs(key: str, outcome: str) -> None:
    sink = _OBS_SINK[0]
    if sink is not None:
        fn = getattr(sink, "char_cache", None)
        if fn is not None:
            fn(key=key, outcome=outcome)


def _cache_get(key: str, path: str) -> Optional[ErrorMetrics]:
    with _lock:
        if key in _mem_cache:
            _obs(key, "mem_hit")
            return _mem_cache[key]
    disk = _load_disk(path)
    if key in disk:
        with _lock:
            _mem_cache[key] = disk[key]
        _obs(key, "disk_hit")
        return disk[key]
    return None


# ---------------------------------------------------------------------------
# Serial + batched characterization
# ---------------------------------------------------------------------------


def characterize(spec: MultiplierSpec, n_samples: int = 200_000,
                 seed: int = 0, cache: bool = True,
                 cache_file: Optional[str] = None) -> ErrorMetrics:
    key = _cache_key(spec, n_samples, seed)
    path = cache_file or cache_path()
    if cache:
        hit = _cache_get(key, path)
        if hit is not None:
            return hit
    a, b, p, exhaustive = _error_grid(spec, n_samples, seed)
    m = _metrics_from_products(a, b, p, spec.bits, exhaustive)
    if cache:
        _store({key: m}, path)
    _obs(key, "serial")
    return m


@functools.lru_cache(maxsize=32)
def _products_fn(spec_keys: Tuple[Tuple, ...], mesh):
    """One jitted program computing the stacked integer products of a
    whole spec group — the batched replacement for the per-spec numpy
    loop.  With a mesh, the sample axis is shard_map-partitioned over
    the data axes (embarrassingly parallel; PR-5 machinery)."""
    import jax
    import jax.numpy as jnp

    specs = [MultiplierSpec(*k) for k in spec_keys]

    def f(a, b):
        return jnp.stack(
            [jnp.asarray(multiply_unsigned(a, b, s), jnp.int32)
             for s in specs])

    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from repro.parallel.sharding import batch_axes

        axes = batch_axes(mesh)
        entry = axes if len(axes) > 1 else (axes[0] if axes else None)
        if entry is not None:
            sharded = jax.shard_map(
                f, mesh=mesh, in_specs=(P(entry), P(entry)),
                out_specs=P(None, entry), check_vma=False)
            return jax.jit(sharded)
    return jax.jit(f)


def _mesh_divides(mesh, n: int) -> bool:
    if mesh is None:
        return False
    from repro.parallel.sharding import batch_axes

    return bool(batch_axes(mesh, n))


def characterize_batch(specs: Sequence[MultiplierSpec],
                       n_samples: int = 200_000, seed: int = 0,
                       mesh=None, cache: bool = True,
                       cache_file: Optional[str] = None
                       ) -> List[ErrorMetrics]:
    """Characterize a whole spec grid with one jitted evaluation per
    (bits) group instead of a serial per-spec numpy loop.

    Metrics are byte-identical to `characterize` (same operand stream,
    same host-side reduction) and land in the same caches.  Specs wider
    than the int32 product budget (bits > 15) and cache hits fall back
    to the serial path transparently.
    """
    import jax

    path = cache_file or cache_path()
    results: List[Optional[ErrorMetrics]] = [None] * len(specs)
    todo: List[int] = []
    seen: Dict[str, int] = {}
    for i, spec in enumerate(specs):
        key = _cache_key(spec, n_samples, seed)
        if cache:
            hit = _cache_get(key, path)
            if hit is not None:
                results[i] = hit
                continue
        if key in seen:           # duplicate spec in one grid
            todo.append(i)
            continue
        seen[key] = i
        todo.append(i)

    groups: Dict[int, List[int]] = {}
    for i in seen.values():       # one compute per distinct key
        if results[i] is None:
            groups.setdefault(specs[i].bits, []).append(i)

    fresh: Dict[str, ErrorMetrics] = {}
    for bits, idxs in sorted(groups.items()):
        if not idxs:
            continue
        a, b, exhaustive = _operands(bits, n_samples, seed)
        if bits <= _MAX_BATCHED_BITS:
            spec_keys = tuple(_spec_key(specs[i]) for i in idxs)
            use_mesh = mesh if _mesh_divides(mesh, a.size) else None
            fn = _products_fn(spec_keys, use_mesh)
            stacked = np.asarray(jax.device_get(
                fn(a.astype(np.int32), b.astype(np.int32)))
            ).astype(np.int64)
            outcome = "batched"
        else:
            stacked = np.stack(
                [np.asarray(multiply_unsigned(a, b, specs[i]),
                            dtype=np.int64) for i in idxs])
            outcome = "serial"
        for row, i in enumerate(idxs):
            m = _metrics_from_products(a, b, stacked[row], bits,
                                       exhaustive)
            key = _cache_key(specs[i], n_samples, seed)
            results[i] = m
            fresh[key] = m
            _obs(key, outcome)
    if cache and fresh:
        _store(fresh, path)
    # duplicates of freshly computed keys resolve off the new rows
    for i in todo:
        if results[i] is None:
            results[i] = fresh[_cache_key(specs[i], n_samples, seed)]
    return results  # type: ignore[return-value]


@dataclasses.dataclass(frozen=True)
class SurrogateModel:
    """Calibrated (mu_rel, c0_abs, c1_rel) noise model for one multiplier."""

    mu_rel: float
    c0_abs: float
    c1_rel: float
    wce: int
    spec: MultiplierSpec

    @classmethod
    def fit(cls, spec: MultiplierSpec, **kw) -> "SurrogateModel":
        m = characterize(spec, **kw)
        return cls(mu_rel=m.mu_rel, c0_abs=m.c0_abs, c1_rel=m.c1_rel,
                   wce=m.wce, spec=spec)

    @classmethod
    def exact(cls, spec: MultiplierSpec) -> "SurrogateModel":
        return cls(0.0, 0.0, 0.0, 0, spec)

    @property
    def is_exact(self) -> bool:
        return self.mu_rel == 0.0 and self.c0_abs == 0.0 and self.c1_rel == 0.0

    @property
    def has_noise(self) -> bool:
        return self.c0_abs > 0.0 or self.c1_rel > 0.0

    def apply_dot(self, exact_dot, sq_dot, k_len, scale2, noise):
        """out = (1+mu)*D + sqrt(c0*K*s^2 + c1*(A^2@B^2)) * eps.

        scale2: squared product-of-quant-scales, broadcastable to the
        output (per-out-channel); sq_dot in real (dequantized) units.
        """
        out = (1.0 + self.mu_rel) * exact_dot
        if noise is not None and self.has_noise:
            import jax.numpy as jnp

            var = self.c0_abs * k_len * scale2
            if self.c1_rel > 0.0 and sq_dot is not None:
                var = var + self.c1_rel * sq_dot
            out = out + jnp.sqrt(jnp.maximum(var, 0.0)) * noise
        return out
