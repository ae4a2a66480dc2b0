"""Continuous-batching serving engine over the CiM dispatch stack
(DESIGN.md §10).

Three cooperating pieces:

  * **Slot pool** — per accuracy tier, a fixed-batch KV-cache pool
    (``LM.init_caches(per_slot=True)``): every batch row is an
    independent sequence with its own (B,)-vector position/fill level.
    New requests *prefill into slots* of a running batch (a batched
    ragged prefill + a jitted scatter of the group caches into the pool
    rows) and finished ones are evicted in place — decode never stops,
    restarts, or changes shape.

  * **Scheduler** — FIFO arrival queues per tier, token-budget
    admission (a request reserves ``prompt_len + max_new`` tokens until
    eviction; the queue head blocks rather than being skipped, so no
    request starves), slot assignment, and eviction on EOS/max-gen.

  * **Tier lanes** — one slot pool per accuracy tier, each executing
    through its own pre-built jitted prefill/decode functions over the
    *shared* weights.  Tier switches are a dict lookup (lane pick), and
    occupancy changes never alter a traced shape: prompt lengths and
    admission group sizes are bucketed to pre-warmed sets, and the
    decode batch is always the full pool.  `warmup()` compiles every
    (tier x prompt-bucket x group-bucket) combination plus the decode
    and insert paths before traffic is admitted;
    `steady_retraces()` (the core/approx_gemm.trace_count probe) must
    stay 0 afterwards.

All shapes the engine ever traces: prefill (G, P) for G in
group_buckets, P in prompt_buckets; decode (n_slots, 1); insert one
scatter per G.  Everything else is host-side bookkeeping.
"""

from __future__ import annotations

import bisect
import dataclasses
import weakref
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.telemetry import NOSPAN

from .sentinel import LaneHealthError


# ---------------------------------------------------------------------------
# Requests / results
# ---------------------------------------------------------------------------


class AdmissionRejected(RuntimeError):
    """Structured backpressure signal: the admission queue is full.

    Carries enough for the caller to implement retry-after semantics
    instead of parsing a message; the engine's own `run` loop responds
    by holding further arrivals until the queues drain.
    """

    def __init__(self, rid: int, queued: int, limit: int):
        super().__init__(
            f"request {rid} rejected: {queued} requests queued >= "
            f"admission limit {limit}")
        self.rid, self.queued, self.limit = rid, queued, limit


@dataclasses.dataclass
class Request:
    """One inference request.  `tier` pins an SLA class by name;
    otherwise `tolerance` (max NMED) is routed through the TierRouter.
    `arrival` is seconds on the engine clock (workload time)."""

    rid: int
    prompt: np.ndarray
    max_new: int
    tolerance: Optional[float] = None
    tier: Optional[str] = None
    arrival: float = 0.0
    eos_id: Optional[int] = None

    @property
    def cost(self) -> int:
        """Token-budget reservation: worst-case KV footprint."""
        return len(self.prompt) + self.max_new


@dataclasses.dataclass
class RequestResult:
    rid: int
    tier: str
    prompt_len: int
    arrival: float
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    logits: Optional[List[np.ndarray]] = None   # record_logits engines
    retries: int = 0         # sentinel-trip restarts (DESIGN.md §14)
    status: str = "ok"       # "ok" | "failed" (retry budget exhausted)

    @property
    def done(self) -> bool:
        return self.t_done is not None

    @property
    def ms_per_token(self) -> float:
        """End-to-end per-token latency (queueing included)."""
        return 1e3 * (self.t_done - self.arrival) / max(len(self.tokens), 1)


@dataclasses.dataclass
class EngineStats:
    n_requests: int
    total_tokens: int
    duration_s: float
    tokens_per_s: float
    p50_ms_per_token: float
    p95_ms_per_token: float
    p50_ttft_ms: float
    p95_ttft_ms: float
    n_failed: int = 0        # retry budget exhausted (DESIGN.md §14)

    @classmethod
    def from_results(cls, results: Dict[int, "RequestResult"],
                     duration_s: float) -> "EngineStats":
        n_failed = sum(1 for r in results.values()
                       if r.done and r.status != "ok")
        done = [r for r in results.values()
                if r.done and r.status == "ok"]
        tot = sum(len(r.tokens) for r in done)
        lat = np.asarray([r.ms_per_token for r in done]) if done else \
            np.zeros(1)
        ttft = np.asarray([1e3 * (r.t_first - r.arrival) for r in done]) \
            if done else np.zeros(1)
        return cls(n_requests=len(done), total_tokens=tot,
                   duration_s=duration_s,
                   tokens_per_s=tot / max(duration_s, 1e-9),
                   p50_ms_per_token=float(np.percentile(lat, 50)),
                   p95_ms_per_token=float(np.percentile(lat, 95)),
                   p50_ttft_ms=float(np.percentile(ttft, 50)),
                   p95_ttft_ms=float(np.percentile(ttft, 95)),
                   n_failed=n_failed)


@dataclasses.dataclass
class TripEvent:
    """One sentinel trip, structured (DESIGN.md §15): engine-clock
    timestamp, tripped lane, the trigger metric (rolling agree/NMED at
    detection, None for forced or non-finite trips), and the breaker
    state on either side of the transition.  Dict-style access
    (``ev["lane"]``, ``ev.get(...)``, ``dict(ev)``) is kept for the
    pre-structured `trip_log` consumers."""

    lane: str
    t: float
    reason: str
    tokens_before_trip: int
    in_flight_displaced: int
    trigger_agree: Optional[float] = None
    trigger_nmed: Optional[float] = None
    breaker_before: str = "healthy"
    breaker_after: str = "tripped"

    def __getitem__(self, key: str):
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def get(self, key: str, default=None):
        return getattr(self, key, default)

    def keys(self):
        return [f.name for f in dataclasses.fields(self)]


def _admit_rows(backend, n: int, prompt_bucket: int) -> int:
    """Token rows one grouped prefill computes: group bucket x prompt
    bucket (a backend without group buckets computes its `n` rows)."""
    groups = getattr(backend, "group_buckets", None)
    g = _bucket_up(n, groups, "admission group") if groups else n
    return g * prompt_bucket


def _bucket_up(v: int, buckets: Sequence[int], what: str) -> int:
    for b in buckets:
        if b >= v:
            return b
    raise ValueError(f"{what} {v} exceeds the largest configured bucket "
                     f"{max(buckets)}")


# ---------------------------------------------------------------------------
# The LM lane backend: one slot pool on one CiM tier
# ---------------------------------------------------------------------------


def check_engine_arch(cfg) -> None:
    """Continuous batching needs every layer's state to be a positional
    KV cache (per-slot fill levels + validity masks).  That is the
    full-attention dense stacks; MLA latents, recurrent states (RG-LRU,
    xLSTM), encoders and windowed ring buffers are rejected."""
    from repro.models import config as C

    kinds = set(cfg.prefix_layers) | set(cfg.period)
    if (cfg.mla is not None or cfg.vision is not None
            or cfg.encoder is not None or not kinds <= {C.ATTN}):
        raise ValueError(
            f"arch {cfg.name!r} is not servable by the slot-pool engine "
            f"(layer kinds {sorted(kinds)}); dense full-attention stacks "
            "only")


def servable_archs(smoke: bool = True) -> List[str]:
    """Registry archs the slot-pool engine can serve (the launcher and
    example restrict their --arch choices to these)."""
    from repro.configs import arch_names, get_config

    out = []
    for name in arch_names():
        try:
            check_engine_arch(get_config(name, smoke=smoke))
        except ValueError:
            continue
        out.append(name)
    return out


class LMLaneBackend:
    """Slot-pool execution for one (LM, CiM tier): pre-jitted ragged
    group prefill, cache scatter-insert, and full-pool decode.

    With `mesh` (DESIGN.md §11) the pool is **data-parallel sharded**:
    slots (the cache batch dim) spread over the mesh's data axes,
    weights are placed tensor-parallel per `DECODE_RULES`, and every
    executable is traced under the mesh so the integer-mode tiers route
    their matmuls through the shard_map dispatch path
    (models/common.cim_linear -> core/approx_gemm.MeshPlan).  The
    scheduler above is device-count agnostic by construction — it only
    ever sees slot indices — so nothing else changes.
    """

    def __init__(self, lm, params, *, n_slots: int, max_len: int,
                 prompt_buckets: Sequence[int] = (16, 32),
                 group_buckets: Sequence[int] = (1, 2, 4),
                 mesh=None):
        import jax
        import jax.numpy as jnp

        from repro.kernels.decode_attn import kv_block
        from repro.models.transformer import (decode_attn_kernel_layers,
                                              kv_inplace_layers)

        check_engine_arch(lm.cfg)
        self.lm, self.params = lm, params
        self.mesh = mesh
        self.n_slots, self.max_len = int(n_slots), int(max_len)
        self.prompt_buckets = tuple(sorted(set(int(p) for p in
                                               prompt_buckets)))
        self.group_buckets = tuple(sorted(set(int(g) for g in
                                              group_buckets)))
        if max(self.prompt_buckets) > self.max_len:
            raise ValueError("prompt bucket exceeds max_len")
        self.caches = lm.init_caches(self.n_slots, self.max_len,
                                     per_slot=True)
        self._tok_shard = self._pos_shard = None
        if mesh is not None:
            from repro.launch.mesh import require_auto_axes
            from repro.parallel.sharding import (DECODE_RULES,
                                                 batch_sharding,
                                                 cache_shardings,
                                                 param_shardings)

            require_auto_axes(mesh)
            # weights TP-sharded per DECODE_RULES (no ZeRO-3 at serve
            # time), slots on the data axes; placing params is idempotent
            # across the lanes sharing them
            self.params = jax.device_put(
                params, param_shardings(lm, params, mesh,
                                        rules=DECODE_RULES))
            self.caches = jax.device_put(
                self.caches, cache_shardings(self.caches, mesh, lm.cfg,
                                             rules=DECODE_RULES))
            self._tok_shard = batch_sharding(mesh, 2, self.n_slots)
            self._pos_shard = batch_sharding(mesh, 1, self.n_slots)
        self.slot_tokens = np.zeros(self.n_slots, np.int64)
        self.slot_pos = np.zeros(self.n_slots, np.int64)
        self.last_prefill_logits: Optional[np.ndarray] = None
        self.last_decode_logits: Optional[np.ndarray] = None
        self.telemetry = None        # obs.EngineTelemetry, set by the engine
        # the decode below writes these layers' KV into the pool in place
        self.kv_inplace_layers = kv_inplace_layers(lm.cfg)
        # ... and, lowered for a TPU with no mesh, attends over them with
        # kernels.decode_attn, `_kv_rows` pool rows per block
        self.decode_attn_kernel_layers = 0 if mesh is not None else \
            decode_attn_kernel_layers(lm.cfg, lm.cim, jax.default_backend())
        cfg = lm.cfg
        self._kv_rows = kv_block(self.max_len, cfg.n_kv_heads * cfg.head_dim_,
                                 cfg.head_dim_,
                                 jnp.dtype(jnp.bfloat16).itemsize)[0]

        # max_len must be a trace-time constant (it sizes the group
        # caches), so it is closed over — same trick as launch/serve.py
        def _prefill(p, toks, lens):
            return lm.prefill(p, {"tokens": toks, "lengths": lens,
                                  "max_len": self.max_len})

        self._prefill = jax.jit(_prefill)
        # decode caches are donated: each round's pool buffers die the
        # moment the next round's exist (in-place update on TPU;
        # ignored with a warning on CPU)
        self._decode = jax.jit(lm.decode_step, donate_argnums=(1,))

        def _insert(lane, grp, slots):
            # scatter group-cache rows into the pool rows named by
            # `slots`; the sentinel slot == n_slots (admission padding)
            # is out of range and dropped, never clamped onto a live row
            def pre(d, s):
                return d.at[slots].set(s.astype(d.dtype), mode="drop")

            def body(d, s):
                return d.at[:, slots].set(s.astype(d.dtype), mode="drop")

            out = {"prefix": [jax.tree_util.tree_map(pre, lp, gp)
                              for lp, gp in zip(lane["prefix"],
                                                grp["prefix"])],
                   "body": None}
            if lane["body"] is not None:
                out["body"] = jax.tree_util.tree_map(body, lane["body"],
                                                     grp["body"])
            return out

        self._insert = jax.jit(_insert, donate_argnums=(0,))
        self._jnp = jnp

    def _ctx(self):
        """Ambient-mesh context for every trace/execute: inside it,
        cim_linear sees the mesh and routes integer-mode matmuls
        through the shard_map dispatch path (DESIGN.md §11)."""
        if self.mesh is not None:
            import jax

            return jax.set_mesh(self.mesh)
        from contextlib import nullcontext

        return nullcontext()

    # -- shape vocabulary --------------------------------------------------
    def prompt_bucket(self, plen: int) -> int:
        return _bucket_up(plen, self.prompt_buckets, "prompt length")

    @property
    def max_group(self) -> int:
        return max(self.group_buckets)

    # -- execution ---------------------------------------------------------
    @staticmethod
    def _fetch(logits) -> np.ndarray:
        """The last position's logits on the host, (B, 1, V) float32:
        waits for the step that makes them, then copies.  The slice is
        its own tiny XLA executable, compiled by `warmup`."""
        return np.asarray(logits[:, -1:, :], np.float32)

    def _sample(self, logits, fetch: str,
                sample: str) -> Tuple[np.ndarray, np.ndarray]:
        """`_fetch` then `_greedy`, each under its own span."""
        tel = self.telemetry
        with (tel.span(fetch) if tel is not None else NOSPAN):
            lg = self._fetch(logits)
        with (tel.span(sample) if tel is not None else NOSPAN):
            return self._greedy(lg)

    def _greedy(self, logits) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side greedy sampling over (B, S, V) logits (the last
        position's).

        Non-finite logits raise a diagnostic `LaneHealthError` instead
        of silently emitting argmax-of-garbage (np.argmax would return
        the first NaN's index); on sentinel-guarded lanes the engine
        catches it as an immediate trip (DESIGN.md §14)."""
        lg = np.asarray(logits[:, -1, :], np.float32)
        if not np.isfinite(lg).all():
            bad = int((~np.isfinite(lg)).sum())
            raise LaneHealthError(
                f"lane produced non-finite logits ({bad}/{lg.size} "
                "entries NaN/inf)")
        return np.argmax(lg, axis=-1), lg

    def admit(self, prompts: List[np.ndarray],
              slots: List[int]) -> np.ndarray:
        """Ragged group prefill into the named pool slots; returns the
        first sampled (greedy) token per prompt."""
        jnp = self._jnp
        g = len(prompts)
        p_bkt = self.prompt_bucket(max(len(p) for p in prompts))
        g_bkt = _bucket_up(g, self.group_buckets, "admission group")
        toks = np.zeros((g_bkt, p_bkt), np.int32)
        lens = np.ones(g_bkt, np.int32)       # padding rows: 1-token stubs
        slot_idx = np.full(g_bkt, self.n_slots, np.int32)   # OOB sentinel
        tel = self.telemetry
        with (tel.span("prefill.dispatch") if tel is not None else NOSPAN):
            for i, (pr, sl) in enumerate(zip(prompts, slots)):
                toks[i, :len(pr)] = pr
                lens[i] = len(pr)
                slot_idx[i] = sl
            with self._ctx():
                logits, grp = self._prefill(self.params, jnp.asarray(toks),
                                            jnp.asarray(lens))
                self.caches = self._insert(self.caches, grp,
                                           jnp.asarray(slot_idx))
        first, lg = self._sample(logits, "prefill.fetch", "prefill.sample")
        self.last_prefill_logits = lg[:g]
        for i, sl in enumerate(slots):
            self.slot_tokens[sl] = first[i]
            self.slot_pos[sl] = lens[i]
        return first[:g]

    def decode_kv_rows(self) -> Tuple[int, int]:
        """(read, pool): the pool rows the next decode round's attention
        reads through `kernels.decode_attn` (each slot's live rows,
        rounded up to whole blocks, in every kernel layer), and the rows
        its carried layers hold (slots x max_len each)."""
        from repro.kernels.decode_attn import live_rows

        read = live_rows(self.slot_pos, self.max_len, self._kv_rows).sum()
        return (self.decode_attn_kernel_layers * int(read),
                self.kv_inplace_layers * self.n_slots * self.max_len)

    def decode_round(self) -> np.ndarray:
        """One greedy decode step for the whole pool (idle slots ride
        along masked by their own fill level; their output is ignored)."""
        jnp = self._jnp
        tel = self.telemetry
        with (tel.span("decode.dispatch") if tel is not None else NOSPAN):
            tok = jnp.asarray(self.slot_tokens[:, None], jnp.int32)
            pos = jnp.asarray(self.slot_pos, jnp.int32)
            if self.mesh is not None:
                import jax

                tok = jax.device_put(tok, self._tok_shard)
                pos = jax.device_put(pos, self._pos_shard)
            with self._ctx():
                logits, self.caches = self._decode(self.params,
                                                   self.caches, tok, pos)
        nxt, lg = self._sample(logits, "decode.fetch", "decode.sample")
        self.slot_tokens = nxt.astype(np.int64)
        self.slot_pos += 1
        self.last_decode_logits = lg
        return nxt

    def warmup(self) -> int:
        """Compile every steady-state executable: (G, P) prefills +
        inserts, and the pool decode.  The sentinel-slot inserts and the
        zero-position decode leave no live state behind (idle rows are
        fully overwritten on first real admission).  Warm-up is not
        served work: it records no spans."""
        tel, self.telemetry = self.telemetry, None
        try:
            return self._warmup()
        finally:
            self.telemetry = tel

    def _warmup(self) -> int:
        jnp = self._jnp
        n = 0
        with self._ctx():
            for p_bkt in self.prompt_buckets:
                for g_bkt in self.group_buckets:
                    toks = jnp.zeros((g_bkt, p_bkt), jnp.int32)
                    lens = jnp.full((g_bkt,), p_bkt, jnp.int32)
                    logits, grp = self._prefill(self.params, toks, lens)
                    sent = jnp.full((g_bkt,), self.n_slots, jnp.int32)
                    self.caches = self._insert(self.caches, grp, sent)
                    self._fetch(logits)    # compiles the sampling slice
                    n += 1
        self.decode_round()                # pool decode (+ sampling slice)
        self.slot_tokens[:] = 0            # zero-position warm decode
        self.slot_pos[:] = 0               # leaves no live state behind
        return n + 1


# ---------------------------------------------------------------------------
# Scheduler + engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Running:
    req: Request
    result: RequestResult


class _Lane:
    def __init__(self, name: str, backend):
        self.name = name
        self.backend = backend
        self.queue: deque = deque()
        self.free: List[int] = list(range(backend.n_slots))
        self.running: Dict[int, _Running] = {}
        self.sentinel = None          # LaneSentinel (DESIGN.md §14)
        self.quarantined = False      # breaker open: no admit, no decode
        self.emitted = 0              # tokens since last trip/recovery
        self.total_emitted = 0        # tokens ever (never reset)
        self.n_retries = 0            # restarts this lane's trips caused


class ServingEngine:
    """Continuous-batching scheduler over per-tier slot-pool lanes.

    `lanes` maps tier name -> backend (LMLaneBackend in production; the
    tests drive the scheduler with a fake backend).  `continuous=False`
    degrades admission to static batching — a lane only admits when it
    is fully drained (the lockstep baseline the benchmark compares
    against); everything else (grouped prefill, decode, eviction) is
    shared, so the comparison isolates the scheduling policy.
    """

    def __init__(self, lanes: Dict[str, object], router, *,
                 continuous: bool = True,
                 token_budget: Optional[int] = None,
                 record_logits: bool = False,
                 check_invariants: bool = False,
                 sentinels: Optional[Dict[str, object]] = None,
                 max_queued: Optional[int] = None,
                 retry_budget: int = 3,
                 retry_backoff_s: float = 0.0,
                 telemetry=None):
        if not lanes:
            raise ValueError("need at least one lane")
        self.lanes = {name: _Lane(name, b) for name, b in lanes.items()}
        self.router = router
        self.continuous = continuous
        self.token_budget = token_budget
        self.record_logits = record_logits
        self.check_invariants = check_invariants
        self.max_queued = max_queued
        self.retry_budget = int(retry_budget)
        self.retry_backoff_s = float(retry_backoff_s)
        for name, sen in (sentinels or {}).items():
            self.lanes[name].sentinel = sen
        self.telemetry = telemetry               # obs.EngineTelemetry
        for lane in self.lanes.values():
            if hasattr(lane.backend, "telemetry"):
                lane.backend.telemetry = telemetry
            if telemetry is not None:
                telemetry.on_lane(lane.name, lane.backend)
        if telemetry is not None:
            # the telemetry reads this engine's clock without keeping
            # the engine (and its KV pools) alive
            span_now = weakref.WeakMethod(self._span_now)
            telemetry.now = lambda: span_now()()
        self.results: Dict[int, RequestResult] = {}
        self.active_tokens = 0
        self.peak_running = 0
        self.trip_log: List[TripEvent] = []      # one entry per trip
        self.last_run_s: Optional[float] = None  # engine-clock duration
        self._deferred: List[Tuple[float, Request]] = []   # backoff queue
        self._expected: Dict[str, int] = {}
        self._trace_mark: Optional[int] = None
        self._clock = None                       # set by run()
        self._tick_now = 0.0                     # the current tick's `now`

    # -- warmup / retrace probe -------------------------------------------
    def warmup(self) -> int:
        """Pre-warm every (tier x bucket) executable — including each
        sentinel's shadow scorer — then arm the steady-state retrace
        probe, so trip/demote/recover cycles never retrace."""
        n = sum(lane.backend.warmup() for lane in self.lanes.values()
                if hasattr(lane.backend, "warmup"))
        n += sum(lane.sentinel.warmup(lane.backend)
                 for lane in self.lanes.values()
                 if lane.sentinel is not None
                 and hasattr(lane.sentinel, "warmup"))
        if self.telemetry is not None:
            # eval_shape MAC profiling may trace; it must finish before
            # the steady-state retrace probe arms
            self.telemetry.on_warmup(self)
        from repro.core.approx_gemm import trace_count

        self._trace_mark = trace_count()
        return n

    def steady_retraces(self) -> int:
        """Dispatch-engine traces since warmup(); 0 in steady state."""
        if self._trace_mark is None:
            raise RuntimeError("call warmup() first")
        from repro.core.approx_gemm import trace_count

        return trace_count() - self._trace_mark

    # -- submission --------------------------------------------------------
    def _route_name(self, req: Request) -> str:
        """Route honoring quarantines: tripped lanes are passed to the
        router as `avoid` so pinned requests demote to the next-feasible
        rung (routers without the kwarg never see quarantine — it only
        arises on sentinel-guarded lanes, which build_engine always
        pairs with a TierRouter)."""
        avoid = {n for n, l in self.lanes.items() if l.quarantined}
        if avoid:
            tier = self.router.route(req.tolerance, req.tier,
                                     avoid=avoid)
        else:
            tier = self.router.route(req.tolerance, req.tier)
        return tier.name if hasattr(tier, "name") else str(tier)

    def submit(self, req: Request) -> str:
        """Route + enqueue; returns the tier name it was routed to.
        A rid may be reused only after its previous request completed
        (its result is replaced) — a live duplicate would alias two
        slots onto one RequestResult and corrupt the accounting.

        With `max_queued` set, submission is bounded: once that many
        requests sit in arrival queues (admitted/running requests do
        not count — they are bounded by the slot pools and the token
        budget), further submits raise `AdmissionRejected` instead of
        growing the queues without limit."""
        prev = self.results.get(req.rid)
        if prev is not None and not prev.done:
            raise ValueError(
                f"request id {req.rid} is already queued or running")
        if self.max_queued is not None:
            queued = (sum(len(l.queue) for l in self.lanes.values())
                      + len(self._deferred))
            if queued >= self.max_queued:
                raise AdmissionRejected(req.rid, queued, self.max_queued)
        name = self._route_name(req)
        lane = self.lanes[name]
        b = lane.backend
        if hasattr(b, "max_len") and req.cost > b.max_len:
            raise ValueError(
                f"request {req.rid}: prompt+max_new = {req.cost} exceeds "
                f"lane max_len {b.max_len}")
        if hasattr(b, "prompt_bucket"):
            b.prompt_bucket(len(req.prompt))    # raises if unbucketable
        if self.token_budget is not None and req.cost > self.token_budget:
            raise ValueError(
                f"request {req.rid}: cost {req.cost} exceeds the engine "
                f"token budget {self.token_budget}")
        lane.queue.append(req)
        if name in self._expected and self._expected[name] > 0:
            self._expected[name] -= 1
        self.results[req.rid] = RequestResult(
            rid=req.rid, tier=name, prompt_len=len(req.prompt),
            arrival=req.arrival,
            logits=[] if self.record_logits else None)
        return name

    # -- scheduling --------------------------------------------------------
    def _budget_ok(self, req: Request) -> bool:
        return (self.token_budget is None
                or self.active_tokens + req.cost <= self.token_budget)

    def _admit_lane(self, lane: _Lane, now: float) -> None:
        if not lane.queue or not lane.free:
            return
        if not self.continuous:
            # static batching: wait for a full drain, then (if more
            # traffic for this tier is still inbound) a full batch
            if lane.running:
                return
            if (len(lane.queue) < lane.backend.n_slots
                    and self._expected.get(lane.name, 0) > 0):
                return
        taken: List[Tuple[Request, int]] = []
        while lane.queue and lane.free:
            req = lane.queue[0]
            if not self._budget_ok(req):
                break                  # FIFO head blocks: no starvation
            lane.queue.popleft()
            slot = lane.free.pop(0)
            self.active_tokens += req.cost
            taken.append((req, slot))
        if not taken:
            return
        # register every taken request as running BEFORE touching the
        # backend: if a prefill raises LaneHealthError mid-chunk, the
        # trip path sees all of them in `running` and requeues them
        # uniformly (no orphans between popped-queue and admitted)
        for req, slot in taken:
            rr = self.results[req.rid]
            rr.t_admit = now
            lane.running[slot] = _Running(req, rr)
        # group by prompt bucket (one traced shape per admit call),
        # chunked to the largest pre-warmed group bucket
        tel = self.telemetry
        groups: Dict[int, List[Tuple[Request, int]]] = {}
        for req, slot in taken:
            pb = (lane.backend.prompt_bucket(len(req.prompt))
                  if hasattr(lane.backend, "prompt_bucket")
                  else len(req.prompt))
            groups.setdefault(pb, []).append((req, slot))
        max_g = getattr(lane.backend, "max_group", lane.backend.n_slots)
        for pb, members in groups.items():
            for i in range(0, len(members), max_g):
                chunk = members[i:i + max_g]
                prompts = [r.prompt for r, _ in chunk]
                slots = [s for _, s in chunk]
                if tel is None:
                    first = lane.backend.admit(prompts, slots)
                else:
                    with tel.span("admit", lane.name,
                                  rows=_admit_rows(lane.backend, len(chunk),
                                                   pb),
                                  useful=sum(len(p) for p in prompts)):
                        first = lane.backend.admit(prompts, slots)
                    tel.on_prefill(lane.name, len(chunk), pb,
                                   [r.rid for r, _ in chunk])
                t = self._now_fine(now)
                pre_lg = getattr(lane.backend, "last_prefill_logits",
                                 None)
                for j, (req, slot) in enumerate(chunk):
                    lg = (pre_lg[j] if self.record_logits
                          and pre_lg is not None else None)
                    self._emit(lane, slot, int(first[j]), t, lg)
        self.peak_running = max(self.peak_running,
                                sum(len(l.running) for l in
                                    self.lanes.values()))

    def _emit(self, lane: _Lane, slot: int, tok: int, now: float,
              logits_row=None) -> None:
        """Hand one token to its request; `now` is when the backend
        call that made it returned."""
        run = lane.running[slot]
        rr = run.result
        rr.tokens.append(tok)
        lane.emitted += 1
        lane.total_emitted += 1
        if self.telemetry is not None:
            self.telemetry.on_token(lane.name)
        if rr.t_first is None:
            rr.t_first = now
        if rr.logits is not None and logits_row is not None:
            rr.logits.append(logits_row)
        if (len(rr.tokens) >= run.req.max_new
                or (run.req.eos_id is not None
                    and tok == run.req.eos_id)):
            rr.t_done = now
            self.active_tokens -= run.req.cost
            del lane.running[slot]
            bisect.insort(lane.free, slot)     # eviction frees capacity
            if self.telemetry is not None:
                self.telemetry.on_request_done(rr, lane.name)

    def _now_fine(self, now: float) -> float:
        """Sub-tick timestamp: the run() clock when one is live, else
        the tick's own `now` (stamps stay on the tick under direct
        step() driving — deterministic tests)."""
        return self._clock.now() if self._clock is not None else now

    def _span_now(self) -> float:
        """The telemetry's clock: `_now_fine` of the current tick."""
        return self._now_fine(self._tick_now)

    def step(self, now: Optional[float] = None) -> List[RequestResult]:
        """One scheduler tick: release due backoff requeues, probe
        quarantined lanes whose cooldown expired, admit, then one
        decode round per lane with live slots (a speculative round on
        spec-decode lanes).  On sentinel-guarded lanes the round is
        shadow-scored every period-th tick, and a trip (drift out of
        envelope, or a LaneHealthError from the sampling path) is
        handled BEFORE the round's tokens are emitted — a tripped
        round's output never reaches a result (DESIGN.md §14).
        Returns results completed this tick."""
        now = 0.0 if now is None else now
        self._tick_now = now
        tel = self.telemetry
        with (tel.span("step") if tel is not None else NOSPAN):
            return self._tick(now)

    def _tick(self, now: float) -> List[RequestResult]:
        tel = self.telemetry
        done_before = {rid for rid, r in self.results.items() if r.done}
        if self._deferred:
            due = [d for d in self._deferred if d[0] <= now]
            if due:
                self._deferred = [d for d in self._deferred
                                  if d[0] > now]
                for _, req in due:
                    self._requeue(req)
        for lane in self.lanes.values():
            if lane.quarantined:
                self._maybe_probe(lane, now)
                continue
            try:
                self._admit_lane(lane, now)
            except LaneHealthError as e:
                if lane.sentinel is None:
                    raise
                self._trip(lane, now, str(e))
        for lane in self.lanes.values():
            if lane.quarantined or not lane.running:
                continue
            if hasattr(lane.backend, "spec_round"):
                self._spec_round(lane, now)
                continue
            sen = lane.sentinel
            shadow = None
            if sen is not None and sen.due():
                # exact reference for the CURRENT state — must precede
                # the lane's own decode, which donates the caches
                shadow = sen.shadow(lane.backend)
            try:
                if tel is None:
                    nxt = lane.backend.decode_round()
                else:
                    kv_rows = (lane.backend.decode_kv_rows()
                               if hasattr(lane.backend, "decode_kv_rows")
                               else None)
                    with tel.span("decode_round", lane.name,
                                  rows=lane.backend.n_slots,
                                  useful=len(lane.running)) as sp:
                        nxt = lane.backend.decode_round()
                    tel.on_decode_round(
                        lane.name, [r.result.rid for r in
                                    lane.running.values()], sp.dur,
                        kv_rows)
            except LaneHealthError as e:
                if sen is None:
                    raise
                self._trip(lane, now, str(e))
                continue
            t = self._now_fine(now)
            if shadow is not None:
                tripped = sen.observe(
                    lane.backend.last_decode_logits, shadow,
                    sorted(lane.running), now)
                if (self.telemetry is not None
                        and sen.last_agree is not None):
                    self.telemetry.on_sentinel(lane.name, sen.last_agree,
                                               sen.last_nmed)
                if tripped:
                    self._trip(lane, now, sen.last_trip_reason,
                               breaker_tripped=True)
                    continue           # trip-before-emit
            dec_lg = getattr(lane.backend, "last_decode_logits", None)
            for slot in sorted(lane.running):
                lg = (dec_lg[slot] if self.record_logits
                      and dec_lg is not None else None)
                self._emit(lane, slot, int(nxt[slot]), t, lg)
        if self.check_invariants:
            self._check()
        return [r for rid, r in self.results.items()
                if r.done and rid not in done_before]

    # -- fault containment (DESIGN.md §14) ---------------------------------
    def _safest_lane(self) -> str:
        """Healthy lane with the tightest characterized NMED (the
        "exact lane" of the ISSUE contract; in a custom assembly,
        whatever healthy rung is safest)."""
        ok = [n for n, l in self.lanes.items() if not l.quarantined]
        if not ok:
            raise RuntimeError("every lane is quarantined")
        tiers = getattr(self.router, "tiers", None)
        if tiers:
            ok.sort(key=lambda n: tiers[n].nmed if n in tiers
                    else float("inf"))
            return ok[0]
        return "exact" if "exact" in ok else ok[0]

    def _requeue(self, req: Request) -> None:
        """Re-enqueue a displaced request on the safest healthy lane
        (bypasses submit: its RequestResult — retry count included —
        survives the restart)."""
        name = self._safest_lane()
        self.results[req.rid].tier = name
        self.lanes[name].queue.append(req)

    def _trip(self, lane: _Lane, now: float, reason: str,
              breaker_tripped: bool = False) -> None:
        """Quarantine `lane` and displace all of its work: queued
        requests re-route untouched (they never ran on the faulty
        datapath); in-flight requests RESTART — emitted tokens are
        discarded (they are fault-suspect) and the request re-prefills
        from its prompt on the safest healthy lane, so its final output
        is token-for-token what an exact-lane-only run produces.  Each
        restart spends one unit of the retry budget; exhaustion marks
        the result "failed" instead of looping forever."""
        if lane.sentinel is not None and not breaker_tripped:
            lane.sentinel.record_failure(now, reason)
        lane.quarantined = True
        displaced = len(lane.running)
        sen = lane.sentinel
        trigger = getattr(sen, "last_trip_stats", None) if sen else None
        after = (sen.breaker.state if sen is not None
                 and hasattr(sen, "breaker") else "tripped")
        ev = TripEvent(
            lane=lane.name, t=now, reason=reason,
            tokens_before_trip=lane.emitted,
            in_flight_displaced=displaced,
            trigger_agree=trigger[0] if trigger else None,
            trigger_nmed=trigger[1] if trigger else None,
            breaker_before="healthy", breaker_after=after)
        self.trip_log.append(ev)
        if self.telemetry is not None:
            self.telemetry.on_trip(ev)
            self.telemetry.on_breaker(lane.name, "healthy", after, now)
        lane.emitted = 0
        while lane.queue:
            self._requeue(lane.queue.popleft())
        for slot in sorted(lane.running):
            run = lane.running.pop(slot)
            bisect.insort(lane.free, slot)
            self.active_tokens -= run.req.cost
            rr = run.result
            lane.n_retries += 1
            if self.telemetry is not None:
                self.telemetry.on_request_retry(rr, lane.name, now)
            rr.tokens.clear()
            if rr.logits is not None:
                rr.logits.clear()
            rr.t_admit = rr.t_first = None
            rr.retries += 1
            if rr.retries > self.retry_budget:
                rr.status = "failed"
                rr.t_done = now
                if self.telemetry is not None:
                    self.telemetry.on_request_done(rr, lane.name)
                continue
            delay = self.retry_backoff_s * (2 ** (rr.retries - 1))
            if delay > 0:
                self._deferred.append((now + delay, run.req))
            else:
                self._requeue(run.req)

    def _maybe_probe(self, lane: _Lane, now: float) -> None:
        """Half-open re-admission: once the cooldown expires (and the
        lane is fully drained), run the sentinel's verification burst
        in a free slot; a clean burst lifts the quarantine."""
        sen = lane.sentinel
        if (sen is None or lane.running or not lane.free
                or not sen.breaker.should_probe(now)):
            return
        if self.telemetry is not None:
            self.telemetry.on_breaker(lane.name, "tripped", "half_open",
                                      now)
        ok = sen.probe(lane.backend, lane.free[0], now)
        if self.telemetry is not None:
            self.telemetry.on_breaker(
                lane.name, "half_open", "healthy" if ok else "tripped",
                now)
        if ok:
            lane.quarantined = False
            lane.emitted = 0

    def _spec_round(self, lane: _Lane, now: float) -> None:
        """One spec call: up to rounds_per_call draft+verify rounds, up
        to k+1 tokens each, per live slot.  The backend truncates each
        slot's emission at its remaining budget and first EOS (a slot
        that finishes mid-call idles for the remaining rounds), so
        per-slot emission order (and thus eviction accounting) is
        exactly the sequential-decode order.  Backends returning the
        single-round (B, k+1)/(B,) shapes are treated as one round."""
        b = lane.backend
        remaining = np.zeros(b.n_slots, np.int64)
        eos = np.full(b.n_slots, -1, np.int64)
        for slot, run in lane.running.items():
            remaining[slot] = run.req.max_new - len(run.result.tokens)
            if run.req.eos_id is not None:
                eos[slot] = run.req.eos_id
        tel = self.telemetry
        if tel is None:
            toks, counts = b.spec_round(remaining, eos)
        else:
            pre = ((b.n_rounds, b.n_drafted, b.n_accepted, b.n_emitted)
                   if hasattr(b, "n_rounds") else None)
            with tel.span("spec_round", lane.name) as sp:
                toks, counts = b.spec_round(remaining, eos)
                if pre is not None:
                    k = getattr(b, "draft_k", 0)
                    d_rounds = b.n_rounds - pre[0]
                    sp.labels.update(k=k, rounds=d_rounds,
                                     emitted=b.n_emitted - pre[3])
            if pre is not None:
                tel.on_spec_round(
                    lane.name, k, d_rounds, b.n_drafted - pre[1],
                    b.n_accepted - pre[2],
                    [r.result.rid for r in lane.running.values()], sp.dur)
        t = self._now_fine(now)
        toks, counts = np.asarray(toks), np.asarray(counts)
        lg = getattr(b, "last_spec_logits", None)
        if counts.ndim == 1:
            toks, counts = toks[:, None, :], counts[:, None]
            lg = lg[:, None] if lg is not None else None
        slots = sorted(lane.running)
        for r in range(counts.shape[1]):
            for slot in slots:
                for i in range(int(counts[slot, r])):
                    row = (lg[slot, r, i] if self.record_logits
                           and lg is not None else None)
                    self._emit(lane, slot, int(toks[slot, r, i]), t, row)

    def _check(self) -> None:
        total = 0
        for lane in self.lanes.values():
            free, busy = set(lane.free), set(lane.running)
            assert not free & busy, f"lane {lane.name}: slot both free+busy"
            assert free | busy == set(range(lane.backend.n_slots)), \
                f"lane {lane.name}: slot leak"
            total += sum(r.req.cost for r in lane.running.values())
        assert total == self.active_tokens, "token budget drifted"
        assert self.active_tokens >= 0
        assert (self.token_budget is None
                or self.active_tokens <= self.token_budget), \
            "admission exceeded the token budget"

    # -- the serving loop --------------------------------------------------
    def run(self, requests: Sequence[Request], clock=None,
            max_steps: int = 1_000_000) -> Dict[int, RequestResult]:
        """Serve a workload to completion against a clock (RealClock by
        default; SimClock for deterministic tests).  Arrival times are
        engine-clock seconds; the loop admits, decodes, and — when fully
        idle with future arrivals pending — waits.  Returns the results
        of *this* workload (the engine is reusable across runs)."""
        if clock is None:
            from .workload import RealClock

            clock = RealClock()
        self._clock = clock              # one time source per run:
        t_run0 = clock.now()             # spans + stats stay coherent
        submitted = [r.rid for r in requests]
        pending = deque(sorted(requests, key=lambda r: r.arrival))
        self.peak_running = sum(len(l.running)                 # per-run
                                for l in self.lanes.values())
        self._expected = {}
        for r in pending:
            t = self.router.route(r.tolerance, r.tier)
            name = t.name if hasattr(t, "name") else str(t)
            self._expected[name] = self._expected.get(name, 0) + 1
        for _ in range(max_steps):
            now = clock.now()
            while pending and pending[0].arrival <= now:
                try:
                    self.submit(pending[0])
                except AdmissionRejected:
                    break          # backpressure: hold further arrivals
                pending.popleft()
            self.step(now)
            busy = any(l.running for l in self.lanes.values())
            queued = any(l.queue for l in self.lanes.values())
            if (not pending and not busy and not queued
                    and not self._deferred):
                self.last_run_s = clock.now() - t_run0
                return {rid: self.results[rid] for rid in submitted}
            if not busy and (pending or self._deferred):
                targets = [r.arrival for r in list(pending)[:1]]
                targets += [t for t, _ in self._deferred]
                clock.wait_until(min(targets))
        raise RuntimeError("engine did not drain the workload "
                           f"within {max_steps} steps")

    # -- telemetry snapshot ------------------------------------------------
    def metrics(self) -> dict:
        """Structured per-lane serving metrics (DESIGN.md §15): tokens,
        throughput (over `last_run_s`), sentinel trips/retries, spec
        acceptance, and — with an `EngineTelemetry` attached — the
        estimated energy per token from the paper's per-MAC anchors.
        Works without telemetry (energy fields are then None)."""
        dur = self.last_run_s
        lanes = {}
        for name, lane in self.lanes.items():
            b = lane.backend
            d = {
                "tokens": lane.total_emitted,
                "tokens_per_s": (lane.total_emitted / dur
                                 if dur else None),
                "trips": sum(1 for t in self.trip_log
                             if t["lane"] == name),
                "retries": lane.n_retries,
                "quarantined": lane.quarantined,
                "energy_j": None,
                "energy_per_token_j": None,
                "acceptance_rate": None,
                "tokens_per_round": None,
                "draft_k": None,
            }
            if hasattr(b, "acceptance_rate"):
                d["acceptance_rate"] = b.acceptance_rate
                d["tokens_per_round"] = b.tokens_per_round
                d["draft_k"] = getattr(b, "draft_k", None)
            if self.telemetry is not None:
                m = self.telemetry.meters.get(name)
                if m is not None and m.profiled:
                    d["energy_j"] = m.energy_j
                    d["energy_per_token_j"] = m.energy_per_token_j
                    d["macs"] = m.macs
            lanes[name] = d
        n_done = sum(1 for r in self.results.values() if r.done)
        out = {
            "duration_s": dur,
            "n_requests": n_done,
            "n_failed": sum(1 for r in self.results.values()
                            if r.done and r.status != "ok"),
            "total_tokens": sum(d["tokens"] for d in lanes.values()),
            "peak_concurrency": self.peak_running,
            "steady_retraces": (self.steady_retraces()
                                if self._trace_mark is not None
                                else None),
            "lanes": lanes,
        }
        return out


# ---------------------------------------------------------------------------
# Production assembly
# ---------------------------------------------------------------------------


def build_engine(cfg, params=None, *, tiers=None, slots_per_tier: int = 4,
                 max_len: int = 128,
                 prompt_buckets: Sequence[int] = (16, 32),
                 group_buckets: Sequence[int] = (1, 2, 4),
                 continuous: bool = True,
                 token_budget: Optional[int] = None,
                 record_logits: bool = False,
                 spec_decode: Optional[int] = None,
                 spec_drafter: Optional[str] = None,
                 spec_ks: Optional[Sequence[int]] = None,
                 spec_rounds: int = 4,
                 fault=None,
                 sentinel: bool = False,
                 sentinel_cfg=None,
                 max_queued: Optional[int] = None,
                 retry_budget: int = 3,
                 retry_backoff_s: float = 0.0,
                 telemetry=None,
                 seed: int = 0, mesh=None) -> ServingEngine:
    """One lane per accuracy tier over shared weights.

    `cfg` is a ModelConfig (its own `cim` field is ignored — each lane
    replaces it with its tier's CiMConfig); `params` defaults to a
    fresh init (weights are tier-independent, so every lane shares
    them).  `tiers` defaults to the DSE ladder (serving/tiers.py).

    `spec_decode=k` turns the exact lane speculative (DESIGN.md §12):
    it decodes through a SpecDecodeBackend pairing `spec_drafter` (by
    default the cheapest approximate rung) with the exact tier upgraded
    to per-token activation scales — output is unchanged by
    construction, only faster.  `spec_ks` pre-warms extra draft depths
    so `set_draft_k` switches never retrace; `spec_rounds` batches that
    many rounds per dispatch (admission granularity trades against
    per-call overhead — see SpecDecodeBackend).  The verify logits are
    only pulled off-device when `record_logits` asks for them.

    With `mesh` every lane's slot pool is data-parallel sharded and the
    shared weights are placed TP-sharded once per `DECODE_RULES`
    (DESIGN.md §11); the scheduler is unchanged.

    `fault` (a `core.faults.FaultConfig`) injects as-fabricated
    stuck-at defects into every APPROXIMATE tier's stored tables and
    weight words — the tiers must run an integer mode
    (`faults.FAULT_MODES`); the exact tier stays clean, it is the
    containment target.  `sentinel=True` (or a `SentinelConfig` via
    `sentinel_cfg`) arms a per-approximate-lane accuracy sentinel with
    graceful degradation (DESIGN.md §14); `max_queued` /
    `retry_budget` / `retry_backoff_s` bound admission and restarts.
    `telemetry` (an `obs.EngineTelemetry`) threads the runtime
    telemetry spine through warmup and serving (DESIGN.md §15).
    """
    import dataclasses as dc

    import jax

    from repro.models.transformer import LM

    from .tiers import TierRouter, build_tiers

    check_engine_arch(cfg)
    if fault is not None and mesh is not None:
        raise ValueError(
            "fault injection does not compose with mesh execution: the "
            "shard_map kernels quantize their words in-kernel and "
            "cannot see the defect map (DESIGN.md §14); drop the mesh "
            "or the fault config")
    if tiers is None:
        tiers = build_tiers()
    if fault is not None:
        tiers = tuple(
            t if t.name == "exact" or t.cim is None
            else dc.replace(t, cim=dc.replace(t.cim, fault=fault))
            for t in tiers)
    d_tier = None
    if spec_decode is not None:
        from .tiers import spec_pair

        d_tier, v_tier = spec_pair(tiers, spec_drafter)
        # the router still routes by name; only the exact rung's
        # numerics change (per-token scales are a QAT-equivalent
        # refinement, not a different multiplier)
        tiers = tuple(v_tier if t.name == "exact" else t for t in tiers)
    if params is None:
        params = LM(cfg).init(jax.random.PRNGKey(seed))
    if mesh is not None:
        from repro.parallel.sharding import DECODE_RULES, param_shardings

        # place the SHARED weights once; per-lane device_puts are then
        # no-ops onto the same buffers
        params = jax.device_put(
            params, param_shardings(LM(cfg), params, mesh,
                                    rules=DECODE_RULES))
    lanes = {}
    for tier in tiers:
        lm = LM(dc.replace(cfg, cim=tier.cim))
        if spec_decode is not None and tier.name == "exact":
            from .spec import SpecDecodeBackend

            lanes[tier.name] = SpecDecodeBackend(
                lm, LM(dc.replace(cfg, cim=d_tier.cim)), params,
                draft_k=spec_decode, draft_ks=spec_ks,
                rounds_per_call=spec_rounds, keep_logits=record_logits,
                n_slots=slots_per_tier, max_len=max_len,
                prompt_buckets=prompt_buckets,
                group_buckets=group_buckets, mesh=mesh)
            continue
        lanes[tier.name] = LMLaneBackend(
            lm, params, n_slots=slots_per_tier, max_len=max_len,
            prompt_buckets=prompt_buckets, group_buckets=group_buckets,
            mesh=mesh)
    sentinels = None
    if sentinel or sentinel_cfg is not None:
        from .sentinel import LaneSentinel, reference_lm

        by_name = {t.name: t for t in tiers}
        if "exact" not in by_name:
            raise ValueError("sentinels need an 'exact' tier as the "
                             "shadow-scoring reference and demotion "
                             f"target; configured: {sorted(by_name)}")
        ref_lm = reference_lm(cfg, by_name["exact"].cim)
        sentinels = {t.name: LaneSentinel(ref_lm, params, t.nmed,
                                          sentinel_cfg)
                     for t in tiers
                     if t.name != "exact" and t.cim is not None}
    return ServingEngine(lanes, TierRouter(tiers), continuous=continuous,
                         token_budget=token_budget,
                         record_logits=record_logits,
                         sentinels=sentinels, max_queued=max_queued,
                         retry_budget=retry_budget,
                         retry_backoff_s=retry_backoff_s,
                         telemetry=telemetry)
