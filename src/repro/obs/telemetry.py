"""EngineTelemetry: the serving engine's obs hub (DESIGN.md §15).

One object wires the whole telemetry spine together:

  * installs itself as the **dispatch-boundary sink**
    (`core/approx_gemm.set_obs_sink` + `core/autotune.set_obs_sink`):
    executable-cache hit/miss and kernel-family invocation counters,
    retrace events, autotune mem/disk-cache resolution events;
  * owns one `LaneEnergyMeter` per lane (profiled at engine warmup,
    before the retrace probe arms) and attributes estimated Joules to
    lanes *and* live requests per scheduler event;
  * records per-request lifecycle spans (queue-wait -> prefill ->
    decode, plus retry spans on sentinel trips) into the registry's
    span ring, and times the program's own phases with `span()`: the
    scheduler tick (`step`), each grouped prefill (`admit` ->
    `prefill.dispatch` / `prefill.fetch` / `prefill.sample`) and each
    pool decode (`decode_round` -> `decode.*`, or `spec_round`).  Each
    such span is also a `jax.profiler.TraceAnnotation`, so it sits in a
    profiler trace on the device trace's clock; `obs/export.
    chrome_trace` renders the ring for Perfetto;
  * folds sentinel scores, breaker transitions, and structured
    `TripEvent`s into gauges/counters and the event ring.

Every hook is a host-side dict update gated on ``registry.enabled``.
An engine without telemetry pays one `is None` branch per span site.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

from jax.profiler import TraceAnnotation

from .energy import LaneEnergyMeter
from .metrics import MetricsRegistry, Span

# what a span site enters when it has no telemetry (stateless, shared)
NOSPAN = contextlib.nullcontext()

# span-duration histogram buckets (seconds): microseconds to minutes
_TIME_BUCKETS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3, 1.0,
                 3.0, 10.0, 30.0, 120.0)


class EngineTelemetry:
    """Telemetry hub for one `ServingEngine` (pass as its `telemetry=`).

    `energy=False` skips the eval_shape MAC profiling (and all Joule
    attribution); `attach=False` leaves the global dispatch/autotune
    sinks untouched (scoped tests).  Call `detach()` when discarding a
    telemetry object that was attached — the dispatch sink is global.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 energy: bool = True, attach: bool = True,
                 span_capacity: int = 8192, event_capacity: int = 4096):
        self.registry = registry or MetricsRegistry(
            span_capacity=span_capacity, event_capacity=event_capacity)
        r = self.registry
        self.dispatch_calls = r.counter(
            "repro_dispatch_calls_total",
            "traced dispatches: eager frontend calls and jit traces "
            "(a compiled step's replays are not counted)")
        self.kernel_calls = r.counter(
            "repro_dispatch_kernel_calls_total",
            "traced dispatches by routed registry kernel")
        self.dispatch_macs = r.counter(
            "repro_dispatch_macs_total",
            "MACs of the traced dispatches (once per trace, not per run)")
        self.retraces = r.counter(
            "repro_dispatch_retraces_total",
            "executable traces (trace_count probe)")
        self.autotune_c = r.counter(
            "repro_autotune_resolutions_total",
            "autotune block resolutions by cache outcome")
        self.char_cache_c = r.counter(
            "repro_char_cache_resolutions_total",
            "multiplier characterizations by cache outcome")
        self.alloc_search_c = r.counter(
            "repro_alloc_search_evals_total",
            "allocation-search evaluator spend by stage")
        self.requests_c = r.counter(
            "repro_serving_requests_total", "completed requests")
        self.tokens_c = r.counter(
            "repro_serving_tokens_total", "emitted tokens")
        self.prefills_c = r.counter(
            "repro_serving_prefills_total", "grouped prefill calls")
        self.decode_rounds_c = r.counter(
            "repro_serving_decode_rounds_total", "pool decode rounds")
        self.retries_c = r.counter(
            "repro_serving_retries_total",
            "request restarts after sentinel trips")
        self.trips_c = r.counter(
            "repro_serving_sentinel_trips_total", "sentinel trips")
        self.breaker_c = r.counter(
            "repro_serving_breaker_transitions_total",
            "circuit-breaker state transitions")
        self.spec_rounds_c = r.counter(
            "repro_serving_spec_subrounds_total",
            "executed speculative draft+verify sub-rounds")
        self.spec_drafted_c = r.counter(
            "repro_serving_spec_drafted_total", "drafted tokens")
        self.spec_accepted_c = r.counter(
            "repro_serving_spec_accepted_total",
            "drafted tokens the verifier accepted")
        self.queue_wait_h = r.histogram(
            "repro_serving_queue_wait_seconds", _TIME_BUCKETS,
            "arrival -> admission wait")
        self.ttft_h = r.histogram(
            "repro_serving_ttft_seconds", _TIME_BUCKETS,
            "arrival -> first token")
        self.decode_h = r.histogram(
            "repro_serving_decode_round_seconds", _TIME_BUCKETS,
            "wall time of one pool decode / spec call")
        self.agree_g = r.gauge(
            "repro_serving_sentinel_agree",
            "rolling argmax agreement per sentinel lane")
        self.nmed_g = r.gauge(
            "repro_serving_sentinel_nmed",
            "rolling logit NMED per sentinel lane")
        self.kv_inplace_g = r.gauge(
            "repro_serving_kv_inplace_layers",
            "layers whose KV the lane's compiled prefill and decode write "
            "in place")
        self.energy_g = r.gauge(
            "repro_serving_energy_joules",
            "estimated energy attributed per lane")
        self.ept_g = r.gauge(
            "repro_serving_energy_per_token_joules",
            "estimated energy per emitted token per lane")
        self.energy_enabled = bool(energy)
        self.meters: Dict[str, LaneEnergyMeter] = {}
        self.request_energy_j: Dict[int, float] = {}
        self._tids: Dict[str, int] = {}
        self._open: List["_OpenSpan"] = []       # program spans, nested
        self._next_sid = 0
        self.now: Callable[[], float] = lambda: 0.0   # the engine's clock
        self._attached = False
        if attach:
            self.attach()

    # -- global sink management --------------------------------------------
    def attach(self) -> None:
        from repro.core import allocate, approx_gemm, autotune, error_model

        approx_gemm.set_obs_sink(self)
        autotune.set_obs_sink(self)
        error_model.set_obs_sink(self)
        allocate.set_obs_sink(self)
        self._attached = True

    def detach(self) -> None:
        from repro.core import allocate, approx_gemm, autotune, error_model

        if self._attached:
            approx_gemm.set_obs_sink(None)
            autotune.set_obs_sink(None)
            error_model.set_obs_sink(None)
            allocate.set_obs_sink(None)
            self._attached = False

    # -- dispatch sink protocol (approx_gemm / autotune) -------------------
    def dispatch(self, op: str, family: str, mode: str, bits: int,
                 macs: float, cache_hit: bool, kernel: str = "") -> None:
        labels = {"op": op, "family": family, "mode": mode,
                  "bits": bits, "cache": "hit" if cache_hit else "miss"}
        self.dispatch_calls.inc(1, **labels)
        self.dispatch_macs.inc(macs, op=op, family=family, bits=bits)
        if kernel:
            self.kernel_calls.inc(1, kernel=kernel)

    def retrace(self) -> None:
        self.retraces.inc(1)

    def autotune(self, key: str, outcome: str) -> None:
        self.autotune_c.inc(1, outcome=outcome)

    def char_cache(self, key: str, outcome: str) -> None:
        self.char_cache_c.inc(1, outcome=outcome)

    def alloc_search(self, event: str, count: int) -> None:
        self.alloc_search_c.inc(count, event=event)

    # -- program spans ------------------------------------------------------
    def span(self, name: str, lane: Optional[str] = None, **labels):
        """Time one phase of the program: a context manager that opens a
        profiler `TraceAnnotation(name)` and, on exit, appends the span
        to the ring on the engine clock (`self.now`), with its parent
        (the span open around it when it started).  `lane` puts it on
        that lane's trace row; otherwise it shares its parent's row (the
        scheduler's row at the top).  The `_OpenSpan` it returns takes
        labels until it closes; a disabled registry keeps none."""
        if lane is not None:
            labels["lane"] = lane
            tid = self._tid(lane)
        else:
            tid = (self._open[-1].tid if self._open
                   else self._row("scheduler"))
        return _OpenSpan(self, name, tid, labels)

    def _row(self, key: str) -> int:
        """Stable negative trace row per lane or the scheduler (request
        rows are >= 0)."""
        tid = self._tids.get(key)
        if tid is None:
            tid = -(len(self._tids) + 1)
            self._tids[key] = tid
        return tid

    def _tid(self, lane: str) -> int:
        return self._row(f"lane {lane}")

    @property
    def tid_names(self) -> Dict[int, str]:
        return {tid: key for key, tid in self._tids.items()}

    # -- engine lifecycle ---------------------------------------------------
    def on_lane(self, lane: str, backend) -> None:
        """Once per lane, when the engine is built: what its config
        fixes about the compiled executables."""
        n = getattr(backend, "kv_inplace_layers", None)
        if n is not None:
            self.kv_inplace_g.set(n, lane=lane)

    def on_warmup(self, engine) -> None:
        """Build the per-lane energy meters (eval_shape MAC profiling;
        cheap, abstract).  MUST run before the engine arms its
        steady-state retrace probe: abstract profiling may trace."""
        tiers = getattr(engine.router, "tiers", {}) or {}
        for name, lane in engine.lanes.items():
            fallback = None
            t = tiers.get(name)
            if t is not None:
                fallback = getattr(t, "energy_per_mac_j", None)
            meter = LaneEnergyMeter(name, fallback_j_per_mac=fallback)
            if self.energy_enabled:
                meter.build(lane.backend)
            self.meters[name] = meter
            self._tid(name)

    def _share(self, j: float, rids: Sequence[int]) -> None:
        if not rids or j == 0.0:
            return
        share = j / len(rids)
        for rid in rids:
            self.request_energy_j[rid] = \
                self.request_energy_j.get(rid, 0.0) + share

    def on_prefill(self, lane: str, n_prompts: int, prompt_len: int,
                   rids: Sequence[int]) -> None:
        if not self.registry.enabled:
            return
        self.prefills_c.inc(1, tier=lane)
        m = self.meters.get(lane)
        if m is not None:
            self._share(m.on_prefill(n_prompts, prompt_len), rids)
            self._update_energy(lane, m)

    def on_decode_round(self, lane: str, rids: Sequence[int],
                        dur: float) -> None:
        """After a `decode_round` span of `dur` seconds closed."""
        if not self.registry.enabled:
            return
        self.decode_rounds_c.inc(1, tier=lane)
        self.decode_h.observe(dur, tier=lane)
        m = self.meters.get(lane)
        if m is not None:
            self._share(m.on_decode(), rids)
            self._update_energy(lane, m)

    def on_spec_round(self, lane: str, k: int, d_rounds: int,
                      d_drafted: int, d_accepted: int,
                      rids: Sequence[int], dur: float) -> None:
        """After a `spec_round` span of `dur` seconds closed."""
        if not self.registry.enabled:
            return
        self.decode_h.observe(dur, tier=lane)
        self.spec_rounds_c.inc(d_rounds, tier=lane, k=k)
        self.spec_drafted_c.inc(d_drafted, tier=lane, k=k)
        self.spec_accepted_c.inc(d_accepted, tier=lane, k=k)
        m = self.meters.get(lane)
        if m is not None:
            self._share(m.on_spec_rounds(k, d_rounds), rids)
            self._update_energy(lane, m)

    def on_token(self, lane: str, n: int = 1) -> None:
        if not self.registry.enabled:
            return
        self.tokens_c.inc(n, tier=lane)
        m = self.meters.get(lane)
        if m is not None:
            m.add_tokens(n)

    def on_request_done(self, rr, lane: str) -> None:
        """Request lifecycle spans, emitted once at completion from the
        result's own engine-clock timestamps (tid = rid)."""
        if not self.registry.enabled:
            return
        self.requests_c.inc(1, tier=lane, status=rr.status)
        if rr.status != "ok" or rr.t_admit is None:
            self.registry.event("request_failed", rr.t_done or 0.0,
                                rid=rr.rid, tier=lane,
                                retries=rr.retries)
            return
        r = self.registry
        wait = max(rr.t_admit - rr.arrival, 0.0)
        self.queue_wait_h.observe(wait, tier=lane)
        r.span("queue", rr.arrival, wait, tid=rr.rid, tier=lane,
               rid=rr.rid)
        if rr.t_first is not None:
            self.ttft_h.observe(max(rr.t_first - rr.arrival, 0.0),
                                tier=lane)
            r.span("prefill", rr.t_admit,
                   max(rr.t_first - rr.t_admit, 0.0), tid=rr.rid,
                   tier=lane, rid=rr.rid)
            if rr.t_done is not None:
                r.span("decode", rr.t_first,
                       max(rr.t_done - rr.t_first, 0.0), tid=rr.rid,
                       tier=lane, rid=rr.rid,
                       tokens=len(rr.tokens), retries=rr.retries)

    def on_request_retry(self, rr, lane: str, now: float) -> None:
        """One displaced in-flight attempt: a `retry` span covering the
        discarded attempt, recorded at trip time (before the result's
        timestamps reset for the restart)."""
        if not self.registry.enabled:
            return
        self.retries_c.inc(1, tier=lane)
        t0 = rr.t_admit if rr.t_admit is not None else now
        self.registry.span("retry", t0, max(now - t0, 0.0), tid=rr.rid,
                           tier=lane, rid=rr.rid, attempt=rr.retries + 1)

    def on_trip(self, ev) -> None:
        if not self.registry.enabled:
            return
        self.trips_c.inc(1, tier=ev.lane)
        fields = dataclasses.asdict(ev)
        fields.pop("t")                  # positional timestamp already
        self.registry.event("sentinel_trip", ev.t, **fields)

    def on_breaker(self, lane: str, frm: str, to: str,
                   now: float) -> None:
        if not self.registry.enabled:
            return
        self.breaker_c.inc(1, tier=lane, frm=frm, to=to)
        self.registry.event("breaker_transition", now, lane=lane,
                            frm=frm, to=to)

    def on_sentinel(self, lane: str, agree: float, nmed: float) -> None:
        self.agree_g.set(agree, tier=lane)
        self.nmed_g.set(nmed, tier=lane)

    def _update_energy(self, lane: str, m: LaneEnergyMeter) -> None:
        self.energy_g.set(m.energy_j, tier=lane)
        self.ept_g.set(m.energy_per_token_j, tier=lane)


class _OpenSpan:
    """One program span while it is open (see `EngineTelemetry.span`)."""

    __slots__ = ("tel", "name", "tid", "labels", "sid", "parent", "t0",
                 "dur", "_ann")

    def __init__(self, tel: EngineTelemetry, name: str, tid: int,
                 labels: Dict[str, object]):
        self.tel, self.name, self.tid, self.labels = tel, name, tid, labels

    def __enter__(self) -> "_OpenSpan":
        tel = self.tel
        self.sid = tel._next_sid
        tel._next_sid += 1
        self.parent = tel._open[-1].sid if tel._open else None
        tel._open.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = tel.now()
        return self

    def __exit__(self, *exc) -> None:
        tel = self.tel
        self.dur = tel.now() - self.t0
        self._ann.__exit__(*exc)
        tel._open.pop()
        if tel.registry.enabled:
            tel.registry.spans.append(Span(self.name, self.t0, self.dur,
                                           self.tid, self.labels, self.sid,
                                           self.parent))
