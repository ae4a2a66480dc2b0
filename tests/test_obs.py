"""Telemetry spine (DESIGN.md §15).

Five layers:

  * instrument primitives — counter/gauge label keying, histogram
    bucketing (Prometheus-inclusive upper bounds + implicit +inf),
    preallocated ring wraparound/drop accounting, and the
    disabled-registry fast path reducing every record to a no-op;
  * exporters — golden Prometheus text exposition, Chrome-trace
    structure (microsecond conversion, per-tid metadata rows), and the
    JSONL event dump round-tripping dataclass events;
  * dispatch-boundary capture — `obs_mac_scale` ambient scaling,
    `MacCapture`/`profile_macs` recovering the exact m*k*n MAC count of
    a GEMM through `jax.eval_shape` (no FLOPs);
  * engine integration on fake lanes (no jax compiles) — request
    lifecycle spans, `engine.metrics()`, structured `TripEvent`s with
    dict back-compat, and retry spans for work a trip displaces —
    plus `EngineStats.from_results` edge cases and the injectable
    serving clocks;
  * program spans on a smoke-width engine — the `step` ⊃ `admit` /
    `decode_round` ⊃ dispatch/fetch/sample tree with parents, and the
    off path (no telemetry: no span, annotation or clock read) — and
    token stamps taken after the backend call (TTFT includes it).
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.obs import (EngineTelemetry, MacCapture, MetricsRegistry,
                       Ring, Span, capture_macs, chrome_trace,
                       events_jsonl, profile_macs, prometheus_text)
from repro.obs.metrics import Counter, Gauge, Histogram
from repro.serving import (Clock, EngineStats, RealClock, ServingEngine,
                           SimClock, TripEvent)
from repro.serving.engine import RequestResult
from repro.serving.tiers import TierRouter
from test_serving import FakeLane, _fake_tiers, _req


# ---------------------------------------------------------------------------
# instrument primitives
# ---------------------------------------------------------------------------


def test_counter_labels_and_total():
    c = Counter("x_total")
    c.inc()
    c.inc(2, op="gemm", family="appro42")
    c.inc(3, family="appro42", op="gemm")    # label order-insensitive
    assert c.value() == 1
    assert c.value(op="gemm", family="appro42") == 5
    assert c.value(op="conv") == 0.0
    assert c.total == 6


def test_gauge_last_write_wins():
    g = Gauge("x")
    g.set(1.5, tier="a")
    g.set(2.5, tier="a")
    assert g.value(tier="a") == 2.5
    assert g.value(tier="b") is None


def test_histogram_bucketing_inclusive_bounds():
    h = Histogram("h", buckets=(0.1, 0.3, 1.0))
    for v in (0.05, 0.1, 0.3, 0.7, 5.0):     # bounds are inclusive (le=)
        h.observe(v, tier="a")
    snap = h.snapshot(tier="a")
    assert snap["buckets"] == [(0.1, 2.0), (0.3, 3.0), (1.0, 4.0),
                               (float("inf"), 5.0)]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(6.15)
    # label sets are independent
    assert h.snapshot(tier="b")["count"] == 0


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", buckets=(1.0, 0.5))


def test_ring_wraparound_and_drop_accounting():
    r = Ring(4)
    for i in range(3):
        r.append(i)
    assert r.items() == [0, 1, 2] and r.dropped == 0
    for i in range(3, 7):
        r.append(i)
    assert len(r) == 4
    assert r.items() == [3, 4, 5, 6]         # oldest dropped, order kept
    assert r.total == 7 and r.dropped == 3
    r.clear()
    assert len(r) == 0 and r.total == 0 and r.items() == []
    with pytest.raises(ValueError):
        Ring(0)


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    h = reg.histogram("h", (1.0,))
    g = reg.gauge("g")
    c.inc(5)
    g.set(1.0)
    h.observe(0.5)
    reg.span("s", 0.0, 1.0)
    reg.event("e", 0.0)
    assert c.total == 0 and g.value() is None
    assert h.snapshot()["count"] == 0
    assert len(reg.spans) == 0 and len(reg.events) == 0


def test_registry_factories_idempotent():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.histogram("h", (1.0,)) is reg.histogram("h", (2.0,))


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    c = reg.counter("repro_calls_total", "calls")
    c.inc(3, op="gemm")
    c.inc(1, op="conv")
    reg.gauge("repro_agree", "agreement").set(0.5, tier="a")
    h = reg.histogram("repro_wait_seconds", (0.1, 1.0), "wait")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(7.0)
    assert prometheus_text(reg) == (
        "# HELP repro_calls_total calls\n"
        "# TYPE repro_calls_total counter\n"
        'repro_calls_total{op="conv"} 1\n'
        'repro_calls_total{op="gemm"} 3\n'
        "# HELP repro_agree agreement\n"
        "# TYPE repro_agree gauge\n"
        'repro_agree{tier="a"} 0.5\n'
        "# HELP repro_wait_seconds wait\n"
        "# TYPE repro_wait_seconds histogram\n"
        'repro_wait_seconds_bucket{le="0.1"} 1\n'
        'repro_wait_seconds_bucket{le="1"} 2\n'
        'repro_wait_seconds_bucket{le="+Inf"} 3\n'
        "repro_wait_seconds_sum 7.55\n"
        "repro_wait_seconds_count 3\n")


def test_chrome_trace_structure():
    spans = [Span("decode", 1.0, 0.5, tid=3,
                  labels={"tier": "a", "cat": "serving"}),
             Span("decode_round", 2.0, -0.1, tid=-1, labels={})]
    out = chrome_trace(spans, tid_names={-1: "lane a"})
    assert out["displayTimeUnit"] == "ms"
    evs = out["traceEvents"]
    x = [e for e in evs if e["ph"] == "X"]
    assert x[0]["ts"] == 1e6 and x[0]["dur"] == 5e5
    assert x[0]["args"] == {"tier": "a"}         # cat lifted, not an arg
    assert x[1]["dur"] == 0.0                    # negative dur clamped
    names = {e["tid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {3: "request 3", -1: "lane a"}
    assert any(e["name"] == "process_name" for e in evs)


def test_events_jsonl_roundtrip(tmp_path):
    ev = TripEvent(lane="a", t=1.0, reason="drift",
                   tokens_before_trip=7, in_flight_displaced=2)
    path = tmp_path / "events.jsonl"
    text = events_jsonl([{"kind": "x", "t": 0.0}, ev], str(path))
    lines = [json.loads(ln) for ln in text.splitlines()]
    assert lines[0] == {"kind": "x", "t": 0.0}
    assert lines[1]["lane"] == "a"
    assert lines[1]["breaker_after"] == "tripped"
    assert path.read_text() == text


def test_trip_event_dict_compat():
    ev = TripEvent(lane="a", t=0.0, reason="r", tokens_before_trip=1,
                   in_flight_displaced=0)
    assert ev["lane"] == "a" and ev["reason"] == "r"
    assert ev.get("missing") is None and ev.get("t", 9) == 0.0
    assert "breaker_before" in ev.keys()
    with pytest.raises(KeyError):
        ev["nope"]


# ---------------------------------------------------------------------------
# dispatch-boundary MAC capture
# ---------------------------------------------------------------------------


def test_obs_mac_scale_nesting():
    from repro.core import approx_gemm

    assert approx_gemm._OBS_MAC_SCALE[0] == 1.0
    with approx_gemm.obs_mac_scale(3):
        assert approx_gemm._OBS_MAC_SCALE[0] == 3.0
        with approx_gemm.obs_mac_scale(2):
            assert approx_gemm._OBS_MAC_SCALE[0] == 6.0
        assert approx_gemm._OBS_MAC_SCALE[0] == 3.0
    assert approx_gemm._OBS_MAC_SCALE[0] == 1.0


def test_profile_macs_gemm_exact_count():
    from repro.core.approx_gemm import GemmParams, cim_matmul

    m, k, n = 5, 16, 8
    gp = GemmParams(family="exact", bits=8, mode="exact")

    def f(x, w):
        return cim_matmul(x, w, gp)

    cap = profile_macs(f, np.zeros((m, k), np.float32),
                       np.zeros((k, n), np.float32))
    assert cap.total == m * k * n
    assert cap.by_family == {("exact", 8): m * k * n}
    assert cap.by_op == {"gemm": m * k * n}


def test_capture_macs_scoped_and_restores_sink():
    from repro.core import approx_gemm
    from repro.core.approx_gemm import GemmParams, cim_matmul

    gp = GemmParams(family="exact", bits=8, mode="exact")
    outer = MacCapture()
    prev = approx_gemm.set_obs_sink(outer)
    try:
        with capture_macs() as cap:
            with approx_gemm.obs_mac_scale(4):  # lax.scan correction
                cim_matmul(np.zeros((2, 4), np.float32),
                           np.zeros((4, 3), np.float32), gp)
        assert cap.total == 4 * 2 * 4 * 3
        assert outer.total == 0                 # scoped: outer untouched
        assert approx_gemm._OBS_SINK[0] is outer
    finally:
        approx_gemm.set_obs_sink(prev)


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------


def test_clock_base_and_impls():
    with pytest.raises(NotImplementedError):
        Clock().now()
    sim = SimClock()
    assert sim.now() == 0.0
    sim.wait_until(2.0)
    sim.wait_until(1.0)                        # never moves backwards
    assert sim.now() == 2.0
    rc = RealClock()
    assert rc.now() >= 0.0
    assert isinstance(sim, Clock) and isinstance(rc, Clock)


# ---------------------------------------------------------------------------
# EngineStats edge cases
# ---------------------------------------------------------------------------


def test_engine_stats_empty_results():
    s = EngineStats.from_results({}, 0.0)
    assert s.n_requests == 0 and s.total_tokens == 0
    assert s.tokens_per_s == 0.0               # zero-duration guarded
    assert s.p50_ms_per_token == 0.0 and s.p50_ttft_ms == 0.0


def test_engine_stats_all_failed():
    rr = RequestResult(rid=0, tier="a", prompt_len=4, arrival=0.0,
                       tokens=[1, 2], t_done=1.0)
    rr.status = "failed"
    s = EngineStats.from_results({0: rr}, 1.0)
    assert s.n_requests == 0                   # ok-completions only
    assert s.n_failed == 1
    assert s.total_tokens == 0                 # failed tokens don't count


def test_engine_stats_ignores_inflight():
    ok = RequestResult(rid=0, tier="a", prompt_len=4, arrival=0.0,
                       tokens=[1, 2, 3], t_first=0.1, t_done=0.5)
    live = RequestResult(rid=1, tier="a", prompt_len=4, arrival=0.2,
                         tokens=[1])           # t_done unset: in flight
    s = EngineStats.from_results({0: ok, 1: live}, 2.0)
    assert s.n_requests == 1 and s.total_tokens == 3
    assert s.tokens_per_s == pytest.approx(1.5)
    assert s.p50_ttft_ms == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# engine integration (fake lanes, no jax)
# ---------------------------------------------------------------------------


def _tel_engine(n_slots=3, names=("a", "b"), **kw):
    tel = EngineTelemetry(attach=False, energy=False)
    tiers = _fake_tiers(names)
    lanes = {t.name: FakeLane(n_slots) for t in tiers}
    eng = ServingEngine(lanes, TierRouter(tiers), check_invariants=True,
                        telemetry=tel, **kw)
    return eng, tel


def test_telemetry_request_lifecycle_spans():
    eng, tel = _tel_engine()
    eng.warmup()
    reqs = [_req(i, tier="ab"[i % 2], max_new=2 + i % 3,
                 arrival=0.01 * i) for i in range(6)]
    res = eng.run(reqs, clock=SimClock())
    assert all(r.done for r in res.values())

    spans = tel.registry.spans.items()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # one queue/prefill/decode span per completed request, tid = rid
    for name in ("queue", "prefill", "decode"):
        assert sorted(s.tid for s in by_name[name]) == list(range(6))
    # lane rows and the scheduler's row are negative and named
    assert all(s.tid < 0 for s in by_name["decode_round"])
    assert all(s.tid < 0 for s in by_name["step"])
    assert set(tel.tid_names.values()) == {"lane a", "lane b",
                                           "scheduler"}

    tok_total = sum(len(r.tokens) for r in res.values())
    assert tel.tokens_c.total == tok_total
    assert tel.requests_c.value(tier="a", status="ok") == 3
    assert tel.queue_wait_h.snapshot(tier="b")["count"] == 3

    m = eng.metrics()
    assert m["n_requests"] == 6 and m["n_failed"] == 0
    assert m["total_tokens"] == tok_total
    assert m["steady_retraces"] == 0
    assert set(m["lanes"]) == {"a", "b"}
    la = m["lanes"]["a"]
    assert la["tokens"] == sum(len(res[r.rid].tokens) for r in reqs
                               if r.tier == "a")
    assert la["energy_per_token_j"] is None    # fake lane: no LM surface
    assert la["trips"] == 0 and la["retries"] == 0


def test_telemetry_trip_retry_spans_and_events():
    eng, tel = _tel_engine(retry_backoff_s=0.0)
    eng.warmup()
    for i in range(3):
        eng.submit(_req(i, tier="b", max_new=4))
    eng.step(0.0)                              # admit + first round
    lane = eng.lanes["b"]
    assert lane.running
    n_running = len(lane.running)
    eng._trip(lane, 0.5, "forced (test)")

    ev = eng.trip_log[0]
    assert isinstance(ev, TripEvent)
    assert ev["lane"] == "b" and ev.in_flight_displaced == n_running
    assert ev.breaker_before == "healthy"
    assert ev.breaker_after == "tripped"       # no sentinel: default
    assert ev.trigger_agree is None

    retry_spans = [s for s in tel.registry.spans.items()
                   if s.name == "retry"]
    assert sorted(s.tid for s in retry_spans) == list(range(n_running))
    assert all(s.labels["tier"] == "b" for s in retry_spans)
    assert tel.retries_c.value(tier="b") == n_running
    assert tel.trips_c.value(tier="b") == 1
    kinds = [e["kind"] for e in tel.registry.events.items()]
    assert "sentinel_trip" in kinds and "breaker_transition" in kinds
    trip_ev = next(e for e in tel.registry.events.items()
                   if e["kind"] == "sentinel_trip")
    assert trip_ev["reason"] == "forced (test)"

    # displaced work drains on the surviving lane, counted as retries
    for t in range(1, 40):
        eng.step(0.1 * t)
        if all(r.done for r in eng.results.values()):
            break
    assert all(r.done and r.status == "ok"
               for r in eng.results.values())
    assert all(r.tier == "a" for r in eng.results.values())
    m = eng.metrics()
    assert m["lanes"]["b"]["trips"] == 1
    assert m["lanes"]["b"]["retries"] == n_running
    assert m["lanes"]["b"]["quarantined"] is True


def test_metrics_without_telemetry():
    from test_serving import _fake_engine

    eng, _ = _fake_engine()
    eng.warmup()
    eng.run([_req(i, tier="a", max_new=2) for i in range(3)],
            clock=SimClock())
    m = eng.metrics()
    assert m["n_requests"] == 3
    assert m["lanes"]["a"]["tokens"] == 6
    assert m["lanes"]["a"]["energy_per_token_j"] is None
    assert m["lanes"]["a"]["acceptance_rate"] is None


def test_telemetry_detach_restores_sink():
    from repro.core import approx_gemm, autotune

    prev_g = approx_gemm._OBS_SINK[0]
    prev_a = autotune._OBS_SINK[0]
    tel = EngineTelemetry(energy=False)        # attaches globally
    assert approx_gemm._OBS_SINK[0] is tel
    assert autotune._OBS_SINK[0] is tel
    tel.detach()
    assert approx_gemm._OBS_SINK[0] is None
    assert autotune._OBS_SINK[0] is None
    approx_gemm._OBS_SINK[0] = prev_g
    autotune._OBS_SINK[0] = prev_a


def test_dispatch_sink_protocol_counts():
    tel = EngineTelemetry(attach=False, energy=False)
    tel.dispatch(op="gemm", family="appro42", mode="surrogate_fast",
                 bits=8, macs=100.0, cache_hit=False)
    tel.dispatch(op="gemm", family="appro42", mode="surrogate_fast",
                 bits=8, macs=100.0, cache_hit=True)
    tel.retrace()
    tel.autotune("k", "disk_hit")
    assert tel.dispatch_calls.value(
        op="gemm", family="appro42", mode="surrogate_fast", bits=8,
        cache="miss") == 1
    assert tel.dispatch_calls.value(
        op="gemm", family="appro42", mode="surrogate_fast", bits=8,
        cache="hit") == 1
    assert tel.dispatch_macs.value(op="gemm", family="appro42",
                                   bits=8) == 200.0
    assert tel.retraces.total == 1
    assert tel.autotune_c.value(outcome="disk_hit") == 1


# ---------------------------------------------------------------------------
# program spans (the scheduler tick and each model step's phases)
# ---------------------------------------------------------------------------


class _CountingClock(Clock):
    """A real clock that counts its reads."""

    def __init__(self):
        self.inner = RealClock()
        self.reads = 0

    def now(self) -> float:
        self.reads += 1
        return self.inner.now()


class _CountingAnnotation:
    opened = 0

    def __init__(self, name):
        type(self).opened += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture(scope="module")
def smoke_lanes():
    """One warmed exact-tier lane at smoke width (compiled once)."""
    from repro.configs import get_config
    from repro.serving import build_engine, build_tiers

    cfg = get_config("qwen3-1.7b", smoke=True)
    eng = build_engine(cfg, tiers=build_tiers(families=("exact",)),
                       slots_per_tier=2, max_len=32, prompt_buckets=(8,),
                       group_buckets=(1, 2))
    eng.warmup()
    return {n: lane.backend for n, lane in eng.lanes.items()}, eng.router


def _smoke_reqs(n=3):
    return [_req(i, tier="exact", plen=5 + i, max_new=3) for i in range(n)]


def _drive(eng, clock, reqs) -> int:
    """Direct step() driving on `clock` until every request is done;
    returns the number of ticks (one clock read each)."""
    eng._clock = clock
    for r in reqs:
        eng.submit(r)
    for n in range(1, 50):
        eng.step(clock.now())
        if all(eng.results[r.rid].done for r in reqs):
            return n
    raise AssertionError("requests did not finish")


def test_program_span_tree(smoke_lanes):
    backends, router = smoke_lanes
    tel = EngineTelemetry(attach=False, energy=False)
    eng = ServingEngine(backends, router, telemetry=tel)
    assert all(b.telemetry is tel for b in backends.values())
    _drive(eng, RealClock(), _smoke_reqs())
    spans = [s for s in tel.registry.spans.items() if s.sid is not None]
    by_sid = {s.sid: s for s in spans}
    names = {s.name for s in spans}
    assert names == {"step", "admit", "prefill.dispatch", "prefill.fetch",
                     "prefill.sample", "decode_round", "decode.dispatch",
                     "decode.fetch", "decode.sample"}
    tree = {"admit": "step", "decode_round": "step",
            "prefill.dispatch": "admit", "prefill.fetch": "admit",
            "prefill.sample": "admit", "decode.dispatch": "decode_round",
            "decode.fetch": "decode_round", "decode.sample": "decode_round"}
    for s in spans:
        if s.name == "step":
            assert s.parent is None
            continue
        parent = by_sid[s.parent]
        assert parent.name == tree[s.name]
        assert s.tid == parent.tid or s.name in ("admit", "decode_round")
        # a child lies inside its parent, on the same clock
        assert parent.t0 <= s.t0 and s.t0 + s.dur <= parent.t0 + parent.dur
    for s in spans:
        kids = [c for c in spans if c.parent == s.sid]
        assert sum(c.dur for c in kids) <= s.dur
    admits = [s for s in spans if s.name == "admit"]
    # 3 prompts of 5-7 tokens into 2 slots: a group of 2, then 1
    assert sorted((s.labels["rows"], s.labels["useful"]) for s in admits) \
        == [(8, 7), (16, 11)]
    assert all(s.labels["lane"] == "exact" and s.tid < 0 for s in admits)
    rounds = [s for s in spans if s.name == "decode_round"]
    assert all(s.labels["rows"] == 2 and 1 <= s.labels["useful"] <= 2
               for s in rounds)
    assert tel.decode_rounds_c.total == len(rounds)
    assert all(s.dur > 0 for s in spans if s.name == "step")


def test_no_telemetry_no_span_no_annotation_no_clock_read(smoke_lanes,
                                                          monkeypatch):
    from repro.obs import telemetry as telmod

    monkeypatch.setattr(telmod, "TraceAnnotation", _CountingAnnotation)
    backends, router = smoke_lanes
    calls = {"admit": 0, "decode_round": 0}
    for b in backends.values():
        for name in calls:
            def counted(*a, _f=getattr(b, name), _n=name):
                calls[_n] += 1
                return _f(*a)
            monkeypatch.setattr(b, name, counted)

    def served(tel):
        _CountingAnnotation.opened = 0
        calls.update(admit=0, decode_round=0)
        clock = _CountingClock()
        eng = ServingEngine(backends, router, telemetry=tel)
        ticks = _drive(eng, clock, _smoke_reqs())
        # _drive reads once per tick; the engine stamps tokens once
        # after each backend call; every other read is a span's
        return clock.reads - ticks - sum(calls.values())

    assert served(None) == 0
    assert all(b.telemetry is None for b in backends.values())
    assert _CountingAnnotation.opened == 0
    assert calls["admit"] == 2 and calls["decode_round"] >= 2
    tel = EngineTelemetry(attach=False, energy=False)
    span_reads = served(tel)
    n_spans = sum(1 for s in tel.registry.spans.items()
                  if s.sid is not None)
    assert span_reads == 2 * n_spans > 0
    assert _CountingAnnotation.opened == n_spans


def test_ttft_includes_the_prefill():
    """Tokens are stamped when the backend call that made them returns:
    a prefill that takes 0.25 s shows whole in TTFT and the `prefill`
    lifecycle span."""
    sim = SimClock()

    class SlowPrefill(FakeLane):
        def admit(self, prompts, slots):
            sim.t += 0.25
            return super().admit(prompts, slots)

    tel = EngineTelemetry(attach=False, energy=False)
    eng = ServingEngine({"a": SlowPrefill(2)}, TierRouter(_fake_tiers(("a",))),
                        telemetry=tel)
    res = eng.run([_req(0, max_new=2), _req(1, max_new=2)], clock=sim)
    for r in res.values():
        assert r.t_admit == 0.0
        assert r.t_first == pytest.approx(0.25)
    assert EngineStats.from_results(res, eng.last_run_s).p50_ttft_ms == \
        pytest.approx(250.0)
    prefill = [s for s in tel.registry.spans.items() if s.name == "prefill"]
    assert [s.dur for s in prefill] == pytest.approx([0.25, 0.25])
    assert tel.ttft_h.snapshot(tier="a")["sum"] == pytest.approx(0.5)
    admit = [s for s in tel.registry.spans.items() if s.name == "admit"]
    assert [s.dur for s in admit] == pytest.approx([0.25])


def test_telemetry_does_not_keep_the_engine_alive():
    import gc
    import weakref

    tel = EngineTelemetry(attach=False, energy=False)
    eng = ServingEngine({"a": FakeLane(2)}, TierRouter(_fake_tiers(("a",))),
                        telemetry=tel)
    eng.run([_req(0, max_new=2)], clock=SimClock())
    gone = weakref.ref(eng)
    del eng
    gc.collect()
    assert gone() is None
