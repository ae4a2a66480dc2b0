"""Drive one cell: set up, serve traffic through the engine on the
harness's own clock, measure a window, check what was served.

The harness builds the engine with `build_engine`, warms every shape
the cell's traffic uses, then drives `ServingEngine.submit()` and
`step()` itself.  Every token is stamped when `step()` returns, when it
is on the host.  Traffic starts with a lead-in; the measured window
opens after it and lasts `--seconds`.  Nothing compiles inside it.

After the window, the device's peak memory is read, the engine is
freed, and a sample of finished requests (per lane the longest and one
from each slot among them) is compared with the plain float32
reference (bench/correct.py).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import trace as tracemod
from bench import traffic as trafficmod
from bench import weights as wmod
from bench.spec import Cell

HOST_SPANS = ("tick", "admit", "decode_round", "submit", "wait", "stamp")


class NoChip(RuntimeError):
    pass


class Clock:
    """Seconds since traffic started (the engine reads `now()` for its
    span durations)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0


@dataclasses.dataclass
class Served:
    """One request as the harness saw it."""

    rid: int
    tier: str
    prompt: np.ndarray
    max_new: int
    due: float
    submitted: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = "ok"
    t_admit: Optional[float] = None
    done: bool = False
    slot: Optional[int] = None          # the pool slot it ran in


@dataclasses.dataclass
class Record:
    """What a run measured; metric readers take their numbers from it."""

    cell: Cell
    seconds: float
    t_open: float
    t_close: float
    served: List[Served]
    spans: List[Dict]                     # harness spans around the model
    engine_spans: List[Dict]              # the engine's telemetry spans
    setup: Dict[str, float]
    device: Dict
    compiles_in_window: int = 0
    retraces: int = 0
    trace: Optional[tracemod.Reduced] = None
    trace_span: Optional[tuple] = None    # traced part, harness clock
    trace_dir: Optional[str] = None       # the profiler's output

    @property
    def config(self) -> Dict:
        return self.cell.config

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def due_in_window(self) -> List[Served]:
        return [r for r in self.served if self.t_open <= r.due < self.t_close]

    def spans_traced(self, name: str) -> List[Dict]:
        """Spans that ran wholly inside the traced part of the window."""
        if self.trace_span is None:
            return []
        a, b = self.trace_span
        return [s for s in self.spans
                if s["name"] == name and s["t0"] >= a and s["t1"] <= b]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


class Session:
    """Weights and a warmed engine for one cell."""

    def __init__(self, cell: Cell, seed: int, trace: bool,
                 require_tpu: bool = True):
        import jax

        t_start = time.perf_counter()
        self.cell, self.trace = cell, trace
        devs = jax.devices()
        t_devs = time.perf_counter()
        if require_tpu and (devs[0].platform != "tpu"
                            or len(devs) < cell.chips):
            raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                         f"JAX found {len(devs)} x {devs[0].platform} "
                         f"({devs[0].device_kind})")
        self.device = devs[0]
        self.n_devices = len(devs)
        set_compile_cache(cell.root)
        self.cfg = cell.config
        self.prog_cfg = wmod.program_config(self.cfg)
        self.weights = wmod.make(self.cfg, seed)
        jax.block_until_ready(self.weights)
        t_init = time.perf_counter()
        self.engine = self._build()
        t_built = time.perf_counter()
        self.executables = self.engine.warmup()
        t_warm = time.perf_counter()
        self.spans: List[Dict] = []
        self.clock = Clock()
        _instrument(self)
        self.times = {"devices_s": t_devs - t_start,
                      "weights_s": t_init - t_devs,
                      "build_s": t_built - t_init,
                      "warmup_s": t_warm - t_built}

    def _build(self):
        from repro.serving import build_engine, build_tiers

        tr = self.cell.traffic
        eng = tr["engine"]
        t = tr["tiers"]
        tiers = build_tiers(mode=t["mode"], families=tuple(t["families"]))
        names = {x.name for x in tiers}
        if not set(t["mix"]) <= names:
            raise ValueError(f"traffic pins tiers {sorted(t['mix'])}; the "
                             f"ladder has {sorted(names)}")
        self.telemetry = None
        if self.trace:
            from repro.obs import EngineTelemetry

            self.telemetry = EngineTelemetry(energy=False,
                                             span_capacity=1 << 17)
        params = wmod.to_program(self.weights, self.prog_cfg)
        return build_engine(self.prog_cfg, params, tiers=tiers,
                            slots_per_tier=eng["slots_per_tier"],
                            max_len=eng["max_len"],
                            prompt_buckets=tuple(eng["prompt_buckets"]),
                            group_buckets=tuple(eng["group_buckets"]),
                            telemetry=self.telemetry)

    def reset(self) -> None:
        """A fresh scheduler over the same compiled lanes, for another
        serve in this process (a slot's stale rows are overwritten when
        it is next admitted)."""
        from repro.serving import ServingEngine

        old = self.engine
        self.engine = ServingEngine(
            {n: lane.backend for n, lane in old.lanes.items()}, old.router,
            telemetry=self.telemetry)
        self.engine.warmup()

    def swap_weights(self, seed: int) -> None:
        """New weights from `seed` under the same compiled engine (the
        control script reads many seeds in one process)."""
        import jax

        backends = [lane.backend for lane in self.engine.lanes.values()]
        self.weights = None
        for b in backends:
            b.params = None
        gc.collect()
        self.weights = wmod.make(self.cfg, seed)
        jax.block_until_ready(self.weights)
        params = wmod.to_program(self.weights, self.prog_cfg)
        for b in backends:
            b.params = params
        self.reset()

    def free_engine(self) -> None:
        if self.telemetry is not None:
            self.telemetry.detach()
        self.engine = None
        gc.collect()


def set_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR`
    where set, else one fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


_COMPILES: List[float] = []       # perf_counter of each backend compile
_LISTENING: List[bool] = []


def _count_compiles() -> None:
    """Count XLA backend compiles (once per process)."""
    import jax

    if _LISTENING:
        return
    _LISTENING.append(True)

    def listener(event, duration, **_):
        if "backend_compile" in event:
            _COMPILES.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(listener)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


def _instrument(sess: "Session") -> None:
    """Harness spans (and profiler annotations) around each lane's
    prefill (`admit`) and decode round, with the shapes each call ran.
    Installed once per session; spans go to `sess.spans` on
    `sess.clock`."""
    from jax.profiler import TraceAnnotation

    for name, lane in sess.engine.lanes.items():
        b = lane.backend
        admit, decode = b.admit, b.decode_round

        def timed_admit(prompts, slots, _f=admit, _b=b, _n=name):
            lens = [len(p) for p in prompts]
            g = min(x for x in _b.group_buckets if x >= len(prompts))
            rows = g * _b.prompt_bucket(max(lens))
            t0 = sess.clock.now()
            with TraceAnnotation("admit"):
                out = _f(prompts, slots)
            sess.spans.append({"name": "admit", "lane": _n, "t0": t0,
                               "t1": sess.clock.now(), "lens": lens,
                               "rows": rows})
            return out

        def timed_decode(_f=decode, _b=b, _n=name):
            running = sess.engine.lanes[_n].running
            ctx = [int(_b.slot_pos[s]) + 1 for s in sorted(running)]
            t0 = sess.clock.now()
            with TraceAnnotation("decode_round"):
                out = _f()
            sess.spans.append({"name": "decode_round", "lane": _n,
                               "t0": t0, "t1": sess.clock.now(),
                               "contexts": ctx, "rows": _b.n_slots})
            return out

        b.admit, b.decode_round = timed_admit, timed_decode


def serve(sess: Session, seed: int, seconds: float,
          trace: bool) -> Record:
    """Serve the cell's traffic from `seed`: lead-in, then the window."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serving import Request

    cell, engine = sess.cell, sess.engine
    tr = cell.traffic
    plan = trafficmod.schedule(tr, cell.config["vocab_size"], seed, seconds)
    clock = sess.clock = Clock()
    engine._clock = clock          # the engine times its spans on it
    spans = sess.spans
    spans.clear()
    t_open = float(tr["lead_in_s"])
    t_close = t_open + float(seconds)
    trace_s = min(float(tr.get("trace_s", seconds)), float(seconds))
    open_loop = tr["arrival"]["kind"] == "open"
    free_at = ([] if open_loop else [0.0] * int(tr["arrival"]["clients"]))
    served: Dict[int, Served] = {}
    live: List[int] = []
    nxt = 0
    tdir = window_ann = t_trace = None
    retrace0 = engine.steady_retraces()
    n_comp0 = len(_COMPILES)
    t_wall_open = None

    def submit(p: trafficmod.Planned, due: float, now: float) -> None:
        req = Request(rid=p.idx, prompt=p.prompt, max_new=p.max_new,
                      tier=p.tier, arrival=due)
        engine.submit(req)
        served[p.idx] = Served(rid=p.idx, tier=p.tier, prompt=p.prompt,
                               max_new=p.max_new, due=due, submitted=now)
        live.append(p.idx)

    while True:
        now = clock.now()
        if now >= t_close:
            break
        if trace and tdir is None and now >= t_close - trace_s:
            # the last `trace_s` of the window: stopping the profiler
            # writes the trace, which must not stall the window
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(tdir)
            window_ann = TraceAnnotation("window")
            window_ann.__enter__()
            t_trace = clock.now()
        if t_wall_open is None and now >= t_open:
            t_wall_open = time.perf_counter()
        with TraceAnnotation("submit"):
            if open_loop:
                while nxt < len(plan) and plan[nxt].due <= now:
                    submit(plan[nxt], plan[nxt].due, now)
                    nxt += 1
            else:
                while free_at:
                    if nxt >= len(plan):
                        raise RuntimeError("closed-loop schedule exhausted; "
                                           "raise max_requests_per_s")
                    submit(plan[nxt], free_at.pop(0), now)
                    nxt += 1
        busy = any(l.running or l.queue for l in engine.lanes.values())
        if not busy:
            nd = plan[nxt].due if nxt < len(plan) else t_close
            with TraceAnnotation("wait"):
                time.sleep(max(0.0, min(nd, t_close) - clock.now()))
            continue
        before = [len(engine.results[r].tokens) for r in live]
        with TraceAnnotation("tick"):
            engine.step(now)
        t_ret = clock.now()
        with TraceAnnotation("stamp"):
            for lane in engine.lanes.values():
                for slot, running in lane.running.items():
                    s = served[running.req.rid]
                    if s.slot is None:
                        s.slot = slot
            still = []
            for rid, n0 in zip(live, before):
                res = engine.results[rid]
                s = served[rid]
                s.stamps.extend([t_ret] * (len(res.tokens) - n0))
                s.t_admit = res.t_admit
                if res.done:
                    s.done, s.status = True, res.status
                    s.tokens = list(res.tokens)
                    if not open_loop:
                        free_at.append(t_ret)
                else:
                    still.append(rid)
            live[:] = still
    trace_span = None
    if window_ann is not None:
        trace_span = (t_trace, clock.now())
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    comp = [t for t in _COMPILES[n_comp0:]
            if t_wall_open is not None and t >= t_wall_open]
    rec = Record(cell=cell, seconds=float(seconds), t_open=t_open,
                 t_close=t_close, served=list(served.values()),
                 spans=list(spans), engine_spans=_engine_spans(sess),
                 setup={}, device={}, compiles_in_window=len(comp),
                 retraces=engine.steady_retraces() - retrace0,
                 trace_span=trace_span, trace_dir=tdir)
    return rec


def _engine_spans(sess: Session) -> List[Dict]:
    if sess.telemetry is None:
        return []
    return [{"name": s.name, "t0": s.t0, "dur": s.dur,
             "lane": s.labels.get("lane")}
            for s in sess.telemetry.registry.spans.items()]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True) -> Dict:
    """One run of `cell`; returns the result line's object.  Prints the
    run's own numbers before it, and the compared numbers with their
    limits as the last lines on standard error."""
    import shutil

    from bench import correct

    _count_compiles()
    imports_s = time.perf_counter() - t_start
    sess = Session(cell, seed, trace, require_tpu=require_tpu)
    setup_s = time.perf_counter() - t_start
    rec = serve(sess, seed, seconds, trace)
    stats = sess.device.memory_stats() or {}
    rec.device = {"platform": sess.device.platform,
                  "kind": sess.device.device_kind,
                  "count": sess.n_devices,
                  "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    rec.setup = dict(sess.times, setup_s=setup_s,
                     executables=sess.executables)
    if rec.trace_dir is not None:
        rec.trace = tracemod.reduce(tracemod.find_xplane(rec.trace_dir),
                                    HOST_SPANS)
        shutil.rmtree(rec.trace_dir, ignore_errors=True)
        rec.device["busy_s"] = rec.trace.busy_s
        rec.device["window_s"] = rec.trace.window_s
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    due = rec.due_in_window()
    late = [1e3 * (s.submitted - s.due) for s in due]
    n_tok = sum(1 for s in rec.served for t in s.stamps if rec.in_window(t))
    t = sess.times
    print(f"[setup] {cell.name} seed {seed}: imports {imports_s:.3f} s, "
          f"devices {t['devices_s']:.3f} s, weights {t['weights_s']:.3f} s, "
          f"engine {t['build_s']:.3f} s, warm-up {t['warmup_s']:.3f} s over "
          f"{sess.executables} executables; set-up {setup_s:.3f} s")
    print(f"[window] {seconds} s after a {cell.traffic['lead_in_s']} s "
          f"lead-in: {len(due)} requests due, "
          f"{sum(1 for s in due if s.done)} finished, {n_tok} tokens; "
          f"generator late p50 {np.median(late) if late else 0:.3f} ms, max "
          f"{max(late) if late else 0:.3f} ms; retraces in window "
          f"{rec.retraces}, compiles in window {rec.compiles_in_window}")
    for name in ("admit", "decode_round"):
        d = [x["t1"] - x["t0"] for x in rec.spans
             if x["name"] == name and rec.in_window(x["t0"])]
        print(f"[work] {name}: {len(d)} calls in the window, "
              f"{1e3 * sum(d):.3f} ms in all, mean "
              f"{1e3 * np.mean(d) if d else 0:.3f} ms")
    print(f"[device] {json.dumps(rec.device)}", flush=True)
    sess.free_engine()
    got = correct.readings(rec, sess.weights, seed, correct.Reference(cell))
    print("[readings] " + json.dumps({k: r["value"] for k, r in got.items()}),
          flush=True)
    checks = correct.check(got, cell.limits)
    ok = correct.passed(checks)
    failed = sum(1 for s in due if s.status != "ok")
    result = {"correct": ok and failed == 0, "attempted": len(due),
              "failed": failed, "metrics": metrics, "device": rec.device}
    if rec.trace is not None:
        result["breakdown"] = tracemod.breakdown(rec.trace)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"{k} {c['value']} limit {c['limit']} ({c['requests']} "
              f"requests, {c['tokens']} tokens)", file=sys.stderr,
              flush=True)
    return result
