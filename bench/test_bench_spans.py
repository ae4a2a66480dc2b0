"""The program's own spans as the benchmark reads them: the span
readers on a synthetic record and on a parent-like one, the trace
reduction naming idle gaps by program phases (bench/testdata/
spans.xplane.pb, made by testdata/record_spans.py), and one tiny traced
serve on the CPU."""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace as tracemod  # noqa: E402
from bench.harness import HOST_SPANS, Record, Session, serve  # noqa: E402
from bench.spec import load_cell  # noqa: E402
from bench.testdata.cells import make_root  # noqa: E402

PB = os.path.join(ROOT, "bench", "testdata", "spans.xplane.pb")
PROGRAM_SPANS = ("step", "prefill.dispatch", "prefill.fetch",
                 "prefill.sample", "decode.dispatch", "decode.fetch",
                 "decode.sample")
READERS = ("step_self_ms", "decode_host_ms", "prefill_ms.span")


def _record(engine_spans):
    cell = load_cell("stablelm-2-1.6b.exact.longdoc")
    return Record(cell=cell, seconds=10.0, t_open=10.0, t_close=20.0,
                  served=[], spans=[], engine_spans=engine_spans, setup={},
                  device={})


def _span(name, t0, dur, lane=None):
    return {"name": name, "t0": t0, "dur": dur, "lane": lane}


def _read(rec):
    return {m: rec.cell.reader(m)(rec) for m in READERS}


def test_span_readers_on_a_synthetic_record():
    spans = [
        # a tick before the window: not read
        _span("step", 5.0, 0.5), _span("admit", 5.1, 0.3, "exact"),
        # a tick with a prefill and a decode round
        _span("step", 11.0, 0.100),
        _span("admit", 11.01, 0.050, "exact"),
        _span("prefill.dispatch", 11.01, 0.004),
        _span("decode_round", 11.06, 0.030, "exact"),
        _span("decode.dispatch", 11.06, 0.002),
        _span("decode.fetch", 11.062, 0.025),
        _span("decode.sample", 11.087, 0.003),
        # a tick with a decode round only
        _span("step", 12.0, 0.040),
        _span("decode_round", 12.001, 0.035, "exact"),
        _span("decode.dispatch", 12.001, 0.001),
        _span("decode.fetch", 12.002, 0.030),
        _span("decode.sample", 12.032, 0.002),
        # the lifecycle spans of a request: not read
        _span("prefill", 11.0, 0.06), _span("decode", 11.06, 1.0),
    ]
    got = _read(_record(spans))
    assert got["step_self_ms"] == pytest.approx((20.0 + 5.0) / 2)
    assert got["decode_host_ms"] == pytest.approx((5.0 + 3.0) / 2)
    assert got["prefill_ms.span"] == pytest.approx(50.0)


def test_span_readers_silent_without_program_spans():
    # what a program without phase spans records: decode rounds and
    # the lifecycle spans of its requests
    spans = [_span("decode_round", 11.0, 0.03, "exact"),
             _span("queue", 10.5, 0.5), _span("prefill", 11.0, 0.0)]
    assert _read(_record(spans)) == dict.fromkeys(READERS)
    assert _read(_record([])) == dict.fromkeys(READERS)


def test_idle_gaps_named_by_program_phase():
    red = tracemod.reduce(PB, HOST_SPANS + PROGRAM_SPANS)
    idle = {k: v * 1e-9 for k, v in red.idle_by_host.items()}
    # the host slept 3 x 20 ms inside decode.sample with the device idle
    assert idle["decode.sample"] > 0.055
    assert max(idle, key=idle.get) == "decode.sample"
    assert tracemod.breakdown(red)["idle_gaps"][0][0] == "decode.sample"
    # the harness's names alone see only the round around it
    old = tracemod.reduce(PB, HOST_SPANS)
    assert max(old.idle_by_host, key=old.idle_by_host.get) == "decode_round"
    assert old.busy_ns == red.busy_ns


def test_tiny_traced_serve_reads_program_spans(tmp_path):
    root = make_root(tmp_path, traffic=("tiny.batch",))
    cell = load_cell("tiny-qwen3.tiny.batch", root)
    sess = Session(cell, 2**33 + 5, trace=True, require_tpu=False)
    try:
        rec = serve(sess, 2**33 + 5, 2.0, trace=True)
    finally:
        sess.free_engine()
    names = {s["name"] for s in rec.engine_spans}
    assert set(PROGRAM_SPANS) | {"admit", "decode_round"} <= names
    got = _read(rec)
    assert all(v is not None and v > 0 for v in got.values())
    # the program's admit span wraps the harness's, a few clock reads apart
    batch = rec.cell.reader("prefill_ms.batch")(rec)
    assert batch <= got["prefill_ms.span"] < batch + 1.0
    red = tracemod.reduce(tracemod.find_xplane(rec.trace_dir),
                          HOST_SPANS + PROGRAM_SPANS)
    shutil.rmtree(rec.trace_dir)
    assert set(red.idle_by_host) <= set(HOST_SPANS + PROGRAM_SPANS) | {
        "no_span"}
