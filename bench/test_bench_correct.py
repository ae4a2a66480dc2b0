"""The comparison that decides `correct`, at a size a test run holds
(`test-stablelm`: 4 layers of width 256 at the program's smoke shapes,
with the benchmark's local-attention q/k bias; one exact lane; limit
0.05 logits on `gap_req`).  Sound runs read far below the limit; the
4-bit control reads above it and comes out not correct; so does a run
with its timed path broken underneath by each fault of
bench/faults.py."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import correct, faults, harness  # noqa: E402
from bench.spec import load_cell  # noqa: E402
from bench.testdata.cells import make_root  # noqa: E402

CELL = "test-stablelm.test.chat"
LIMIT = 0.05


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"),
                     configs=("test-stablelm",), traffic=("test.chat",),
                     limit=LIMIT)
    return load_cell(CELL, root)


def test_control_fails_where_sound_runs_pass(cell):
    sess = harness.Session(cell, 1, trace=False, require_tpu=False)
    ref = correct.Reference(cell, control=True)
    for i, seed in enumerate((2**33 + 1, 5, 2**31 + 11)):
        if i:
            sess.swap_weights(seed)
        rec = harness.serve(sess, seed, 4.0, trace=False)
        got = correct.readings(rec, sess.weights, seed, ref)
        c = got["gap_req.exact"]
        assert c["tokens"] >= 10
        assert c["value"] <= LIMIT < c["control"], c
        assert correct.passed(correct.check(got, cell.limits))
        assert not correct.passed(correct.check(got, cell.limits,
                                                "control"))


def _run_broken(cell, monkeypatch, fault, seed):
    from repro.serving.engine import LMLaneBackend

    init = LMLaneBackend.__init__

    def broken(self, *args, **kwargs):
        init(self, *args, **kwargs)
        faults.plant(self, fault)

    monkeypatch.setattr(LMLaneBackend, "__init__", broken)
    return harness.run(cell, seed, 3.0, trace=False,
                       t_start=time.perf_counter(), require_tpu=False)


def test_altered_token_is_not_correct(cell, monkeypatch):
    res = _run_broken(cell, monkeypatch, "altered", 2**34 + 3)
    assert res["correct"] is False
    assert res["checks"]["gap_req.exact"]["value"] > LIMIT


@pytest.mark.parametrize("fault", ["no_kv", "wrong_slot"])
def test_broken_kv_cache_is_not_correct(cell, monkeypatch, fault):
    res = _run_broken(cell, monkeypatch, fault, 2**34 + 5)
    assert res["correct"] is False
    assert res["checks"]["gap_req.exact"]["value"] > LIMIT
