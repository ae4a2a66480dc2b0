"""The harness: cells, traffic and metrics found by name; no result off
a TPU; schedules from the seed alone; one tiny run end to end on the
CPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import traffic as trafficmod  # noqa: E402
from bench.spec import load_cell  # noqa: E402
from bench.testdata.cells import TINY_TRAFFIC, make_root  # noqa: E402

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_committed_cells_load(name):
    cell = load_cell(name)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "itl_p95_ms"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]))
    lanes = {k.split(".", 1)[1] for k in cell.limits}
    assert lanes == set(cell.traffic["tiers"]["mix"])
    assert callable(cell.reference().forward)


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path, configs=("tiny-qwen3", "tiny-stablelm"),
                     traffic=("tiny.chat", "tiny.batch"))
    with open(os.path.join(root, "bench", "metrics", "requests_due.py"),
              "w") as f:
        f.write("def read(rec):\n    return len(rec.due_in_window())\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["per_layer"].append({"name": "requests_due", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "tokens_per_s"})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = load_cell("tiny-stablelm.tiny.batch", root)
    assert cell.config["reference"] == "stablelm"
    assert cell.traffic["arrival"]["kind"] == "closed"
    assert "requests_due" in [m["name"] for m in cell.per_layer]
    assert cell.reader("requests_due").__module__.startswith("bench_metric")
    with pytest.raises(KeyError, match="unknown workload"):
        load_cell("no-such.cell", root)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         CELLS[0], "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_result_off_a_tpu():
    r = _run_py(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    r = _run_py(str(tmp_path), env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.parametrize("name", sorted(TINY_TRAFFIC))
def test_schedule_comes_from_the_seed_alone(name):
    tr = TINY_TRAFFIC[name]
    a = trafficmod.schedule(tr, 512, 2**40 + 9, 3.0)
    b = trafficmod.schedule(tr, 512, 2**40 + 9, 3.0)
    assert [(p.max_new, p.tier, p.due) for p in a] == \
        [(p.max_new, p.tier, p.due) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    c = trafficmod.schedule(tr, 512, 7, 3.0)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]
    # every seed draws the same sizes in each block, in another order
    blk = trafficmod.block_size(tr)
    for s in (a, c):
        for i in range(0, len(s), blk):
            assert sorted(len(p.prompt) for p in s[i:i + blk]) == \
                sorted(len(p.prompt) for p in a[:blk])
            assert sorted(p.max_new for p in s[i:i + blk]) == \
                sorted(p.max_new for p in a[:blk])
    if tr["arrival"]["kind"] == "open":
        gaps_a = sorted(np.diff([0.0] + [p.due for p in a[:blk]]))
        gaps_c = sorted(np.diff([0.0] + [p.due for p in c[:blk]]))
        np.testing.assert_allclose(gaps_a, gaps_c)
    else:
        assert all(p.due is None for p in a)


def test_tiny_run_end_to_end(tmp_path):
    from bench.harness import run

    root = make_root(tmp_path, traffic=("tiny.chat",))
    cell = load_cell("tiny-qwen3.tiny.chat", root)
    res = run(cell, 2**35 + 1, 3.0, trace=False, t_start=time.perf_counter(),
              require_tpu=False)
    assert res["correct"] is True
    assert res["attempted"] > 3 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "itl_p95_ms"} <= set(res["metrics"])
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["gap_req.exact"]["limit"] == 1.0
