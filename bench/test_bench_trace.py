"""The trace reduction on a small trace recorded on the CPU
(bench/testdata/cpu.xplane.pb, made by testdata/record_trace.py: three
annotated matmul calls with 20 ms host-only waits between them)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import trace as tracemod  # noqa: E402

PB = os.path.join(ROOT, "bench", "testdata", "cpu.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return tracemod.reduce(PB, ("tick", "decode_round", "wait"))


def test_window_busy_and_idle_add_up(red):
    assert red.n_devices == 1
    assert 0.06 < red.window_s < 0.2          # three 20 ms waits and more
    assert 0 < red.busy_s < red.window_s
    idle = sum(red.idle_by_host.values()) * 1e-9
    assert idle <= red.window_s - red.busy_s + 1e-9


def test_idle_gaps_named_by_host_span(red):
    # the waits are host-only: the device sat idle under `wait`
    assert red.idle_by_host["wait"] * 1e-9 > 0.055
    assert max(red.idle_by_host, key=red.idle_by_host.get) == "wait"


def test_op_time_by_name_and_breakdown(red):
    t = tracemod.op_time_ns(red, r"dot_general")
    assert t and t <= red.busy_ns + 1
    assert tracemod.op_time_ns(red, r"no_such_kernel") is None
    b = tracemod.breakdown(red)
    assert b["device_ops"][0][0] == "dot_general"
    assert b["idle_gaps"][0][0] == "wait"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_kind_from_hlo_text():
    assert tracemod.op_kind("%mitchell_matmul_fused.42 = f32[8,2048]{1,0} "
                            "custom-call(f32[1,1] %x)") == \
        "mitchell_matmul_fused"
    assert tracemod.op_kind("%while.24 = (s32[], bf16[1,256]) while(%t)") \
        == "while"
    assert tracemod.op_kind("dot_general.1") == "dot_general"
    assert tracemod.op_kind("wrapped_tanh") == "wrapped_tanh"


def test_union_and_clip():
    assert tracemod.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3),
                                                                (5, 9)]
    assert tracemod.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
