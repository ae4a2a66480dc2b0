"""Operation and byte counts against hand counts, and the peaks table."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import work  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_peaks_v5e_and_unknown_kind_raises():
    p = work.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")


def test_qwen3_hand_counts():
    cfg = config("qwen3-1.7b")
    # per layer: q 2048x2048, k/v 2048x1024, o 2048x2048, 3 x 2048x6144
    per_layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
    assert work.layer_macs_per_token(cfg) == per_layer
    assert work.body_macs_per_token(cfg) == 28 * per_layer == 1_409_286_144
    assert work.head_macs_per_token(cfg) == 2048 * 151_936 == 311_164_928
    # attention: 28 layers x (q.k + p.v) x 16 heads x 128 per key
    assert work.attn_macs(cfg, 10) == 28 * 2 * 16 * 128 * 10


def test_stablelm_hand_counts():
    cfg = config("stablelm-2-1.6b")
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert work.body_macs_per_token(cfg) == 24 * per_layer
    assert work.head_macs_per_token(cfg) == 2048 * 100_352


def test_prefill_and_decode_flops():
    cfg = config("qwen3-1.7b")
    body, head = work.body_macs_per_token(cfg), work.head_macs_per_token(cfg)
    a1 = work.attn_macs(cfg, 1)
    assert work.prefill_flops(cfg, 3) == 2.0 * (3 * body + 6 * a1 + head)
    assert work.decode_flops(cfg, 100) == 2.0 * (body + 100 * a1 + head)


def test_gemm_work_and_roofline_bound():
    w = work.gemm_work(8, 2048, 6144)
    assert w["ops"] == 2 * 8 * 2048 * 6144
    assert w["bytes"] == 4 * (8 * 2048 + 2048 * 6144) + 4 * 8 * 6144
    p = work.peaks("TPU v5 lite")
    assert work.roofline_seconds(w, p)["bound"] == "memory"
    big = work.gemm_work(4096, 2048, 6144)
    r = work.roofline_seconds(big, p)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(big["ops"] / 393e12)
    cfg = config("qwen3-1.7b")
    ops = sum(work.gemm_work(8, k, n)["ops"]
              for k, n in work.layer_matmuls(cfg).values())
    assert ops * 28 == 2 * 8 * work.body_macs_per_token(cfg)
