"""Tiny cells for the benchmark's CPU tests: a copy of the benchmark's
files under a temporary root, with configurations at the program's
smoke widths and short traffic."""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CONFIGS = {
    "tiny-qwen3": {
        "source": "test", "reduced": [], "hidden_size": 64,
        "intermediate_size": 160, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rope_theta": 10000, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": True, "initializer_range": 0.02,
        "reference": "qwen3",
        "program": {"arch": "qwen3-1.7b", "smoke": True,
                    "overrides": {"tie_embeddings": True}}},
    "tiny-stablelm": {
        "source": "test", "reduced": [], "hidden_size": 64,
        "intermediate_size": 160, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 512, "rope_theta": 10000, "layer_norm_eps": 1e-05,
        "partial_rotary_factor": 0.25, "use_qkv_bias": True,
        "tie_word_embeddings": False, "initializer_range": 0.02,
        "reference": "stablelm",
        "program": {"arch": "stablelm-1.6b", "smoke": True,
                    "overrides": {"qkv_bias": True}}},
}
# wide enough that 4-bit projections move the served tokens (the control)
TINY_CONFIGS["test-qwen3"] = dict(
    TINY_CONFIGS["tiny-qwen3"], hidden_size=256, intermediate_size=768,
    num_hidden_layers=4, head_dim=64, vocab_size=4096,
    program={"arch": "qwen3-1.7b", "smoke": True,
             "overrides": {"tie_embeddings": True, "d_model": 256,
                           "d_ff": 768, "n_layers": 4, "n_periods": 4,
                           "head_dim": 64, "vocab": 4096}})
# the same, with the benchmark's local-attention q/k bias, so that the
# served tokens depend on the KV cache (the cache faults)
TINY_CONFIGS["test-stablelm"] = dict(
    TINY_CONFIGS["tiny-stablelm"], hidden_size=256, intermediate_size=768,
    num_hidden_layers=4, vocab_size=4096,
    assumed={"rotary_qk_bias_std": 4.0},
    program={"arch": "stablelm-1.6b", "smoke": True,
             "overrides": {"qkv_bias": True, "d_model": 256, "d_ff": 768,
                           "n_layers": 4, "n_periods": 4, "vocab": 4096}})

TINY_TRAFFIC = {
    "tiny.chat": {
        "tiers": {"mode": "surrogate_fast", "families": ["exact"],
                  "mix": {"exact": 1}},
        "strata_per_tier": 4,
        "arrival": {"kind": "open", "rate_per_s": 8.0},
        "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
        "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
        "engine": {"slots_per_tier": 4, "max_len": 48,
                   "prompt_buckets": [32], "group_buckets": [1]},
        "lead_in_s": 0.5, "trace_s": 1.0, "sample_per_lane": 3},
    "tiny.batch": {
        "tiers": {"mode": "surrogate_fast", "families": ["exact"],
                  "mix": {"exact": 1}},
        "strata_per_tier": 4,
        "arrival": {"kind": "closed", "clients": 4,
                    "max_requests_per_s": 200},
        "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
        "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
        "engine": {"slots_per_tier": 4, "max_len": 48,
                   "prompt_buckets": [32], "group_buckets": [1]},
        "lead_in_s": 0.5, "trace_s": 1.0, "sample_per_lane": 3},
    "test.chat": {
        "tiers": {"mode": "surrogate_fast", "families": ["exact"],
                  "mix": {"exact": 1}},
        "strata_per_tier": 4,
        "arrival": {"kind": "open", "rate_per_s": 8.0},
        "prompt": {"median": 16, "sigma": 0.5, "min": 4, "max": 32},
        "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
        "engine": {"slots_per_tier": 4, "max_len": 64,
                   "prompt_buckets": [32], "group_buckets": [1]},
        "lead_in_s": 0.5, "trace_s": 1.0, "sample_per_lane": 6},
}


def make_root(tmp, configs=("tiny-qwen3",), traffic=("tiny.chat",),
              limit: float = 1.0) -> str:
    """A benchmark root under `tmp` holding the benchmark's code and
    metric files plus the tiny cells `<config>.<traffic>`."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  "test_bench_*"))
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    spec["configs"], spec["workloads"] = [], []
    for c in configs:
        path = f"bench/configs/{c}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(TINY_CONFIGS[c], f)
        spec["configs"].append({"name": c, "source": "test", "file": path,
                                "reduced": [], "why": "test"})
        for t in traffic:
            with open(os.path.join(root, "bench", "traffic", t + ".json"),
                      "w") as f:
                json.dump(TINY_TRAFFIC[t], f)
            name = f"{c}.{t}"
            spec["workloads"].append({"name": name, "config": c,
                                      "traffic": t, "chips": 1,
                                      "why": "test"})
            lanes = TINY_TRAFFIC[t]["tiers"]["mix"]
            with open(os.path.join(root, "bench", "limits", name + ".json"),
                      "w") as f:
                json.dump({f"gap_req.{n}": limit for n in lanes}, f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
