"""Operation and byte counts, and the chips' published peaks.

Counts come from shapes alone (a model configuration file and the
lengths actually served), never from the program or its traces, so a
change to the program cannot move the yardstick.
"""

from __future__ import annotations

from typing import Dict

# Published peaks of one chip, keyed by `device.device_kind` as JAX
# reports it.  Source: Google Cloud documentation, "TPU v5e" (per chip:
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of `device_kind`; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def layer_matmuls(cfg: Dict) -> Dict[str, tuple]:
    """(K, N) of each weight matmul of one decoder layer, from a
    configuration file's published sizes."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    return {"q": (d, h * hd), "k": (d, kh * hd), "v": (d, kh * hd),
            "o": (h * hd, d), "gate": (d, ff), "up": (d, ff),
            "down": (ff, d)}


def layer_macs_per_token(cfg: Dict) -> int:
    """Weight MACs of one decoder layer for one token."""
    return sum(k * n for k, n in layer_matmuls(cfg).values())


def body_macs_per_token(cfg: Dict) -> int:
    """Non-embedding weight MACs per token over all layers."""
    return cfg["num_hidden_layers"] * layer_macs_per_token(cfg)


def head_macs_per_token(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attn_macs(cfg: Dict, n_keys: int) -> int:
    """Attention MACs of one query token over `n_keys` keys, all
    layers: q.k scores and the weighted sum of values."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return cfg["num_hidden_layers"] * 2 * h * hd * n_keys


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    """Model FLOPs to prefill one prompt at its true length: every
    position's weight matmuls, causal attention over the positions up
    to it, and the head for the one position whose logits are used."""
    p = int(prompt_len)
    weights = p * body_macs_per_token(cfg)
    attn = attn_macs(cfg, 1) * p * (p + 1) // 2
    return 2.0 * (weights + attn + head_macs_per_token(cfg))


def decode_flops(cfg: Dict, context: int) -> float:
    """Model FLOPs to decode one token whose query sees `context` keys
    (the cache and itself)."""
    return 2.0 * (body_macs_per_token(cfg) + attn_macs(cfg, context)
                  + head_macs_per_token(cfg))


def gemm_work(m: int, k: int, n: int, in_bytes: int = 4,
              out_bytes: int = 4) -> Dict[str, float]:
    """Operations and compulsory bytes of one (M, K) x (K, N) matmul:
    2 ops per product, each operand read once and the output written
    once at the dtypes the kernel takes and gives."""
    return {"ops": 2.0 * m * k * n,
            "bytes": float(in_bytes * (m * k + k * n) + out_bytes * m * n)}


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float],
                     ops_key: str = "int8_ops") -> Dict[str, float]:
    """The least time the chip allows for `work`, and which bound sets
    it ("compute" or "memory")."""
    t_ops = work["ops"] / peak[ops_key]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
