"""Random weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: `make` draws them from the seed in the
benchmark's own flat layout (bfloat16, the type they are served in,
layers stacked on a leading axis), and the plain reference reads them
from there.  `to_program` hands the same buffers to the program in its
parameter tree; it copies nothing.

Layout (L layers, d hidden, H/KH heads of hd, ff intermediate, V vocab):
  embed (V, d); head (d, V) unless tied; final_norm.scale/bias (d,)
  norm1.scale/bias, norm2.scale/bias (L, d)
  wq (L, d, H, hd); wk, wv (L, d, KH, hd); wo (L, H, hd, d)
  bq (L, H, hd); bk, bv (L, KH, hd)      -- with a q/k/v bias
  q_norm, k_norm (L, hd)                 -- with q/k RMSNorm
  w_gate, w_up (L, d, ff); w_down (L, ff, d)
Rotary pairs are adjacent dims (2i, 2i+1) of each head's rotated part.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def sizes(cfg: Dict) -> Dict[str, int]:
    h = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": h, "KH": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // h,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def layernorm(cfg: Dict) -> bool:
    return "layer_norm_eps" in cfg


def qkv_bias(cfg: Dict) -> bool:
    return bool(cfg.get("use_qkv_bias") or cfg.get("attention_bias"))


def qk_norm(cfg: Dict) -> bool:
    """Qwen3 normalises q and k per head (RMSNorm over head_dim)."""
    return cfg.get("reference") == "qwen3"


def shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init kind) of every weight."""
    s = sizes(cfg)
    L, d, H, KH, hd, ff, V = (s[k] for k in
                              ("L", "d", "H", "KH", "hd", "ff", "V"))
    out = {"embed": ((V, d), "normal"),
           "final_norm.scale": ((d,), "scale"),
           "norm1.scale": ((L, d), "scale"),
           "norm2.scale": ((L, d), "scale"),
           "wq": ((L, d, H, hd), "normal"),
           "wk": ((L, d, KH, hd), "normal"),
           "wv": ((L, d, KH, hd), "normal"),
           "wo": ((L, H, hd, d), "normal"),
           "w_gate": ((L, d, ff), "normal"),
           "w_up": ((L, d, ff), "normal"),
           "w_down": ((L, ff, d), "normal")}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((d, V), "normal")
    if layernorm(cfg):
        for n in ("final_norm", "norm1", "norm2"):
            out[n + ".bias"] = (out[n + ".scale"][0], "normal")
    if qkv_bias(cfg):
        out["bq"] = ((L, H, hd), "normal")
        out["bk"] = ((L, KH, hd), "normal")
        out["bv"] = ((L, KH, hd), "normal")
    if qk_norm(cfg):
        out["q_norm"] = ((L, hd), "scale")
        out["k_norm"] = ((L, hd), "scale")
    return out


def key_data(seed: int) -> np.ndarray:
    """A threefry key from any whole-number seed (64 bits and more)."""
    return np.random.SeedSequence(int(seed)).generate_state(2).astype(
        np.uint32)


def rotary_dims(cfg: Dict) -> int:
    s = sizes(cfg)
    return int(s["hd"] * float(cfg.get("partial_rotary_factor", 1.0)))


def local_bias_std(cfg: Dict) -> float:
    """Std of the q/k bias shared on the rotated dims (0: none).  A bias
    that q and k share turns RoPE into a score that peaks at distance 0,
    so heads attend to recent tokens as trained models' do; with plain
    N(0, 0.02) weights attention is near-uniform over thousands of
    positions and the served tokens do not depend on the KV cache."""
    return float(cfg.get("assumed", {}).get("rotary_qk_bias_std", 0.0))


def make(cfg: Dict, seed: int):
    """All weights of `cfg` from `seed`, on the default device, in one
    jitted call."""
    import jax
    import jax.numpy as jnp

    spec = shapes(cfg)
    std = float(cfg["initializer_range"])
    names = sorted(spec)
    local = local_bias_std(cfg)
    if local and not qkv_bias(cfg):
        raise ValueError("rotary_qk_bias_std needs a q/k/v bias")
    s = sizes(cfg)
    rot = rotary_dims(cfg)

    def build(kd):
        keys = jax.random.split(jax.random.wrap_key_data(kd), len(names) + 1)
        out = {}
        for name, k in zip(names, keys):
            shape, kind = spec[name]
            v = jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)
            out[name] = v + jnp.bfloat16(1.0) if kind == "scale" else v
        if local:
            b = jax.random.normal(keys[-1], (s["L"], s["KH"], rot),
                                  jnp.bfloat16) * jnp.bfloat16(local)
            out["bk"] = out["bk"].at[..., :rot].set(b)
            out["bq"] = out["bq"].at[..., :rot].set(
                jnp.repeat(b, s["H"] // s["KH"], axis=1))
        return out

    return jax.jit(build)(jnp.asarray(key_data(seed)))


def program_config(cfg: Dict):
    """The program's ModelConfig for this configuration: the repo preset
    with the file's overrides, checked size by size against the file."""
    import dataclasses

    from repro.configs import get_config

    prog = dataclasses.replace(
        get_config(cfg["program"]["arch"],
                   smoke=bool(cfg["program"].get("smoke", False))),
        **cfg["program"]["overrides"])
    s = sizes(cfg)
    want = {"n_layers": s["L"], "d_model": s["d"], "n_heads": s["H"],
            "n_kv_heads": s["KH"], "head_dim_": s["hd"], "d_ff": s["ff"],
            "vocab": s["V"], "tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": qkv_bias(cfg), "qk_norm": qk_norm(cfg),
            "norm": "layernorm" if layernorm(cfg) else "rmsnorm",
            "rope_theta": float(cfg["rope_theta"]),
            "rope_fraction": float(cfg.get("partial_rotary_factor", 1.0)),
            "act": "swiglu"}
    bad = {k: (getattr(prog, k), v) for k, v in want.items()
           if getattr(prog, k) != v}
    if bad:
        raise ValueError(f"program config departs from the file "
                         f"(program, file): {bad}")
    return prog


# program parameter path -> benchmark weight name
_PROGRAM_NAMES = {
    ("embed",): "embed", ("head",): "head",
    ("final_norm", "scale"): "final_norm.scale",
    ("final_norm", "bias"): "final_norm.bias",
    ("norm1", "scale"): "norm1.scale", ("norm1", "bias"): "norm1.bias",
    ("norm2", "scale"): "norm2.scale", ("norm2", "bias"): "norm2.bias",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("attn", "bq"): "bq", ("attn", "bk"): "bk",
    ("attn", "bv"): "bv", ("attn", "q_norm"): "q_norm",
    ("attn", "k_norm"): "k_norm",
    ("mlp", "wi"): "w_gate", ("mlp", "wg"): "w_up", ("mlp", "wo"): "w_down",
}


def to_program(weights: Dict, prog_cfg):
    """The program's parameter tree over the same buffers.  Its
    structure and logical specs come from the program's own init, traced
    abstractly; every leaf must match a weight in shape and dtype."""
    import jax

    from repro.models.common import Param
    from repro.models.transformer import LM

    abstract = jax.eval_shape(LM(prog_cfg).init, jax.random.PRNGKey(0))
    used = set()

    def fill(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "idx", None))
                     for p in path)
        keys = tuple(k for k in keys if k not in ("body", "0"))
        name = _PROGRAM_NAMES.get(keys)
        if name is None or name not in weights:
            raise KeyError(f"no benchmark weight for program leaf {keys}")
        w = weights[name]
        if (tuple(w.shape) != tuple(leaf.value.shape)
                or w.dtype != leaf.value.dtype):
            raise ValueError(f"{name}: benchmark {w.shape}/{w.dtype} vs "
                             f"program {leaf.value.shape}/"
                             f"{leaf.value.dtype}")
        used.add(name)
        return Param(w, leaf.spec)

    tree = jax.tree_util.tree_map_with_path(
        fill, abstract, is_leaf=lambda x: isinstance(x, Param))
    unused = set(weights) - used
    if unused:
        raise ValueError(f"benchmark weights the program does not take: "
                         f"{sorted(unused)}")
    return tree
