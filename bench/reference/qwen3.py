"""Plain float32 forward of Qwen3 (dense), after the published
`Qwen3ForCausalLM`: pre-RMSNorm decoder layers with grouped-query
attention, RMSNorm on each head's q and k before RoPE, a SwiGLU MLP,
a final RMSNorm and a head tied to the embedding.

It reads the benchmark's weights (bench/weights.py) and nothing of the
program.  Every matmul runs in float32 at the highest precision, over
one whole sequence, with no cache and no batching.  Departure: rotary
pairs are adjacent dims (2i, 2i+1) instead of (i, i + hd/2); with
random weights that is the same model with q/k columns permuted.

`quant_bits` fake-quantizes the inputs (one scale per call) and weights
(one scale per output column) of the seven projections of each layer:
the lower-precision control of the benchmark's comparison.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fake_quant(x, bits: int, axis=None):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / qmax
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def linear(x, w, bits: Optional[int]):
    """x (S, K) @ w (K, N) in float32."""
    w = w.astype(jnp.float32)
    if bits is not None:
        x = fake_quant(x, bits)
        w = fake_quant(w, bits, axis=0)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def rope(x, pos, theta: float):
    """x (S, heads, hd); rotates every adjacent pair of dims."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv     # (S, 1, hd/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def attention(q, k, v, block: int = 256):
    """Causal softmax attention; q (S, H, hd), k/v (S, KH, hd).  Queries are
    taken `block` at a time, each against every key, to bound memory."""
    s, h, hd = q.shape
    block = min(block, s)
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(s)

    def one(args):
        i, qi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / jnp.sqrt(
            jnp.float32(hd))
        causal = kpos[None, :] <= (i * block + jnp.arange(block))[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HI)

    qb = q.reshape(s // block, block, h, hd)
    return jax.lax.map(one, (jnp.arange(s // block), qb)).reshape(s, h, hd)


def forward(w: Dict, tokens, cfg: Dict, quant_bits: Optional[int] = None,
            rows=None):
    """Logits, float32, of one sequence `tokens` (S,): at every position
    (S, V), or at the positions `rows` only (R, V).  S must be a
    multiple of the attention block or smaller than it."""
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in ("norm1.scale", "norm2.scale", "wq", "wk",
                                "wv", "wo", "q_norm", "k_norm", "w_gate",
                                "w_up", "w_down")}

    def layer(x, lw):
        d = x.shape[-1]
        a = rms_norm(x, lw["norm1.scale"], eps)
        q = linear(a, lw["wq"].reshape(d, h * hd), quant_bits)
        k = linear(a, lw["wk"].reshape(d, kh * hd), quant_bits)
        v = linear(a, lw["wv"].reshape(d, kh * hd), quant_bits)
        q = rms_norm(q.reshape(s, h, hd), lw["q_norm"], eps)
        k = rms_norm(k.reshape(s, kh, hd), lw["k_norm"], eps)
        o = attention(rope(q, pos, theta), rope(k, pos, theta),
                      v.reshape(s, kh, hd))
        x = x + linear(o.reshape(s, h * hd), lw["wo"].reshape(h * hd, d),
                       quant_bits)
        m = rms_norm(x, lw["norm2.scale"], eps)
        g = linear(m, lw["w_gate"], quant_bits)
        u = linear(m, lw["w_up"], quant_bits)
        return x + linear(jax.nn.silu(g) * u, lw["w_down"], quant_bits), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = rms_norm(x, w["final_norm.scale"], eps)
    if rows is not None:
        x = x[rows]
    return jnp.matmul(x, w["embed"].astype(jnp.float32).T, precision=HI)
