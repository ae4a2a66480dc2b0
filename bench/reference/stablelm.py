"""Plain float32 forward of StableLM 2 (dense), after the published
`StableLmForCausalLM`: pre-LayerNorm decoder layers (sequential
residual) with full multi-head attention, a q/k/v bias, RoPE on the
first `partial_rotary_factor` of each head's dims, a SwiGLU MLP, a
final LayerNorm and an untied head.

It reads the benchmark's weights (bench/weights.py) and nothing of the
program.  Every matmul runs in float32 at the highest precision, over
one whole sequence, with no cache and no batching.  Departure: rotary
pairs are adjacent dims (2i, 2i+1) of the rotated part instead of
(i, i + rot/2); with random weights that is the same model with q/k
columns permuted.

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


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + \
        b.astype(jnp.float32)


def rope(x, pos, theta: float, rot: int):
    """x (S, heads, hd); rotates adjacent pairs of the first `rot` dims
    and passes the rest through."""
    xr, xp = x[..., :rot], x[..., rot:]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None, None].astype(jnp.float32) * inv     # (S, 1, rot/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    yr = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1)


def attention(q, k, v, block: int = 256):
    """Causal softmax attention; q, k, v (S, H, hd).  Queries are
    taken `block` at a time, each against every key, to bound memory."""
    s, h, hd = q.shape
    block = min(block, s)
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
    eps = float(cfg["layer_norm_eps"])
    theta = float(cfg["rope_theta"])
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    rot = int(hd * cfg["partial_rotary_factor"])
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in ("norm1.scale", "norm1.bias", "norm2.scale",
                                "norm2.bias", "wq", "wk", "wv", "wo", "bq",
                                "bk", "bv", "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        d = x.shape[-1]
        a = layer_norm(x, lw["norm1.scale"], lw["norm1.bias"], eps)
        q = linear(a, lw["wq"].reshape(d, h * hd), quant_bits)
        k = linear(a, lw["wk"].reshape(d, h * hd), quant_bits)
        v = linear(a, lw["wv"].reshape(d, h * hd), quant_bits)
        q = q.reshape(s, h, hd) + lw["bq"].astype(jnp.float32)
        k = k.reshape(s, h, hd) + lw["bk"].astype(jnp.float32)
        v = v.reshape(s, h, hd) + lw["bv"].astype(jnp.float32)
        o = attention(rope(q, pos, theta, rot), rope(k, pos, theta, rot), v)
        x = x + linear(o.reshape(s, h * hd), lw["wo"].reshape(h * hd, d),
                       quant_bits)
        m = layer_norm(x, lw["norm2.scale"], lw["norm2.bias"], eps)
        g = linear(m, lw["w_gate"], quant_bits)
        u = linear(m, lw["w_up"], quant_bits)
        return x + linear(jax.nn.silu(g) * u, lw["w_down"], quant_bits), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = layer_norm(x, w["final_norm.scale"], w["final_norm.bias"], eps)
    if rows is not None:
        x = x[rows]
    return jnp.matmul(x, w["head"].astype(jnp.float32), precision=HI)
