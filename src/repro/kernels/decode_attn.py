"""Pallas TPU kernel: single-token decode attention against the carried
KV pool.

The decode scan carries every dense layer's K and V as one stacked
``(L, B, T, KH*D)`` pool (`models/attention._put_kv`).  This kernel
reads layer ``li`` of it where it lies: the layer index and the slots'
fill levels are scalar-prefetch operands, so no layer is sliced out,
relaid or copied, and no block past a slot's fill level is fetched.

Grid = (slot, KV-head block, T block), T innermost.  The K/V
``index_map`` clamps the T block to the slot's last live block: a
repeated block index issues no DMA, and ``pl.when`` skips the compute
of the dead steps.  Online softmax with the running max, sum and
accumulator in f32 VMEM scratch.

The pool stays flat in ``KH*D`` lanes.  A head block spans whole heads
and a multiple of 128 lanes (or all of ``KH*D``), and its query is made
block-diagonal: row ``(g, h)`` holds head ``h``'s group-``g`` query in
head ``h``'s ``D`` lanes and zeros elsewhere.  Then scores are one MXU
dot ``Q_bd . K_blk^T`` and ``p . V_blk`` keeps each row's own head in
its own lanes, with no lane reshape.  That spends FLOPs, not bytes, on
a read bound by bandwidth.

Numerics are those of the XLA decode path: bf16 x bf16 dots with f32
accumulation, scores scaled by ``1/sqrt(D)``, rows past the fill level
masked, softmax in f32, ``p`` cast to the pool dtype for ``p . V``, and
the output in the pool dtype.  A masked row has weight exactly 0 there,
so skipping it is the same mathematics.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# one K (or V) block's bytes: double-buffered K and V blocks then take
# 4 MiB of the 16 MiB scoped VMEM, the rest is the query and f32 scratch
_KV_BLOCK_BYTES = 1 << 20
_MAX_BLOCK_LANES = 2048


def _head_block_lanes(khd: int, d: int) -> int:
    """Lanes per KV-head block: all of ``KH*D`` when it is at most
    2048 lanes, else the widest whole-head multiple of 128 lanes that
    divides it (at most 2048, which bounds the block-diagonal query's
    wasted rows), else all of it."""
    if khd <= _MAX_BLOCK_LANES:
        return khd
    step = math.lcm(128, d)
    for lanes in range(_MAX_BLOCK_LANES // step * step, 0, -step):
        if khd % lanes == 0:
            return lanes
    return khd


def _kv_block_rows(t: int, lanes: int, itemsize: int) -> int:
    """Pool rows per T block: the largest multiple of 16 (the bf16
    sublane tile) that divides ``t`` and keeps one block within
    `_KV_BLOCK_BYTES`; ``t`` itself (one block) when none does."""
    cap = max(16, _KV_BLOCK_BYTES // (lanes * itemsize))
    for rows in range(min(cap, t) // 16 * 16, 0, -16):
        if t % rows == 0:
            return rows
    return t


def kv_block(t: int, khd: int, d: int, itemsize: int):
    """(rows, lanes) of the kernel's K/V block over a pool ``t`` deep
    and ``khd = KH*D`` wide, of ``itemsize``-byte elements."""
    lanes = _head_block_lanes(khd, d)
    return _kv_block_rows(t, lanes, itemsize), lanes


def _kernel(li_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, rows, t_len, d, g, hb, scale):
    b, t = pl.program_id(0), pl.program_id(2)
    last = jnp.minimum(pos_ref[b], t_len - 1)       # last live row

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t * rows <= last)
    def _step():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (R, rows)
        row = t * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row <= last, s, NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            corr * l_ref[...][:, :1] + p.sum(axis=-1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _flush():
        o = acc_ref[...] / l_ref[...][:, :1]                # (R, lanes)
        shape = (hb, o.shape[1])
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * d
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        own = (lane >= head) & (lane < head + d)
        for gi in range(g):                   # rows are (g, h): keep each
            og = jnp.where(own, o[gi * hb:(gi + 1) * hb], 0.0)  # head's
            o_ref[0, 0, gi:gi + 1, :] = og.sum(                 # lanes
                axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pool_decode_attention(q, k_pool, v_pool, layer, pos, *,
                          interpret: bool = False):
    """Decode attention of one query token per slot against layer
    ``layer`` of the stacked pools.

    q: (B, 1, H, D); k_pool, v_pool: (L, B, T, KH*D); layer: int32
    scalar; pos: int32 scalar (lockstep) or (B,) per-slot fill levels,
    the row this step's key was written at (rows ``<= pos`` are live; a
    slot at ``pos >= T`` had its write dropped and reads all ``T``).
    Returns (B, 1, H, D) in the pool dtype, heads ordered as
    ``q.reshape(B, 1, KH, G, D)``."""
    nl, nb, t_len, khd = k_pool.shape
    h, d = q.shape[2], q.shape[3]
    kh = khd // d
    g = h // kh
    dt = k_pool.dtype
    rows, lanes = kv_block(t_len, khd, d, jnp.dtype(dt).itemsize)
    hb, nhb = lanes // d, khd // lanes
    nt = t_len // rows

    # block-diagonal query: (B, nhb, G*hb, lanes), rows ordered (g, h)
    qh = q.reshape(nb, nhb, hb, g, d).astype(dt).transpose(0, 1, 3, 2, 4)
    q_bd = (qh[:, :, :, :, None, :]
            * jnp.eye(hb, dtype=dt)[:, :, None]).reshape(
                nb, nhb, g * hb, lanes)
    li = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (nb,))

    def kv_map(b, j, t, li_ref, pos_ref):
        last = jnp.clip(pos_ref[b], 0, t_len - 1) // rows
        return li_ref[0], b, jnp.minimum(t, last), j

    kv_spec = pl.BlockSpec((None, None, rows, lanes), kv_map)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, t_len=t_len, d=d, g=g,
                          hb=hb, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, nhb, nt),
            in_specs=[
                pl.BlockSpec((1, 1, g * hb, lanes),
                             lambda b, j, t, *_: (b, j, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, g, lanes),
                                   lambda b, j, t, *_: (b, j, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g * hb, 128), jnp.float32),
                            pltpu.VMEM((g * hb, 128), jnp.float32),
                            pltpu.VMEM((g * hb, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nb, nhb, g, lanes), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
    )(li, pos, q_bd, k_pool, v_pool)
    # (B, nhb, G, hb, D) -> heads ordered (kh, g)
    out = out.reshape(nb, nhb, g, hb, d).transpose(0, 1, 3, 2, 4)
    return out.reshape(nb, 1, h, d)
