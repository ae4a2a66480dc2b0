"""Pallas TPU kernel: fused surrogate CiM GEMM (the production path).

The calibrated surrogate needs two contractions over the same operands
(DESIGN.md §2):   D = A@B   and   SQ = A^2 @ B^2.
Computed naively that is two HBM passes over A and B; this kernel fuses
them — each (bm x bk) / (bk x bn) tile pair is read into VMEM once and
fed to the MXU twice (int8 x int8 -> int32 for D, f32 for SQ), halving
the memory traffic of surrogate mode.  Dequantization, the (1+mu) bias
and the noise term are cheap O(MN) epilogues left to XLA fusion.

Accumulators: D in int32 (bit-exact dot of int8 operands), SQ in f32
(it only feeds sqrt(var); |rel err| <= 2^-24 * K is irrelevant there).

Entry points (DESIGN.md §8): ``cim_gemm_core``/``cim_gemm`` (int8 in,
the registry-oracle surface) and ``cim_gemm_fused`` (f32 operands in ->
f32 out in ONE pallas_call: per-tensor/per-channel quantization on tile
load and the full surrogate epilogue — dequant scale, (1+mu) bias,
sqrt(var)*eps noise — on flush, with the scales as SMEM/VMEM operands).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .approx_matmul import _pad2, _quantize_tile


def _kernel(x_ref, w_ref, d_ref, sq_ref, accd_ref, accs_ref, *, need_sq):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accd_ref[...] = jnp.zeros_like(accd_ref)
        if need_sq:
            accs_ref[...] = jnp.zeros_like(accs_ref)

    a = x_ref[...]
    b = w_ref[...]
    # int8 x int8 -> int32 on the MXU (Mosaic refuses an int32
    # accumulator over float operands)
    accd_ref[...] += jax.lax.dot(a.astype(jnp.int8), b.astype(jnp.int8),
                                 preferred_element_type=jnp.int32)
    if need_sq:
        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)
        accs_ref[...] += jax.lax.dot(af * af, bf * bf,
                                     preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        d_ref[...] = accd_ref[...]
        if need_sq:
            sq_ref[...] = accs_ref[...]
        else:
            sq_ref[...] = jnp.zeros_like(sq_ref)


@functools.partial(jax.jit, static_argnames=("need_sq", "block", "interpret"))
def cim_gemm_core(xq: jnp.ndarray, wq: jnp.ndarray, need_sq: bool = True,
                  block: tuple = (128, 128, 128),
                  interpret: bool = True):
    """Fused (D, SQ) over int8 operands. Returns (int32 (M,N), f32 (M,N))."""
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2, (xq.shape, wq.shape)
    bm, bk, bn = block
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    xp = jnp.pad(xq, ((0, pm), (0, pk)))
    wp = jnp.pad(wq, ((0, pk), (0, pn)))
    gm, gk, gn = (m + pm) // bm, (k + pk) // bk, (n + pn) // bn
    d, sq = pl.pallas_call(
        functools.partial(_kernel, need_sq=need_sq),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m + pm, n + pn), jnp.int32),
            jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return d[:m, :n], sq[:m, :n]


def cim_gemm(xq, wq, sx, sw, eps, mu: float, c0: float, c1: float,
             block: tuple = (128, 128, 128), interpret: bool = True):
    """Full surrogate GEMM in real units (see ref.cim_gemm_ref)."""
    need_sq = c1 > 0.0 and eps is not None
    d, sq = cim_gemm_core(xq, wq, need_sq=need_sq, block=block,
                          interpret=interpret)
    scale = sx * sw[None, :]
    out = (1.0 + mu) * d.astype(jnp.float32) * scale
    if eps is not None and (c0 > 0.0 or c1 > 0.0):
        k = xq.shape[-1]
        var = c0 * k * scale ** 2
        if need_sq:
            var = var + c1 * sq * scale ** 2
        out = out + jnp.sqrt(jnp.maximum(var, 0.0)) * eps
    return out


def _fused_kernel(sx_ref, x_ref, w_ref, sw_ref, eps_ref, d_ref, accd_ref,
                  accs_ref, *, bits, k_len, mu, c0, c1, stochastic):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accd_ref[...] = jnp.zeros_like(accd_ref)
        if stochastic and c1 > 0.0:
            accs_ref[...] = jnp.zeros_like(accs_ref)

    qmax = (1 << (bits - 1)) - 1
    aq = _quantize_tile(x_ref[...], sx_ref[0, 0], qmax)
    bq = _quantize_tile(w_ref[...], sw_ref[...], qmax)
    accd_ref[...] += jax.lax.dot(aq.astype(jnp.int8), bq.astype(jnp.int8),
                                 preferred_element_type=jnp.int32)
    if stochastic and c1 > 0.0:
        af = aq.astype(jnp.float32)
        bf = bq.astype(jnp.float32)
        accs_ref[...] += jax.lax.dot(af * af, bf * bf,
                                     preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        scale = sx_ref[0, 0] * sw_ref[...]                   # (1, bn)
        out = (1.0 + mu) * accd_ref[...].astype(jnp.float32) * scale
        if stochastic:
            var = c0 * k_len * scale ** 2
            if c1 > 0.0:
                var = var + c1 * accs_ref[...] * scale ** 2
            out = out + jnp.sqrt(jnp.maximum(var, 0.0)) * eps_ref[...]
        d_ref[...] = out


@functools.partial(jax.jit, static_argnames=("mu", "c0", "c1", "bits",
                                             "block", "interpret"))
def cim_gemm_fused(x, w, eps, mu: float, c0: float, c1: float,
                   bits: int = 8, block: tuple = (128, 128, 128),
                   interpret: bool = True):
    """Fused-quantization surrogate GEMM: f32 x (M,K), w (K,N) -> f32.

    Quantization scales are computed on-device (cheap XLA reductions)
    and enter the kernel as SMEM (per-tensor sx) / VMEM (per-channel
    sw) operands; D, SQ and the entire surrogate epilogue execute in
    one pallas_call.  ``eps`` may be None (deterministic bias term
    only).  Matches ref.cim_gemm_ref within fp32 tolerance.
    """
    from repro.core.quantization import quant_scale

    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    stochastic = eps is not None and (c0 > 0.0 or c1 > 0.0)
    sx2 = jnp.reshape(quant_scale(x, bits), (1, 1)).astype(jnp.float32)
    sw = quant_scale(w, bits, axis=0)                        # (1, N)
    bm, bk, bn = block
    pm, pk, pn = _pad2(m, k, n, block)
    xp = jnp.pad(x.astype(jnp.float32), ((0, pm), (0, pk)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, pk), (0, pn)))
    swp = jnp.pad(sw.reshape(1, -1).astype(jnp.float32), ((0, 0), (0, pn)),
                  constant_values=1.0)
    if stochastic:
        epsp = jnp.pad(eps.astype(jnp.float32), ((0, pm), (0, pn)))
    else:
        epsp = jnp.zeros((1, 1), jnp.float32)     # placeholder, never read
    gm, gk, gn = (m + pm) // bm, (k + pk) // bk, (n + pn) // bn
    eps_spec = (pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)) if stochastic
                else pl.BlockSpec(memory_space=pltpu.SMEM))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, bits=bits, k_len=k, mu=mu, c0=c0,
                          c1=c1, stochastic=stochastic),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            eps_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(sx2, xp, wp, swp, epsp)
    return out[:m, :n]
