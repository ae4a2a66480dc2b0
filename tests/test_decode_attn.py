"""Decode attention over the carried KV pool (`kernels/decode_attn.py`).

On a TPU, single-token decode over a carried pool attends with the
Pallas kernel; everywhere else with the XLA path in
`models/attention.attention_block`.  Held here, with the kernel in
interpret mode on the CPU:

  * kernel and XLA path agree on the same inputs, within 2 bf16 ulps of
    the output's largest magnitude (both compute bf16 x bf16 dots with
    f32 accumulation; they round the scores and the softmax weights at
    different points), for stablelm's (32 x 64, G = 1) and qwen3's
    (8 x 128, G = 2) KV head layouts, per-slot fill levels 0, a block's
    last and next row, a full slot and one past ``max_len``, and a
    scalar (lockstep) fill level, at the last layer of an L = 3 pool;
  * no block past a slot's last live block reaches the computation;
  * `LM.decode_step` with the kernel gives the XLA path's greedy tokens;
  * block shapes and the block-rounded live rows.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attn import kv_block, live_rows, \
    pool_decode_attention
from repro.models import attention
from repro.models.attention import attention_block, init_attention
from repro.models.common import OFF
from repro.models.transformer import LM

LAYOUTS = {"stablelm": (32, 32, 64), "qwen3": (16, 8, 128)}  # H, KH, D
L, D_MODEL = 3, 256


def _bf16_tol(ref):
    """2 ulps of bf16 (8 significant bits) at the largest |ref|."""
    big = float(jnp.abs(ref.astype(jnp.float32)).max())
    return 2 * 2.0 ** (np.floor(np.log2(big)) - 7)


def _pool_case(layout, seed=0):
    """A random (L, B, T, KH*D) pool three kernel blocks deep, random
    in every row (rows past a fill level too), and attention weights
    that give peaked softmaxes."""
    h, kh, d = LAYOUTS[layout]
    rows, _ = kv_block(1 << 16, kh * d, d, 2)
    t = 3 * rows
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = init_attention(ks[0], D_MODEL, h, kh, d, qkv_bias=False,
                            qk_norm=False)
    for name, key in (("wq", ks[1]), ("wk", ks[2])):
        w = params[name]
        params[name] = dataclasses.replace(
            w, value=(jax.random.normal(key, w.value.shape)
                      / np.sqrt(D_MODEL)).astype(w.value.dtype))
    pool = jax.random.normal(ks[3], (2, L, 6, t, kh * d)).astype(
        jnp.bfloat16)
    x = jax.random.normal(jax.random.fold_in(ks[0], 1), (6, 1, D_MODEL)
                          ).astype(jnp.bfloat16)
    return params, pool[0], pool[1], x, rows, t


def _fills(rows, t, kind):
    if kind == "slot":   # empty, a block's last and next row, mid-block,
        return jnp.asarray([0, rows - 1, rows, rows + 37, t - 1, t + 3],
                           jnp.int32)       # full, past max_len (dropped)
    return jnp.int32(rows + 5)


@pytest.mark.parametrize("pos_kind", ["slot", "lockstep"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_matches_xla_decode(monkeypatch, layout, pos_kind):
    """attention_block's single-token decode over the carried pool: the
    kernel (interpret) and the XLA path on the same q, pool and fills."""
    h, kh, d = LAYOUTS[layout]
    params, kp, vp, x, rows, t = _pool_case(layout)
    pos = _fills(rows, t, pos_kind)
    got = {}

    def both(q, ck, cv, layer, pos, xla_sdpa):
        got["kernel"] = pool_decode_attention(q, ck, cv, layer, pos,
                                              interpret=True)
        got["xla"] = xla_sdpa()
        return got["xla"]

    monkeypatch.setattr(attention, "_pool_decode_sdpa", both)
    positions = jnp.broadcast_to(jnp.minimum(pos, t - 1), (6,))[:, None]
    attention_block(params, x, n_heads=h, n_kv_heads=kh, head_dim=d,
                    rope_fraction=1.0, rope_theta=1e4, qk_norm=False,
                    ctx=OFF, positions=positions,
                    cache={"k": kp, "v": vp, "pos": pos},
                    layer=jnp.int32(L - 1))
    k, r = got["kernel"], got["xla"]
    assert k.shape == r.shape == (6, 1, h, d) and k.dtype == r.dtype
    err = jnp.abs(k.astype(jnp.float32) - r.astype(jnp.float32)).max()
    assert float(err) <= _bf16_tol(r), (float(err), _bf16_tol(r))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_kernel_reads_no_block_past_the_fill_level(layout):
    """NaN in every block past each slot's last live block changes
    nothing: no such block reaches the computation (on the chip, the
    clamped block index also skips its DMA)."""
    h, kh, d = LAYOUTS[layout]
    _, kp, vp, _, rows, t = _pool_case(layout)
    pos = _fills(rows, t, "slot")
    q = jax.random.normal(jax.random.PRNGKey(3), (6, 1, h, d))
    dead = (jnp.arange(t)[None, :]
            >= jnp.asarray(live_rows(np.asarray(pos), t, rows))[:, None])
    dead = dead[None, :, :, None]
    run = functools.partial(pool_decode_attention, q, layer=L - 1,
                            pos=pos, interpret=True)
    clean = run(kp, vp)
    poisoned = run(jnp.where(dead, jnp.nan, kp),
                   jnp.where(dead, jnp.nan, vp))
    assert bool(jnp.isfinite(clean.astype(jnp.float32)).all())
    assert np.array_equal(np.asarray(clean, np.float32),
                          np.asarray(poisoned, np.float32))


def test_decode_step_kernel_gives_the_xla_tokens(monkeypatch):
    """Four greedy `LM.decode_step` rounds from a ragged prefill: the
    kernel path gives the XLA path's tokens, and logits within bf16
    rounding."""
    cfg = get_config("stablelm-1.6b", smoke=True)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (3, 12)), jnp.int32)
    lens = jnp.asarray([12, 7, 3], jnp.int32)
    lg0, caches0 = lm.prefill(params, {"tokens": toks, "lengths": lens,
                                       "max_len": 40})

    def greedy():
        caches, pos = caches0, lens
        tok = jnp.argmax(lg0[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out, logits = [], []
        step = jax.jit(lambda *a: lm.decode_step(*a))
        for _ in range(4):
            lg, caches = step(params, caches, tok, pos)
            tok = jnp.argmax(lg[:, -1], axis=-1)[:, None].astype(jnp.int32)
            out.append(np.asarray(tok[:, 0]))
            logits.append(lg[:, -1].astype(jnp.float32))
            pos = pos + 1
        return np.stack(out), jnp.stack(logits)

    ref_tok, ref_lg = greedy()
    traced = []

    def kernel(q, ck, cv, layer, pos, xla_sdpa):
        traced.append(ck.shape)
        return pool_decode_attention(q, ck, cv, layer, pos, interpret=True)

    monkeypatch.setattr(attention, "_pool_decode_sdpa", kernel)
    got_tok, got_lg = greedy()
    assert traced == [(cfg.n_periods, 3, 40, cfg.n_kv_heads * cfg.head_dim_)]
    np.testing.assert_array_equal(got_tok, ref_tok)
    err = float(jnp.abs(got_lg - ref_lg).max())
    assert err <= 4 * _bf16_tol(ref_lg), err


def test_kv_block_shapes():
    """Blocks of about 1 MiB: whole heads, all of KH*D up to 2048
    lanes, rows a multiple of 16 dividing T (else all of T)."""
    assert kv_block(4096, 2048, 64, 2) == (256, 2048)     # stablelm
    assert kv_block(4096, 1024, 128, 2) == (512, 1024)    # qwen3
    assert kv_block(4096, 8192, 128, 2) == (256, 2048)    # 64 x 128
    assert kv_block(4096, 40 * 128, 128, 2) == (256, 1280)
    assert kv_block(768, 2048, 64, 2) == (256, 2048)
    assert kv_block(100, 2048, 64, 2) == (100, 2048)
    assert kv_block(40, 64, 16, 2) == (40, 64)


def test_live_rows_round_up_to_blocks():
    np.testing.assert_array_equal(
        live_rows([0, 255, 256, 600, 4095, 5000], 4096, 256),
        [256, 256, 512, 768, 4096, 4096])
