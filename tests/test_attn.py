"""Fused CiM attention coverage (DESIGN.md §13).

The attention frontend (`cim_attention`) must be **bit-identical** to
the materialized oracle surface (`attn_materialized_oracle`: the same
integer math with the (B, H, Sq, Skv) score tensor written through HBM)
on every routed kernel, across the masking universe (causal / windowed
/ ragged prefill / single-token decode) and GQA group counts; carry the
STE backward (= exact float VJP); fall back per the documented
predicates; and execute through the zero-retrace executable cache like
every other frontend.  Also pins the `_chunked_attn` q-padding fix and
the attention rows of the shared autotune disk cache.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import approx_gemm, autotune
from repro.core.approx_gemm import (ATTN_MODES, AttnParams, GemmParams,
                                    _attn_bit_safe, attn_materialized_oracle,
                                    cim_attention, plan_attn,
                                    select_attn_kernel, trace_count)
from repro.models.attention import _chunked_attn, _cim_sdpa, _use_cim_attn
from repro.models.common import CiMParams

# (family, mode, expected kernel): every attention kernel family, incl.
# both LUT layouts via the nibble predicate
HW_CASES = [
    ("exact", "exact", "pallas_attn_mxu"),
    ("exact", "hardware", "pallas_attn_nibble"),
    ("appro42", "hardware", "pallas_attn_lut"),
    ("mitchell", "hardware", "pallas_attn_log"),
    ("log_our", "hardware", "pallas_attn_log"),
    ("appro42", "bit_exact", "attn_xla"),
]

# small ragged geometry + small tiles: every test kernel runs in
# interpret mode off-TPU, so tile counts dominate the suite's runtime
B, H, KH, SQ, SKV, D = 2, 4, 2, 21, 29, 12
BLOCK = (8, 16)


def _ops(b=B, sq=SQ, skv=SKV, h=H, kh=KH, d=D, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, sq, h, d))
    k = jax.random.normal(kk, (b, skv, kh, d))
    v = jax.random.normal(kv, (b, skv, kh, d))
    return q, k, v


def _full_pos(b, sq, skv):
    qpos = jnp.broadcast_to(jnp.arange(skv - sq, skv, dtype=jnp.int32),
                            (b, sq))
    kpos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32), (b, skv))
    kval = jnp.ones((b, skv), jnp.int32)
    return qpos, kpos, kval


def _oracle(q, k, v, gp, plan, qpos, kpos, kval):
    """Frontend-layout wrapper over the kernel-layout oracle surface."""
    t = lambda a: jnp.transpose(a, (0, 2, 1, 3))  # noqa: E731
    return t(attn_materialized_oracle(t(q), t(k), t(v), gp, plan,
                                      qpos, kpos, kval))


# ------------------------------------------------------------- routing ----


@pytest.mark.parametrize("family,mode,kernel", HW_CASES)
def test_attn_routing(family, mode, kernel):
    gp = GemmParams(family=family, bits=8, mode=mode)
    assert select_attn_kernel(family, mode, 8, spec=gp.spec).name == kernel
    plan = plan_attn(family, mode, 8, B, H, KH, SQ, SKV, D, AttnParams(),
                     spec=gp.spec)
    assert plan.entry.name == kernel
    assert plan.attn == AttnParams()


def test_attn_mode_and_geometry_validation():
    gp = GemmParams(family="appro42", bits=8, mode="hardware")
    q, k, v = _ops()
    with pytest.raises(ValueError):
        plan_attn("appro42", "surrogate", 8, B, H, KH, SQ, SKV, D)
    with pytest.raises(ValueError):      # H % KH != 0
        cim_attention(q[:, :, :3], k, v, gp)
    with pytest.raises(ValueError):      # per-token scales: linear-only
        cim_attention(q, k, v, GemmParams(family="appro42", bits=8,
                                          mode="hardware", per_token=True))
    assert "surrogate" not in ATTN_MODES


def test_attn_predicates_reject_unsafe_geometry():
    # 12-bit products overflow the f32-exact window on the MXU path but
    # fit the int32 paths
    assert not _attn_bit_safe(12, "mxu", 128, 128)
    assert _attn_bit_safe(8, "mxu", 128, 128)
    assert _attn_bit_safe(12, "log", 128, 128)
    # no registered kernel survives 16-bit operands
    with pytest.raises(ValueError):
        plan_attn("appro42", "hardware", 16, B, H, KH, SQ, SKV, D)


# -------------------------------------------- bit-identity vs oracle ----


@pytest.mark.parametrize("family,mode,kernel", HW_CASES)
@pytest.mark.parametrize("variant", ["causal", "window", "ragged",
                                     "decode"])
def test_attn_bit_identity_vs_materialized_oracle(family, mode, kernel,
                                                  variant):
    gp = GemmParams(family=family, bits=8, mode=mode)
    causal, window = True, None
    if variant == "decode":
        q, k, v = _ops(sq=1, seed=3)
        qpos, kpos, kval = _full_pos(B, 1, SKV)
        kval = (kpos < jnp.asarray([[23], [29]])).astype(jnp.int32)
    else:
        q, k, v = _ops(seed=3)
        qpos, kpos, kval = _full_pos(B, SQ, SKV)
        if variant == "window":
            window = 5
        elif variant == "ragged":
            kval = (kpos < jnp.asarray([[17], [29]])).astype(jnp.int32)
    plan = plan_attn(family, mode, 8, *q.shape[:1], H, KH, q.shape[1],
                     SKV, D, AttnParams(causal=causal, window=window),
                     block=BLOCK, spec=gp.spec)
    assert plan.entry.name == kernel
    got = cim_attention(q, k, v, gp, causal=causal, window=window,
                        q_positions=qpos, kv_positions=kpos,
                        kv_valid=kval, block=BLOCK)
    want = _oracle(q, k, v, gp, plan, qpos, kpos, kval)
    assert got.shape == q.shape
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got) == np.asarray(want)).all(), \
        f"{kernel} diverged from the materialized oracle ({variant})"


@pytest.mark.parametrize("kh", [1, 2, 4])
def test_attn_bit_identity_across_gqa_groups(kh):
    gp = GemmParams(family="appro42", bits=8, mode="hardware")
    q, k, v = _ops(kh=kh, seed=7)
    qpos, kpos, kval = _full_pos(B, SQ, SKV)
    plan = plan_attn("appro42", "hardware", 8, B, H, kh, SQ, SKV, D,
                     AttnParams(), block=BLOCK, spec=gp.spec)
    got = cim_attention(q, k, v, gp, q_positions=qpos, kv_positions=kpos,
                        kv_valid=kval, block=BLOCK)
    want = _oracle(q, k, v, gp, plan, qpos, kpos, kval)
    assert (np.asarray(got) == np.asarray(want)).all()


# ----------------------------------------------------------- backward ----


def test_attn_ste_backward_is_exact_float_vjp():
    from repro.kernels.attn_gemm import attn_float

    gp = GemmParams(family="appro42", bits=8, mode="hardware")
    q, k, v = _ops(seed=11)
    qpos, kpos, kval = _full_pos(B, SQ, SKV)
    t = lambda a: jnp.transpose(a, (0, 2, 1, 3))  # noqa: E731

    # linear loss: the upstream cotangent is then independent of the
    # (approximate) forward value, so STE == the float VJP exactly
    def loss(a):
        return cim_attention(a, k, v, gp, q_positions=qpos,
                             kv_positions=kpos, kv_valid=kval,
                             block=BLOCK).sum()

    def floss(a):
        return t(attn_float(t(a), t(k), t(v), qpos, kpos, kval)).sum()

    g = jax.grad(loss)(q)
    gf = jax.grad(floss)(q)
    assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(np.asarray(g), np.asarray(gf),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- model-layer routing ----


def test_use_cim_attn_gates():
    hw = CiMParams(mode="hardware", family="appro42", attn=True)
    assert _use_cim_attn(hw, is_cross=False)
    assert not _use_cim_attn(hw, is_cross=True)          # cross-attn
    assert not _use_cim_attn(
        CiMParams(mode="hardware", family="appro42"), False)   # attn off
    assert not _use_cim_attn(
        CiMParams(mode="surrogate_fast", family="appro42", attn=True),
        False)                                           # float mode


def test_cim_sdpa_falls_back_on_unsupported_geometry():
    # 12-bit exact: qmax^2 * head_dim overflows the f32-exact MXU
    # accumulator, so the bit-safety predicate rejects every entry (a
    # geometry rejection) -> the helper returns None and the caller
    # keeps the float path
    q, k, v = _ops(seed=13)
    qpos, kpos, kval = _full_pos(B, SQ, SKV)
    kw = dict(causal=True, window=None, qpos=qpos, kpos=kpos, kval=kval)
    p = CiMParams(mode="exact", family="exact", bits=12, attn=True)
    assert _cim_sdpa(q, k, v, p, **kw) is None
    # 16-bit appro42 hardware: no registered kernel serves the request
    # at all (a routing refusal) -> raises, never a silent float path
    p = CiMParams(mode="hardware", family="appro42", bits=16, attn=True)
    with pytest.raises(approx_gemm.RoutingError, match="registered"):
        _cim_sdpa(q, k, v, p, **kw)


def test_cim_sdpa_per_head_tiers_match_per_family_runs():
    heads = ("exact", "appro42", "appro42", "mitchell")
    p = CiMParams(mode="hardware", family="appro42", attn=True,
                  attn_heads=heads)
    q, k, v = _ops(seed=17)
    qpos, kpos, kval = _full_pos(B, SQ, SKV)
    out = _cim_sdpa(q, k, v, p, causal=True, window=None,
                    qpos=qpos, kpos=kpos, kval=kval)
    assert out is not None and out.shape == q.shape
    # expanding K/V to the per-q-head layout keeps per-head scales, so
    # each head must equal a single-family full run of the same head
    g = H // KH
    ke, ve = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    for i, fam in enumerate(heads):
        gp = GemmParams(family=fam, bits=8, mode="hardware")
        want = cim_attention(q[:, :, i:i + 1], ke[:, :, i:i + 1],
                             ve[:, :, i:i + 1], gp, q_positions=qpos,
                             kv_positions=kpos, kv_valid=kval)
        assert (np.asarray(out[:, :, i:i + 1])
                == np.asarray(want)).all(), f"head {i} ({fam})"


def test_cim_sdpa_rejects_wrong_head_count():
    p = CiMParams(mode="hardware", family="appro42", attn=True,
                  attn_heads=("exact",))
    q, k, v = _ops(seed=19)
    qpos, kpos, kval = _full_pos(B, SQ, SKV)
    with pytest.raises(ValueError):
        _cim_sdpa(q, k, v, p, causal=True, window=None,
                  qpos=qpos, kpos=kpos, kval=kval)


# --------------------------------------------- _chunked_attn q padding ----


@pytest.mark.parametrize("sq,qc", [(37, 16), (41, 8), (13, 13)])
def test_chunked_attn_prime_sq_pads_instead_of_degrading(sq, qc):
    """Regression (PR 7): `while sq % qc: qc -= 1` degraded to 1-row
    chunks for prime Sq.  The q axis now pads to a chunk multiple; the
    result must stay bit-identical to the unpadded single-chunk run."""
    q, k, v = _ops(sq=sq, skv=sq, seed=23)
    a = _chunked_attn(q, k, v, qc, 16, True, None, 0, sq)
    b = _chunked_attn(q, k, v, sq, 16, True, None, 0, sq)
    assert a.shape == q.shape
    assert (np.asarray(a) == np.asarray(b)).all()


def test_chunked_attn_q_padding_ragged_path():
    sq = 19
    q, k, v = _ops(sq=sq, skv=sq, seed=29)
    pos = jnp.broadcast_to(jnp.arange(sq), (B, sq))
    valid = (pos < jnp.asarray([[11], [19]]))
    info = (pos, pos, valid)
    a = _chunked_attn(q, k, v, 8, 8, True, None, 0, sq, seq_info=info)
    b = _chunked_attn(q, k, v, sq, 8, True, None, 0, sq, seq_info=info)
    assert (np.asarray(a[:, :11]) == np.asarray(b[:, :11])).all()
    assert (np.asarray(a) == np.asarray(b)).all()


# -------------------------------------------------- executable cache ----


def test_attn_zero_retrace_across_buckets_and_tiers():
    tiers = [GemmParams(family="appro42", bits=8, mode="hardware"),
             GemmParams(family="mitchell", bits=8, mode="hardware")]
    shapes = [(2, 21, 29), (2, 27, 31), (1, 9, 11)]   # two seq buckets

    def sweep():
        for gp in tiers:
            for (b, sq, skv) in shapes:
                q, k, v = _ops(b=b, sq=sq, skv=skv, seed=31)
                qpos, kpos, kval = _full_pos(b, sq, skv)
                cim_attention(q, k, v, gp, q_positions=qpos,
                              kv_positions=kpos, kv_valid=kval,
                              block=BLOCK)

    sweep()                                    # build + compile
    t0, n0 = trace_count(), approx_gemm.executable_cache_size()
    sweep()
    assert trace_count() == t0, "steady-state attention calls retraced"
    assert approx_gemm.executable_cache_size() == n0
    # same bucket, different shape: executable reused
    q, k, v = _ops(b=2, sq=24, skv=30, seed=37)
    qpos, kpos, kval = _full_pos(2, 24, 30)
    cim_attention(q, k, v, tiers[0], q_positions=qpos, kv_positions=kpos,
                  kv_valid=kval, block=BLOCK)
    assert approx_gemm.executable_cache_size() == n0


def test_attn_cached_matches_uncached():
    gp = GemmParams(family="log_our", bits=8, mode="hardware")
    q, k, v = _ops(seed=41)
    qpos, kpos, kval = _full_pos(B, SQ, SKV)
    kw = dict(q_positions=qpos, kv_positions=kpos, kv_valid=kval,
              block=BLOCK)
    a = cim_attention(q, k, v, gp, **kw)
    b = cim_attention(q, k, v, gp, cached=False, **kw)
    assert (np.asarray(a) == np.asarray(b)).all()


# ----------------------------------------------------------- autotune ----


def test_attn_autotune_sweep_persists_and_caches(tmp_path):
    # the attention kernels build only in interpret mode (the TPU
    # compiler refuses their (1, bk) position blocks), so their sweep is
    # keyed to the CPU; a caller-supplied measure still drives it
    entries = {e.name: e for e in approx_gemm.registered_kernels()}
    assert entries["pallas_attn_lut"].backends == ("cpu",)
    cache = os.path.join(tmp_path, "tune.json")
    calls = []

    def fake_measure(block):
        calls.append(block)
        bq, bk = block
        return abs(bq - 32) + abs(bk - 128) + 1.0

    autotune.clear_memory_cache()
    best = autotune.best_attn_block("pallas_attn_lut", 8, 4, 8, 4, 512,
                                    512, 64, backend="cpu",
                                    measure=fake_measure, cache_file=cache)
    assert best == (32, 128)
    assert len(calls) == len(
        autotune.candidate_attn_blocks("pallas_attn_lut", 512, 512))
    autotune.clear_memory_cache()
    calls.clear()
    again = autotune.best_attn_block("pallas_attn_lut", 8, 4, 8, 4, 512,
                                     512, 64, backend="cpu",
                                     measure=fake_measure, cache_file=cache)
    assert again == best and not calls


@pytest.mark.parametrize("garbage", ["{not json", '{"k": [1, "a", 3]}',
                                     '{"k": [1, 2]}'])
def test_attn_autotune_corrupt_cache_hardening(tmp_path, garbage):
    """Shared hardened loader: corrupt payloads are ignored and
    rewritten.  A 2-element row is only valid under an ``:attn`` key —
    under a GEMM/conv key (the `[1, 2]` case) it is malformed."""
    cache = os.path.join(tmp_path, "tune.json")
    with open(cache, "w") as fh:
        fh.write(garbage)
    autotune.clear_memory_cache()
    best = autotune.best_attn_block("pallas_attn_log", 8, 2, 4, 2, 64,
                                    64, 32, backend="cpu",
                                    measure=lambda blk: float(sum(blk)),
                                    cache_file=cache)
    assert best in autotune.candidate_attn_blocks("pallas_attn_log", 64,
                                                  64)
    with open(cache) as fh:
        disk = json.load(fh)
    assert list(disk.values()) == [list(best)]


def test_attn_autotune_row_arity_is_key_aware(tmp_path):
    cache = os.path.join(tmp_path, "tune.json")
    attn_key = autotune.attn_cache_key("pallas_attn_lut", 8, 2, 4, 2, 64,
                                       64, 32, "tpu")
    with open(cache, "w") as fh:
        json.dump({attn_key: [16, 64],          # valid attn pair
                   "pallas_gemm_lut:b8:m8k64n128:tpu": [16, 64],  # bad
                   "pallas_attn_lut:b8:attn8x4x2x64x64x16:tpu":
                       [16, 64, 128]},          # bad: attn rows are pairs
                  fh)
    loaded = autotune._load_disk(cache)
    assert loaded == {attn_key: (16, 64)}


def test_attn_bucket_keeps_heads_and_head_dim_exact():
    assert autotune.bucket_attn(3, 8, 4, 33, 47, 64) \
        == (8, 8, 4, 64, 64, 64)
    k1 = autotune.attn_cache_key("pallas_attn_lut", 8, 3, 8, 4, 33, 47,
                                 64, "cpu")
    k2 = autotune.attn_cache_key("pallas_attn_lut", 8, 4, 8, 4, 40, 50,
                                 64, "cpu")
    assert k1 == k2                    # same bucket, one plan
    k3 = autotune.attn_cache_key("pallas_attn_lut", 8, 3, 8, 4, 33, 47,
                                 128, "cpu")
    assert k1 != k3                    # head_dim changes the lane padding


def test_attn_autotune_off_tpu_never_writes_disk(tmp_path, monkeypatch):
    cache = os.path.join(tmp_path, "never.json")
    monkeypatch.setenv("OPENACM_AUTOTUNE_CACHE", cache)
    autotune.clear_memory_cache()
    blk = autotune.best_attn_block("pallas_attn_lut", 8, 2, 4, 2, 64, 64,
                                   32, backend="cpu")
    assert blk == autotune.heuristic_attn_block("pallas_attn_lut", 64, 64)
    assert not os.path.exists(cache)
