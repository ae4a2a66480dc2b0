"""Implicit-GEMM convolution coverage (DESIGN.md §9).

The conv frontend (`cim_conv2d`) must be **bit-identical** to the
materialized oracle — `_im2col + cim_linear` / `im2col + cim_matmul` —
on the integer (hardware) paths, fp32-close on the exact/surrogate
paths, route through the conv registry universe, and execute through
the zero-retrace executable cache like every other frontend.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import approx_gemm, autotune
from repro.core.approx_gemm import (ConvParams, GemmParams, cim_conv2d,
                                    cim_matmul, conv_out_hw, im2col_nhwc,
                                    plan_conv, select_conv_kernel,
                                    trace_count)
from repro.core.multipliers import MultiplierSpec

# (family, n_approx_cols, expected hardware kernel): every conv kernel
# family, incl. both LUT layouts via the nibble predicate
HW_CASES = [
    ("exact", None, "pallas_conv_nibble"),
    ("appro42", None, "pallas_conv_lut"),
    ("appro42", 4, "pallas_conv_nibble"),
    ("mitchell", None, "pallas_conv_log"),
    ("log_our", None, "pallas_conv_log"),
]

# randomized-ish shape sweep: ragged B/H/W/C/N, every tap count the CNN
# zoo uses, plus stride 2 (bit-exactness needs stride <= min(kh, kw))
SHAPES = [
    # (b, h, w, c, n, kh, kw, stride)
    (2, 9, 10, 5, 7, 3, 3, 1),
    (1, 7, 7, 3, 4, 5, 5, 1),
    (3, 8, 6, 4, 5, 1, 1, 1),
    (2, 10, 9, 3, 6, 3, 3, 2),
]


def _ops(b, h, w, c, n, kh, kw, seed=0):
    kx, kw_ = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (b, h, w, c))
    wt = jax.random.normal(kw_, (kh * kw * c, n))
    return x, wt


def _oracle(x, wt, gp, cp: ConvParams, key=None):
    cols = im2col_nhwc(x, cp)
    out = cim_matmul(cols.reshape(-1, cols.shape[-1]), wt, gp, key)
    return out.reshape(cols.shape[:3] + (wt.shape[-1],))


# ------------------------------------------------------------- routing ----


def test_conv_routing_per_family():
    for family, nac, kernel in HW_CASES:
        spec = MultiplierSpec(family, 8, True, n_approx_cols=nac)
        assert select_conv_kernel(family, "hardware", 8, spec=spec).name \
            == kernel
    assert select_conv_kernel("exact", "exact", 8).name == "pallas_conv_mxu"
    # spec-less routing stays conservative (predicate entries skipped)
    assert select_conv_kernel("exact", "hardware", 8).name \
        == "pallas_conv_lut"
    # no implicit kernel covers the surrogates: materialized fallback
    assert select_conv_kernel("log_our", "surrogate", 8).name \
        == "conv_im2col"
    assert select_conv_kernel("appro42", "bit_exact", 8).name \
        == "conv_im2col"


def test_conv_plan_falls_back_when_plane_exceeds_vmem():
    """A 224x224 plane cannot sit in VMEM: the plan must degrade to the
    materialized im2col path instead of routing an OOM kernel."""
    spec = MultiplierSpec("exact", 8, True)
    small = plan_conv("exact", "hardware", 8, 4, 16, 16, 16, 16,
                      ConvParams(3, 3, 1), spec=spec)
    big = plan_conv("exact", "hardware", 8, 4, 224, 224, 64, 64,
                    ConvParams(3, 3, 1), spec=spec)
    assert small.entry.name == "pallas_conv_nibble"
    assert big.entry.name == "conv_im2col"


def test_conv_plan_enforces_bit_bound_stride_limit():
    """Geometries where some input pixel reaches no patch (stride >
    min(kh, kw), or a sampling residue beyond the padding) can make
    quant_scale(x) differ from the oracle's quant_scale(im2col(x)):
    routing must honor the declared bit bound by materializing."""
    spec = MultiplierSpec("exact", 8, True)
    ok = plan_conv("exact", "hardware", 8, 2, 13, 13, 4, 4,
                   ConvParams(3, 3, 3), spec=spec)
    assert ok.entry.name == "pallas_conv_nibble"   # residue 0: covered
    over = plan_conv("exact", "hardware", 8, 2, 13, 13, 4, 4,
                     ConvParams(3, 3, 4), spec=spec)
    assert over.entry.name == "conv_im2col"
    # stride <= taps but residue (12+2-3) % 3 = 2 > kh//2: the last
    # real row/col is never sampled — the gate sees the ACTUAL dims
    # (12 and 13 share a shape bucket, so bucketing would miss this)
    res = plan_conv("exact", "hardware", 8, 2, 12, 12, 4, 4,
                    ConvParams(3, 3, 3), spec=spec)
    assert res.entry.name == "conv_im2col"
    # and the frontend result therefore stays bit-identical even there
    gp = GemmParams(family="exact", bits=8, mode="hardware")
    for (hh, ss) in ((13, 4), (12, 3)):
        x, wt = _ops(2, hh, hh, 4, 4, 3, 3, seed=70 + hh)
        got = cim_conv2d(x, wt, gp, stride=ss)
        want = _oracle(x, wt, gp, ConvParams(3, 3, ss))
        assert (np.asarray(got) == np.asarray(want)).all()


def test_conv_params_reject_even_kernels_and_bad_stride():
    with pytest.raises(ValueError, match="even conv kernels"):
        ConvParams(2, 2, 1)
    with pytest.raises(ValueError, match="stride"):
        ConvParams(3, 3, 0)
    with pytest.raises(ValueError):
        from repro.models.cnn import _im2col

        _im2col(jnp.zeros((1, 8, 8, 3)), 4, 4)
    # the low-level kernel wrappers must reject even kernels too, not
    # silently mis-pad them (the bug ConvParams exists to retire)
    from repro.kernels import ops

    with pytest.raises(ValueError, match="even conv kernels"):
        ops.conv2d_mxu_fused(jnp.zeros((1, 8, 8, 3)),
                             jnp.zeros((2 * 2 * 3, 4)), kh=2, kw=2)


# ------------------------------------------------- oracle equivalence ----


@pytest.mark.parametrize("family,nac,kernel", HW_CASES)
def test_hardware_conv_bit_matches_im2col_oracle(family, nac, kernel):
    """The implicit-GEMM kernels gather patches with index arithmetic;
    the result must equal the materialized im2col + GEMM path bit for
    bit, across ragged shapes, every tap count and stride 2."""
    gp = GemmParams(family=family, bits=8, mode="hardware",
                    n_approx_cols=nac)
    for i, (b, h, w, c, n, kh, kw, s) in enumerate(SHAPES):
        cp = ConvParams(kh, kw, s)
        plan = plan_conv(family, "hardware", 8, b, h, w, c, n, cp,
                         spec=gp.spec)
        assert plan.entry.name == kernel, (plan.entry.name, kernel)
        x, wt = _ops(b, h, w, c, n, kh, kw, seed=i)
        got = cim_conv2d(x, wt, gp, kh=kh, kw=kw, stride=s)
        want = _oracle(x, wt, gp, cp)
        assert (np.asarray(got) == np.asarray(want)).all(), \
            f"{family}/{nac} diverged at shape {(b, h, w, c, n, kh, kw, s)}"


def test_exact_mode_conv_matches_oracle_fp32():
    gp = GemmParams(family="exact", bits=8, mode="exact")
    for i, (b, h, w, c, n, kh, kw, s) in enumerate(SHAPES):
        x, wt = _ops(b, h, w, c, n, kh, kw, seed=10 + i)
        got = cim_conv2d(x, wt, gp, kh=kh, kw=kw, stride=s)
        want = _oracle(x, wt, gp, ConvParams(kh, kw, s))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", ["exact", "appro42", "mitchell"])
def test_surrogate_conv_matches_oracle_with_same_key(family):
    """Surrogate conv runs the materialized fallback; with the same key
    it must reproduce the im2col + cim_matmul result exactly (same
    noise draw, same variance law)."""
    gp = GemmParams(family=family, bits=8, mode="surrogate", mu=-0.01,
                    c0=120.0, c1=2e-4)
    key = jax.random.PRNGKey(7)
    x, wt = _ops(2, 8, 8, 4, 6, 3, 3, seed=20)
    got = cim_conv2d(x, wt, gp, key)
    want = _oracle(x, wt, gp, ConvParams(3, 3, 1), key)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_im2col_matches_float_conv():
    """The generalized (kh, kw, stride) im2col agrees with XLA's conv
    for every geometry in the sweep (incl. stride 2 and 1x1)."""
    for b, h, w, c, n, kh, kw, s in SHAPES:
        x, wt = _ops(b, h, w, c, n, kh, kw, seed=30)
        cp = ConvParams(kh, kw, s)
        cols = im2col_nhwc(x, cp)
        want = approx_gemm._float_conv(x, wt, cp)
        got = (cols.reshape(-1, kh * kw * c) @ wt).reshape(want.shape)
        assert cols.shape[1:3] == conv_out_hw(h, w, kh, kw, s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------ models/cnn.py integration ----


def test_models_conv2d_fused_matches_materialized_baseline():
    """conv2d(fused=True) and the fused=False im2col + cim_linear
    baseline are the same computation — bit-identical on hardware —
    while exact mode (the QAT configuration) stays on the materialized
    fake-quant path in BOTH forms: its gradient semantics (autodiff
    through the quantizer, quantized operands in the VJP) must not
    silently change under the default fused flag."""
    from repro.models.common import CiMContext, CiMParams, Param

    from repro.models.cnn import conv2d

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    wt = Param(jax.random.normal(jax.random.PRNGKey(1), (36, 8)), None)
    ctx = CiMContext(CiMParams(mode="hardware", family="appro42", bits=8))
    fused = conv2d(wt, x, ctx, "c", fused=True)
    base = conv2d(wt, x, ctx, "c", fused=False)
    assert (np.asarray(fused) == np.asarray(base)).all()

    ctx_ex = CiMContext(CiMParams(mode="exact", bits=8))

    def loss(form):
        def f(xv, wv):
            return jnp.sum(
                conv2d(Param(wv, None), xv, ctx_ex, "c", fused=form) ** 2)
        return jax.grad(f, argnums=(0, 1))(x, wt.value)

    for g_fused, g_base in zip(loss(True), loss(False)):
        assert (np.asarray(g_fused) == np.asarray(g_base)).all(), \
            "exact-mode QAT gradients changed under fused=True"


def test_models_conv2d_mixed_allocation_runs_exact_macro():
    """apply_to prefixes that exclude a conv must drop it to the exact
    int8 macro with cim_linear's fake-quant semantics — identical to
    the materialized path, and different from the approximate family."""
    from repro.models.common import CiMContext, CiMParams, Param

    from repro.models.cnn import conv2d

    x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, 6, 3))
    wt = Param(jax.random.normal(jax.random.PRNGKey(3), (27, 4)), None)
    ctx = CiMContext(CiMParams(mode="hardware", family="mitchell", bits=8,
                               apply_to=("mlp",)))
    got = conv2d(wt, x, ctx, "c1", fused=True)
    base = conv2d(wt, x, ctx, "c1", fused=False)
    assert (np.asarray(got) == np.asarray(base)).all()
    applied = conv2d(wt, x, CiMContext(CiMParams(
        mode="hardware", family="mitchell", bits=8)), "c1", fused=True)
    assert not (np.asarray(got) == np.asarray(applied)).all()


def test_cnn_forward_hardware_end_to_end():
    from repro.models.cnn import cnn_forward, init_cnn
    from repro.models.common import CiMContext, CiMParams

    params = init_cnn(jax.random.PRNGKey(0), n_classes=10, width=8)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    ctx = CiMContext(CiMParams(mode="hardware", family="appro42", bits=8))
    logits = cnn_forward(params, x, ctx)
    assert logits.shape == (2, 10) and bool(jnp.isfinite(logits).all())


def test_conv_grads_match_float_conv_vjp():
    """STE backward must be the exact float conv's VJP."""
    gp = GemmParams(family="exact", bits=8, mode="hardware")
    x, wt = _ops(2, 6, 6, 3, 4, 3, 3, seed=40)
    cp = ConvParams(3, 3, 1)

    g = jax.random.normal(jax.random.PRNGKey(9), (2, 6, 6, 4))
    _, vjp = jax.vjp(lambda a, b: approx_gemm._float_conv(a, b, cp), x, wt)
    want_gx, want_gw = vjp(g)

    def loss(xv, wv):
        return jnp.sum(cim_conv2d(xv, wv, gp) * g)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, wt)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(want_gx),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(want_gw),
                               rtol=1e-4, atol=1e-4)


# -------------------------------------------------- executable cache ----


def test_conv_zero_retrace_on_repeated_calls():
    gp = GemmParams(family="appro42", bits=8, mode="hardware")
    x, wt = _ops(2, 8, 8, 4, 6, 3, 3, seed=50)
    cim_conv2d(x, wt, gp)                      # build + compile
    t0 = trace_count()
    for _ in range(4):
        cim_conv2d(x, wt, gp)
    assert trace_count() == t0, "cached eager conv calls retraced"
    # same bucket, different batch: still no retrace of the *forward*
    # builder (jit respecializes the shape but reuses the executable
    # entry); a new bucket is allowed to trace
    n0 = approx_gemm.executable_cache_size()
    cim_conv2d(x[:1], wt, gp)
    assert approx_gemm.executable_cache_size() == n0


def test_conv_cached_matches_uncached():
    gp = GemmParams(family="log_our", bits=8, mode="hardware")
    x, wt = _ops(2, 7, 9, 5, 4, 3, 3, seed=60)
    a = cim_conv2d(x, wt, gp)
    b = cim_conv2d(x, wt, gp, cached=False)
    assert (np.asarray(a) == np.asarray(b)).all()


# ----------------------------------------------------------- autotune ----


def test_conv_autotune_sweep_persists_and_caches(tmp_path):
    # the implicit-GEMM conv kernels have not been built by the TPU
    # compiler, so they claim (and their sweep is keyed to) the CPU; a
    # caller-supplied measure still drives it
    entries = {e.name: e for e in approx_gemm.registered_kernels()}
    assert entries["pallas_conv_nibble"].backends == ("cpu",)
    cache = os.path.join(tmp_path, "tune.json")
    calls = []

    def fake_measure(block):
        calls.append(block)
        bb, bc, bn = block
        return abs(bb - 8) + abs(bc - 64) + abs(bn - 128) + 1.0

    autotune.clear_memory_cache()
    best = autotune.best_conv_block("pallas_conv_nibble", 8, 64, 16, 16,
                                    64, 128, backend="cpu",
                                    measure=fake_measure, cache_file=cache)
    assert best == (8, 64, 128)
    assert len(calls) == len(
        autotune.candidate_conv_blocks("pallas_conv_nibble", 64, 64, 128))
    # second resolve: disk hit, measure never invoked
    autotune.clear_memory_cache()
    calls.clear()
    again = autotune.best_conv_block("pallas_conv_nibble", 8, 64, 16, 16,
                                     64, 128, backend="cpu",
                                     measure=fake_measure, cache_file=cache)
    assert again == best and not calls


@pytest.mark.parametrize("garbage", ["{not json", '{"k": [1, "a", 3]}'])
def test_conv_autotune_corrupt_cache_hardening(tmp_path, garbage):
    """The conv resolver shares best_block's hardened loader: a corrupt
    cache file is ignored and rewritten, never fatal."""
    cache = os.path.join(tmp_path, "tune.json")
    with open(cache, "w") as fh:
        fh.write(garbage)
    autotune.clear_memory_cache()
    best = autotune.best_conv_block("pallas_conv_log", 8, 16, 16, 16, 16,
                                    32, backend="cpu",
                                    measure=lambda b: float(sum(b)),
                                    cache_file=cache)
    assert best in autotune.candidate_conv_blocks("pallas_conv_log", 16,
                                                  16, 32)
    with open(cache) as fh:
        disk = json.load(fh)
    assert list(disk.values()) == [list(best)]


def test_conv_bucket_keeps_taps_and_stride_exact():
    assert autotune.bucket_conv(3, 9, 10, 5, 3, 3, 2) \
        == (8, 16, 16, 8, 3, 3, 2)
    k1 = autotune.conv_cache_key("pallas_conv_lut", 8, 3, 9, 10, 5, 7,
                                 3, 3, 1, "cpu")
    k2 = autotune.conv_cache_key("pallas_conv_lut", 8, 4, 12, 12, 6, 7,
                                 3, 3, 1, "cpu")
    assert k1 == k2                    # same bucket, one plan
    k3 = autotune.conv_cache_key("pallas_conv_lut", 8, 3, 9, 10, 5, 7,
                                 5, 5, 1, "cpu")
    assert k1 != k3                    # taps change the index arithmetic


def test_conv_autotune_off_tpu_never_writes_disk(tmp_path, monkeypatch):
    cache = os.path.join(tmp_path, "never.json")
    monkeypatch.setenv("OPENACM_AUTOTUNE_CACHE", cache)
    autotune.clear_memory_cache()
    blk = autotune.best_conv_block("pallas_conv_lut", 8, 4, 16, 16, 3, 16,
                                   backend="cpu")
    assert blk == autotune.heuristic_conv_block("pallas_conv_lut", 4, 3, 16)
    assert not os.path.exists(cache)
