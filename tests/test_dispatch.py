"""Kernel registry / dispatcher coverage (DESIGN.md §8).

Every (family, mode) pair must route to a registered kernel whose
output matches the kernels/ref.py oracle within the family's documented
error bound: bit-for-bit for the integer paths, fp32-allclose for the
exact/surrogate deterministic terms, and moment-level for the
stochastic surrogate (covered separately in test_error_model.py).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CiMConfig, compile_macro
from repro.core import approx_gemm, autotune
from repro.core.approx_gemm import (FAMILIES, MODES, GemmParams,
                                    cim_matmul, model_matmul, plan_gemm,
                                    run_int_kernel, select_kernel,
                                    registered_kernels, trace_count)
from repro.core.multipliers import MultiplierSpec
from repro.core.quantization import dequantize, quant_scale, quantize
from repro.kernels import ref

ALL_PAIRS = [(f, m) for f in FAMILIES for m in MODES]


def _float_ops(m, k, n, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (m, k)), jax.random.normal(kw, (k, n)))


def _int_ops(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    xq = jnp.asarray(rng.integers(-127, 128, (m, k), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int8))
    return xq, wq


# ------------------------------------------------------------- routing ----


@pytest.mark.parametrize("family,mode", ALL_PAIRS)
def test_every_pair_routes_to_a_kernel(family, mode):
    entry = select_kernel(family, mode, bits=8)
    assert entry.supports(family, mode, 8, jax.default_backend())


@pytest.mark.parametrize("family,mode", ALL_PAIRS)
def test_macro_matmul_executes_every_pair(family, mode):
    macro = compile_macro(CiMConfig(family=family, bits=8, mode=mode))
    x, w = _float_ops(9, 33, 7)
    out = macro.matmul(x, w, key=jax.random.PRNGKey(3))
    assert out.shape == (9, 7) and bool(jnp.isfinite(out).all())


def test_surrogate_routes_to_fused_kernel_on_tpu_only():
    cpu = select_kernel("log_our", "surrogate", 8, backend="cpu")
    tpu = select_kernel("log_our", "surrogate", 8, backend="tpu")
    assert cpu.name == "xla_surrogate"
    assert tpu.name == "pallas_fused_surrogate"


def test_hardware_mode_prefers_arithmetic_kernel_for_log_families():
    assert select_kernel("mitchell", "hardware", 8).name == "pallas_log"
    assert select_kernel("log_our", "hardware", 8).name == "pallas_log"
    assert select_kernel("appro42", "hardware", 8,
                         backend="cpu").name == "pallas_lut_gather"
    # without a spec, predicate-gated entries (nibble) are not eligible
    assert select_kernel("exact", "hardware", 8,
                         backend="cpu").name == "pallas_lut_gather"
    # the TPU compiler refuses the gather kernels: on a TPU the LUT
    # families' hardware requests raise at routing, with the inventory
    assert select_kernel("mitchell", "hardware", 8,
                         backend="tpu").name == "pallas_log"
    for family in ("exact", "appro42"):
        spec = MultiplierSpec(family, 8, True)
        with pytest.raises(approx_gemm.RoutingError, match="registered"):
            select_kernel(family, "hardware", 8, backend="tpu", spec=spec)


def test_nibble_routing_requires_decomposable_spec():
    """The nibble kernel outranks the full-LUT gather exactly when the
    family's table factorizes bit-exactly into half-word sub-LUTs."""
    exact = MultiplierSpec("exact", 8, True)
    assert select_kernel("exact", "hardware", 8, spec=exact).name \
        == "pallas_lut_nibble"
    # appro42 default approximates columns 0..7: cross sub-products
    # differ from the full tree -> fall back to the k-sliced gather
    a8 = MultiplierSpec("appro42", 8, True)
    assert select_kernel("appro42", "hardware", 8, spec=a8).name \
        == "pallas_lut_gather"
    # approximated columns confined to the low half-word -> decomposable
    a4 = MultiplierSpec("appro42", 8, True, n_approx_cols=4)
    assert select_kernel("appro42", "hardware", 8, spec=a4).name \
        == "pallas_lut_nibble"
    # odd widths never decompose (half-words must be equal width)
    from repro.core.luts import nibble_decomposable

    assert not nibble_decomposable(MultiplierSpec("exact", 9, True))


def test_gemm_params_route_through_nibble_predicate():
    gp = GemmParams(family="exact", bits=8, mode="hardware")
    plan = plan_gemm("exact", "hardware", 8, 16, 16, 16, spec=gp.spec)
    assert plan.entry.name == "pallas_lut_nibble"
    gp8 = GemmParams(family="appro42", bits=8, mode="hardware")
    plan8 = plan_gemm("appro42", "hardware", 8, 16, 16, 16, spec=gp8.spec)
    assert plan8.entry.name == "pallas_lut_gather"


def test_unroutable_request_raises_with_inventory():
    with pytest.raises(ValueError, match="no kernel"):
        # no hardware kernel covers a 20-bit compressor-tree family
        select_kernel("appro42", "hardware", bits=20)
    with pytest.raises(ValueError, match="not in"):
        select_kernel("exact", "warp_drive")


def test_registry_entries_document_oracles():
    for e in registered_kernels():
        assert e.oracle, f"kernel {e.name} lacks an oracle reference"
        assert e.bound in ("bit", "fp32", "stochastic")


# ------------------------------------------------- oracle equivalence ----


@pytest.mark.parametrize("family", ["exact", "appro42"])
def test_hardware_lut_kernel_bit_matches_oracle(family):
    xq, wq = _int_ops(17, 40, 9, seed=1)
    gp = GemmParams(family=family, bits=8, mode="hardware")
    plan = plan_gemm(family, "hardware", 8, 17, 40, 9)
    got = run_int_kernel(plan, xq, wq, gp)
    from repro.core.luts import signed_product_lut

    lut = jnp.asarray(signed_product_lut(gp.spec).ravel())
    want = ref.lut_matmul_ref(xq, wq, lut)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("family,nac", [("exact", None), ("appro42", 4)])
def test_nibble_kernel_bit_matches_oracle(family, nac):
    """The nibble-decomposed kernel is bit-identical to the full-LUT
    oracle for every spec it routes (ragged shape exercises padding)."""
    xq, wq = _int_ops(17, 40, 9, seed=3)
    gp = GemmParams(family=family, bits=8, mode="hardware",
                    n_approx_cols=nac)
    plan = plan_gemm(family, "hardware", 8, 17, 40, 9, spec=gp.spec)
    assert plan.entry.name == "pallas_lut_nibble"
    got = run_int_kernel(plan, xq, wq, gp)
    from repro.core.luts import signed_product_lut

    lut = jnp.asarray(signed_product_lut(gp.spec).ravel())
    want = ref.lut_matmul_ref(xq, wq, lut)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("family", ["mitchell", "log_our"])
def test_hardware_log_kernel_bit_matches_oracle(family):
    xq, wq = _int_ops(17, 40, 9, seed=2)
    gp = GemmParams(family=family, bits=8, mode="hardware")
    plan = plan_gemm(family, "hardware", 8, 17, 40, 9)
    got = run_int_kernel(plan, xq, wq, gp)
    want = ref.mitchell_matmul_ref(xq, wq,
                                   compensated=(family == "log_our"))
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("family", FAMILIES)
def test_hardware_mode_equals_bit_exact_mode(family):
    """The Pallas kernels and the jnp LUT oracle implement the same
    integer semantics (quantization clips to [-127, 127], so the
    sign-magnitude -128 edge case never arises)."""
    macro = compile_macro(CiMConfig(family=family, bits=8))
    x, w = _float_ops(13, 29, 11, seed=4)
    be = macro.matmul(x, w, mode="bit_exact")
    hw = macro.matmul(x, w, mode="hardware")
    np.testing.assert_allclose(np.asarray(be), np.asarray(hw),
                               rtol=1e-6, atol=1e-6)


def test_exact_mode_is_quantize_dequantize_dot():
    macro = compile_macro(CiMConfig(family="exact", bits=8, mode="exact"))
    x, w = _float_ops(8, 32, 4, seed=5)
    got = macro.matmul(x, w)
    sx = quant_scale(x, 8)
    sw = quant_scale(w, 8, axis=0)
    want = dequantize(quantize(x, sx, 8), sx) @ dequantize(
        quantize(w, sw, 8), sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_surrogate_without_key_is_deterministic_bias_term():
    macro = compile_macro(CiMConfig(family="log_our", bits=8))
    x, w = _float_ops(8, 64, 8, seed=6)
    a = macro.matmul(x, w)                 # key=None: no noise drawn
    b = macro.matmul(x, w)
    assert (np.asarray(a) == np.asarray(b)).all()
    gp = macro.gemm_params()
    exact = macro.matmul(x, w, mode="exact")
    np.testing.assert_allclose(np.asarray(a),
                               (1.0 + gp.mu) * np.asarray(exact),
                               rtol=3e-5, atol=3e-5)


def test_model_path_hardware_matches_macro_path():
    """cim_linear (model frontend) and CiMMacro.matmul (macro frontend)
    execute the same routed kernel for hardware mode."""
    from repro.models.common import CiMContext, CiMParams, Param, cim_linear

    macro = compile_macro(CiMConfig(family="appro42", bits=8,
                                    mode="hardware"))
    x, w = _float_ops(12, 24, 8, seed=7)
    p = CiMParams.from_config(macro.config)
    got = cim_linear(x, Param(w, None), CiMContext(p))
    want = macro.matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_model_path_hardware_grads_with_3d_activations():
    """Model-zoo activations are (batch, seq, K); the STE backward must
    see a flattened x (regression: xf.T @ g crashed for rank-3 x)."""
    from repro.models.common import CiMContext, CiMParams, Param, cim_linear

    p = CiMParams(mode="hardware", family="appro42", bits=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 4))

    def loss(xv, wv):
        return jnp.sum(cim_linear(xv, Param(wv, None), CiMContext(p)) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    assert bool(jnp.isfinite(gx).all()) and bool(jnp.isfinite(gw).all())


def test_model_path_hardware_has_ste_gradients():
    from repro.models.common import CiMContext, CiMParams, Param, cim_linear

    p = CiMParams(mode="hardware", family="log_our", bits=8)
    x, w = _float_ops(6, 16, 4, seed=8)

    def loss(xv, wv):
        return jnp.sum(cim_linear(xv, Param(wv, None), CiMContext(p)) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert bool(jnp.isfinite(gx).all()) and bool(jnp.isfinite(gw).all())
    assert float(jnp.abs(gx).max()) > 0 and float(jnp.abs(gw).max()) > 0


def test_lut_cache_first_touched_under_trace_does_not_leak():
    """Regression: _signed_lut_flat must cache numpy, not a jnp array —
    a jnp constant created during tracing is a tracer, and caching it
    leaked it out of the trace (UnexpectedTracerError when a scanned
    model layer was the first hardware-mode caller)."""
    from repro.core import approx_gemm

    approx_gemm._signed_lut_flat.cache_clear()
    gp = GemmParams(family="appro42", bits=8, mode="hardware")

    @jax.jit
    def first_touch_inside_trace(x, w):
        return cim_matmul(x, w, gp)

    x, w = _float_ops(4, 16, 4, seed=9)
    inside = first_touch_inside_trace(x, w)
    outside = cim_matmul(x, w, gp)       # reuses the cached table
    np.testing.assert_allclose(np.asarray(inside), np.asarray(outside),
                               rtol=1e-6, atol=1e-6)


# -------------------------------------------------- executable cache ----


def test_executable_cache_no_retrace_on_reuse():
    """Same GemmParams + shape + dtype reuses a cached executable: the
    trace probe must stay flat over repeated eager calls."""
    gp = GemmParams(family="appro42", bits=8, mode="hardware", mu=0.001)
    x, w = _float_ops(24, 32, 16, seed=11)
    cim_matmul(x, w, gp)                       # build + compile
    t0 = trace_count()
    for _ in range(4):
        cim_matmul(x, w, gp)
    assert trace_count() == t0, "cached eager calls retraced"
    # model frontend shares the cache machinery
    model_matmul(x, w, gp)
    t0 = trace_count()
    for _ in range(4):
        model_matmul(x, w, gp)
    assert trace_count() == t0


def test_executable_cache_semantics_match_uncached():
    gp = GemmParams(family="log_our", bits=8, mode="surrogate",
                    mu=-0.01, c0=120.0, c1=2e-4)
    x, w = _float_ops(12, 40, 8, seed=12)
    key = jax.random.PRNGKey(5)
    a = cim_matmul(x, w, gp, key)
    b = cim_matmul(x, w, gp, key, cached=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    am = model_matmul(x, w, gp, key)
    bm = model_matmul(x, w, gp, key, cached=False)
    np.testing.assert_allclose(np.asarray(am), np.asarray(bm),
                               rtol=1e-5, atol=1e-5)


def test_executable_cache_misses_on_bucket_dtype_params():
    """Different shape-bucket / dtype / GemmParams miss correctly (new
    entries); same-bucket different shapes share one executable."""
    gp = GemmParams(family="exact", bits=8, mode="exact")
    x, w = _float_ops(16, 32, 16, seed=13)
    cim_matmul(x, w, gp)
    n0 = approx_gemm.executable_cache_size()
    # same bucket (m=16 vs m=12 both bucket to 16): no new entry
    cim_matmul(x[:12], w, gp)
    assert approx_gemm.executable_cache_size() == n0
    # new shape bucket
    x2, w2 = _float_ops(200, 32, 16, seed=13)
    cim_matmul(x2, w2, gp)
    assert approx_gemm.executable_cache_size() == n0 + 1
    # new dtype
    cim_matmul(x.astype(jnp.bfloat16), w, gp)
    assert approx_gemm.executable_cache_size() == n0 + 2
    # new params
    cim_matmul(x, w, GemmParams(family="exact", bits=8, mode="exact",
                                mu=0.5))
    assert approx_gemm.executable_cache_size() == n0 + 3


def test_executable_cache_key_distinguishes_backend():
    """Backend is part of the executable key (a TPU plan must never be
    served to a CPU call)."""
    gp = GemmParams(family="log_our", bits=8, mode="surrogate")
    plan_cpu = plan_gemm("log_our", "surrogate", 8, 16, 16, 16,
                         backend="cpu", spec=gp.spec)
    plan_tpu = plan_gemm("log_our", "surrogate", 8, 16, 16, 16,
                         backend="tpu", spec=gp.spec)
    x, w = _float_ops(16, 16, 16, seed=14)
    k_cpu = approx_gemm._exec_key("cim", gp, plan_cpu, False, "normal",
                                  True, x, w, 16, 16, 16)
    k_tpu = approx_gemm._exec_key("cim", gp, plan_tpu, False, "normal",
                                  True, x, w, 16, 16, 16)
    assert k_cpu != k_tpu


def test_cached_path_grads_match_uncached():
    gp = GemmParams(family="appro42", bits=8, mode="hardware")
    x, w = _float_ops(8, 24, 8, seed=15)

    def loss_cached(xv, wv):
        return jnp.sum(cim_matmul(xv, wv, gp) ** 2)

    def loss_uncached(xv, wv):
        return jnp.sum(cim_matmul(xv, wv, gp, cached=False) ** 2)

    gc = jax.grad(loss_cached, argnums=(0, 1))(x, w)
    gu = jax.grad(loss_uncached, argnums=(0, 1))(x, w)
    for a, b in zip(gc, gu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- autotune ----


def test_autotune_sweep_persists_and_caches(tmp_path):
    cache = os.path.join(tmp_path, "tune.json")
    calls = []

    def fake_measure(block):
        calls.append(block)
        bm, bk, bn = block
        return abs(bm - 128) + abs(bk - 256) + abs(bn - 128) + 1.0

    autotune.clear_memory_cache()
    best = autotune.best_block("pallas_fused_surrogate", 8, 512, 512, 512,
                               backend="tpu", measure=fake_measure,
                               cache_file=cache)
    assert best == (128, 256, 128)
    assert len(calls) == len(
        autotune.candidate_blocks("pallas_fused_surrogate", 512, 512, 512))
    assert os.path.exists(cache)

    # second resolve: served from disk, measure never invoked
    autotune.clear_memory_cache()
    calls.clear()
    again = autotune.best_block("pallas_fused_surrogate", 8, 512, 512, 512,
                                backend="tpu", measure=fake_measure,
                                cache_file=cache)
    assert again == best and not calls


def test_autotune_off_tpu_returns_clipped_heuristic(tmp_path):
    autotune.clear_memory_cache()
    blk = autotune.best_block("pallas_log", 8, 16, 16, 16, backend="cpu",
                              cache_file=os.path.join(tmp_path, "t.json"))
    bm, bk, bn = blk
    assert bm <= 16 and bk <= 16 and bn <= 16
    assert min(blk) >= 8
    # off-TPU heuristics must not pollute the disk cache
    assert not os.path.exists(os.path.join(tmp_path, "t.json"))


def test_autotune_rejecting_measure_falls_back(tmp_path, caplog):
    """A refused candidate is logged and the sweep goes on without it;
    when the compiler refuses every candidate the resolve raises instead
    of handing out a block that was never shown to build."""
    tried = []

    def oom_unless_small(block):
        tried.append(block)
        if block != (8, 64, 64):
            raise RuntimeError("RESOURCE_EXHAUSTED: VMEM")
        return 1.0

    autotune.clear_memory_cache()
    with caplog.at_level("WARNING", logger="repro.core.autotune"):
        blk = autotune.best_block(
            "pallas_log", 8, 64, 64, 64, backend="tpu",
            measure=oom_unless_small,
            cache_file=os.path.join(tmp_path, "t.json"))
    assert blk == (8, 64, 64) and len(tried) > 1
    assert "RESOURCE_EXHAUSTED" in caplog.text

    def oom(block):
        raise RuntimeError("RESOURCE_EXHAUSTED: VMEM")

    autotune.clear_memory_cache()          # (and a cold disk cache)
    with pytest.raises(RuntimeError, match="every candidate block"):
        autotune.best_block("pallas_log", 8, 64, 64, 64, backend="tpu",
                            measure=oom,
                            cache_file=os.path.join(tmp_path, "t2.json"))


@pytest.mark.parametrize("kernel", ["pallas_log", "pallas_fused_surrogate"])
def test_autotune_tpu_measure_runs_concretely_inside_a_trace(monkeypatch,
                                                             kernel):
    """Plans resolve while the jitted prefill/decode is traced (inside
    the layer scan); the TPU sweep's measure must still compile and run
    each candidate on concrete arrays instead of staging it into the
    enclosing trace.  The CPU cannot run the kernels compiled, so the
    test swaps in their interpret-mode form."""
    from repro.kernels import cim_gemm, ops

    for mod, name in ((ops, "log_matmul_fused"),
                      (cim_gemm, "cim_gemm_fused")):
        real = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda *a, _f=real, **kw: _f(
            *a, **dict(kw, interpret=True)))
    measure = autotune._default_measure(kernel, 8, 8, 128, 128)
    times = []

    def step(c, _):
        times.append(measure((8, 128, 128)))
        return c + 1.0, None

    out = jax.jit(lambda c: jax.lax.scan(step, c, None, length=1)[0])(0.0)
    assert float(out) == 1.0
    assert len(times) == 1 and isinstance(times[0], float) and times[0] > 0


@pytest.mark.parametrize("garbage", [
    "{not json",                                  # truncated / corrupt
    "[1, 2, 3]",                                  # wrong top-level type
    '{"k": 5}',                                   # wrong row type
    '{"k": [1, 2]}',                              # wrong row arity
    '{"k": ["a", "b", "c"]}',                     # wrong element type
])
def test_autotune_corrupt_cache_is_ignored_and_rewritten(tmp_path, garbage):
    cache = os.path.join(tmp_path, "tune.json")
    with open(cache, "w") as fh:
        fh.write(garbage)

    autotune.clear_memory_cache()
    best = autotune.best_block("pallas_log", 8, 64, 64, 64, backend="tpu",
                               measure=lambda b: float(sum(b)),
                               cache_file=cache)
    assert best in autotune.candidate_blocks("pallas_log", 64, 64, 64)
    # the sweep rewrote the file as valid JSON holding the winner
    with open(cache) as fh:
        disk = json.load(fh)
    assert list(disk.values()) == [list(best)]


def test_autotune_env_override_respected(tmp_path, monkeypatch):
    cache = os.path.join(tmp_path, "envtune.json")
    monkeypatch.setenv("OPENACM_AUTOTUNE_CACHE", cache)
    assert autotune.cache_path() == cache
    autotune.clear_memory_cache()
    autotune.best_block("pallas_log", 8, 64, 64, 64, backend="tpu",
                        measure=lambda b: float(sum(b)))
    assert os.path.exists(cache)
    # and the override is where a second resolve reads from
    autotune.clear_memory_cache()
    calls = []
    autotune.best_block("pallas_log", 8, 64, 64, 64, backend="tpu",
                        measure=lambda b: calls.append(b) or 1.0)
    assert not calls, "disk row under OPENACM_AUTOTUNE_CACHE was ignored"


def test_autotune_off_tpu_heuristic_never_writes_disk(tmp_path, monkeypatch):
    cache = os.path.join(tmp_path, "never.json")
    monkeypatch.setenv("OPENACM_AUTOTUNE_CACHE", cache)
    autotune.clear_memory_cache()
    for kernel in ("pallas_lut_gather", "pallas_lut_nibble", "pallas_log"):
        autotune.best_block(kernel, 8, 128, 128, 128, backend="cpu")
    assert not os.path.exists(cache)


@pytest.mark.parametrize("family,mode,kernel", [
    ("exact", "exact", "mxu_dot"),
    ("appro42", "surrogate_fast", "xla_surrogate"),
    ("mitchell", "hardware", "pallas_log"),
])
def test_model_gemm_ops_carry_the_kernel_name(family, mode, kernel):
    """A model_gemm dispatch runs under `jax.named_scope(<kernel>)`, so
    the ops it lowers to name the registry kernel in their metadata
    (what a device trace shows as the op's name stack)."""
    gp = GemmParams(family=family, bits=8, mode=mode)
    x = jnp.ones((8, 64), jnp.bfloat16)
    w = jnp.ones((64, 32), jnp.bfloat16)
    hit = approx_gemm.model_matmul(x, w, gp)         # fills the front cache
    f = jax.jit(lambda x, w: approx_gemm.model_matmul(x, w, gp))
    text = f.lower(x, w).as_text(debug_info=True)
    assert f'jit(<lambda>)/{kernel}/' in text
    np.testing.assert_array_equal(np.asarray(f(x, w)), np.asarray(hit))
