"""Mesh-partitioned dispatch (DESIGN.md §11): tensor-parallel shard_map
executables bit-identical to the single-device oracle, zero-retrace
across mesh AND tier switches, mesh-plan validation, and the serving
engine's data-parallel slot pool reproducing lockstep logits.

Device-forcing runs in subprocesses (shared _hostmesh helper: the main
test process keeps its single-device view, pre-existing XLA_FLAGS are
preserved).  Validation-error tests run in-process — they only touch
mesh *shapes*, never devices.
"""

import pytest

from _hostmesh import run_host_mesh

# ---------------------------------------------------------------------------
# TP GEMM + conv bit-identity, all three kernel families, both layouts
# ---------------------------------------------------------------------------

_TP_GEMM = """
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core import approx_gemm as ag

    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    cases = [
        ag.GemmParams(family="exact", bits=8, mode="bit_exact"),
        ag.GemmParams(family="exact", bits=8, mode="hardware"),
        ag.GemmParams(family="appro42", bits=8, mode="hardware",
                      n_approx_cols=6),
        ag.GemmParams(family="log_our", bits=8, mode="hardware"),
        ag.GemmParams(family="mitchell", bits=8, mode="hardware"),
    ]
    layouts = [("K", P("data", "model"), P("model", None)),
               ("N", P("data", None), P(None, "model"))]
    results = {}
    for gp in cases:
        base = ag.cim_matmul(x, w, gp)
        for lname, xs, ws in layouts:
            out = ag.cim_matmul(x, w, gp, mesh=mesh, x_spec=xs, w_spec=ws)
            results[f"{gp.family}/{gp.mode}/{lname}"] = bool(
                jnp.all(out == base))
    # model frontend: dtype preserved, still bit-identical
    xb = x.astype(jnp.bfloat16)
    gp = ag.GemmParams(family="exact", bits=8, mode="hardware")
    mb = ag.model_matmul(xb, w, gp)
    mm = ag.model_matmul(xb, w, gp, mesh=mesh, x_spec=P("data", "model"),
                         w_spec=P("model", None))
    results["model/bf16"] = bool(jnp.all(mm == mb))
    results["model/dtype"] = str(mm.dtype)
    # bucket-bypass regression: m=16 (warm, divides the 2-way data
    # axis) and m=15 share bucket 16 — the warm front-cache entry must
    # NOT serve the non-divisible shape; it must raise cleanly
    try:
        ag.cim_matmul(x[:15], w, gp, mesh=mesh,
                      x_spec=P("data", "model"), w_spec=P("model", None))
        results["validation/bucket_bypass_raises"] = False
    except ValueError:
        results["validation/bucket_bypass_raises"] = True
    print(json.dumps(results))
"""


def test_tp_gemm_bit_identical_to_single_device():
    res = run_host_mesh(_TP_GEMM)
    dtype = res.pop("model/dtype")
    assert dtype == "bfloat16"
    bad = [k for k, v in res.items() if not v]
    assert not bad, f"mesh GEMM diverged from oracle: {bad}"


_TP_CONV = """
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core import approx_gemm as ag

    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    x4 = jax.random.normal(key, (4, 8, 8, 16), jnp.float32)
    results = {}
    for kh, stride in [(3, 1), (3, 2), (5, 1)]:
        w2 = jax.random.normal(jax.random.PRNGKey(kh),
                               (kh * kh * 16, 8), jnp.float32)
        for gp in [ag.GemmParams(family="exact", bits=8, mode="hardware"),
                   ag.GemmParams(family="appro42", bits=8, mode="hardware",
                                 n_approx_cols=6),
                   ag.GemmParams(family="log_our", bits=8,
                                 mode="hardware"),
                   ag.GemmParams(family="exact", bits=8,
                                 mode="bit_exact")]:
            base = ag.cim_conv2d(x4, w2, gp, kh=kh, kw=kh, stride=stride)
            for lname, ws in [("C", P("model", None)),
                              ("N", P(None, "model"))]:
                out = ag.cim_conv2d(
                    x4, w2, gp, kh=kh, kw=kh, stride=stride, mesh=mesh,
                    x_spec=P("data", None, None, None), w_spec=ws)
                results[f"{gp.family}/{gp.mode}/{kh}x{kh}s{stride}/"
                        f"{lname}"] = bool(jnp.all(out == base))
    # bucket-bypass regression: 3x3 stride 3 is bit-safe at h=w=8 but
    # NOT at h=w=6, and both bucket to 8 — the warm cache entry must
    # not serve the unsafe geometry (it would silently diverge bitwise)
    gp = ag.GemmParams(family="exact", bits=8, mode="hardware")
    w2s = jax.random.normal(jax.random.PRNGKey(9), (9 * 16, 8),
                            jnp.float32)
    ag.cim_conv2d(x4, w2s, gp, kh=3, kw=3, stride=3, mesh=mesh,
                  x_spec=P("data", None, None, None),
                  w_spec=P("model", None))
    x6 = jax.random.normal(jax.random.PRNGKey(8), (4, 6, 6, 16),
                           jnp.float32)
    try:
        ag.cim_conv2d(x6, w2s, gp, kh=3, kw=3, stride=3, mesh=mesh,
                      x_spec=P("data", None, None, None),
                      w_spec=P("model", None))
        results["validation/conv_bucket_bypass_raises"] = False
    except ValueError:
        results["validation/conv_bucket_bypass_raises"] = True
    print(json.dumps(results))
"""


def test_tp_conv_bit_identical_to_single_device():
    res = run_host_mesh(_TP_CONV, timeout=560)
    bad = [k for k, v in res.items() if not v]
    assert not bad, f"mesh conv diverged from oracle: {bad}"


# ---------------------------------------------------------------------------
# Zero-retrace steady state across mesh AND tier switches
# ---------------------------------------------------------------------------

_RETRACE = """
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core import approx_gemm as ag

    mesh_a = make_mesh((2, 4), ("data", "model"))
    mesh_b = make_mesh((1, 8), ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    tiers = [ag.GemmParams(family="exact", bits=8, mode="hardware"),
             ag.GemmParams(family="log_our", bits=8, mode="hardware"),
             ag.GemmParams(family="exact", bits=8, mode="bit_exact")]

    def sweep():
        for gp in tiers:
            for mesh in (mesh_a, mesh_b, None):
                ag.cim_matmul(
                    x, w, gp, mesh=mesh,
                    x_spec=P(None, "model") if mesh is not None else None,
                    w_spec=P("model", None) if mesh is not None else None)

    sweep()                                    # warm every combination
    mark = ag.trace_count()
    for _ in range(3):
        sweep()
    print(json.dumps({"steady_retraces": ag.trace_count() - mark,
                      "cache_entries": ag.executable_cache_size()}))
"""


def test_zero_retrace_across_mesh_and_tier_switches():
    res = run_host_mesh(_RETRACE)
    assert res["steady_retraces"] == 0
    assert res["cache_entries"] >= 9           # 3 tiers x 3 mesh choices


# ---------------------------------------------------------------------------
# Mesh-plan validation (shape-only: no devices needed)
# ---------------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_mesh_plan_rejects_float_modes():
    from repro.core.approx_gemm import plan_gemm

    mesh = _FakeMesh({"data": 2, "model": 4})
    for mode in ("exact", "surrogate", "surrogate_fast"):
        with pytest.raises(ValueError, match="integer modes"):
            plan_gemm("exact", mode, 8, 16, 64, 32, mesh=mesh,
                      w_spec=("model", None))


def test_mesh_plan_rejects_double_sharded_weight():
    from jax.sharding import PartitionSpec as P

    from repro.core.approx_gemm import plan_gemm

    mesh = _FakeMesh({"data": 2, "model": 4})
    with pytest.raises(ValueError, match="both K .* and N"):
        plan_gemm("exact", "hardware", 8, 16, 64, 32, mesh=mesh,
                  w_spec=P("model", "data"))


def test_mesh_plan_rejects_non_divisible_dims():
    from jax.sharding import PartitionSpec as P

    from repro.core.approx_gemm import plan_gemm

    mesh = _FakeMesh({"data": 2, "model": 4})
    with pytest.raises(ValueError, match="not divisible"):
        plan_gemm("exact", "hardware", 8, 16, 63, 32, mesh=mesh,
                  w_spec=P("model", None))
    with pytest.raises(ValueError, match="not divisible"):
        plan_gemm("exact", "hardware", 8, 15, 64, 32, mesh=mesh,
                  x_spec=P("data", None), w_spec=P("model", None))


def test_mesh_conv_rejects_unsafe_geometry():
    from jax.sharding import PartitionSpec as P

    from repro.core.approx_gemm import ConvParams, plan_conv

    mesh = _FakeMesh({"data": 2, "model": 4})
    # stride 4 > kernel 3: unsampled pixels, per-tensor scale unsafe
    with pytest.raises(ValueError, match="bit-safe"):
        plan_conv("exact", "hardware", 8, 4, 8, 8, 16, 8,
                  ConvParams(3, 3, 4), mesh=mesh,
                  w_spec=P("model", None))


# ---------------------------------------------------------------------------
# Serving: data-parallel slot pool == lockstep engine, logit for logit
# ---------------------------------------------------------------------------

_SERVE_DP = """
    import json
    import jax
    from repro.launch.mesh import make_mesh
    import numpy as np
    from repro.configs import get_config
    from repro.core.compiler import CiMConfig
    from repro.models.transformer import LM
    from repro.serving import Request, SimClock, build_engine
    from repro.serving.tiers import AccuracyTier

    cfg = get_config("qwen3-1.7b", smoke=True)
    mesh = make_mesh((4, 2), ("data", "model"))
    # integer-mode ladder: these tiers route through the shard_map
    # dispatch path and must be BITWISE identical (float tiers under TP
    # reassociate the psum and are only allclose — DESIGN.md §11)
    tiers = [
        AccuracyTier("exact", CiMConfig(family="exact", bits=8,
                                        mode="hardware"), 0.0, 2.45e-12),
        AccuracyTier("economy", CiMConfig(family="log_our", bits=8,
                                          mode="hardware"), 5e-3,
                     2.82e-12),
    ]
    params = LM(cfg).init(jax.random.PRNGKey(0))

    def mk_reqs():
        r = np.random.default_rng(0)
        return [Request(rid=i, prompt=r.integers(0, cfg.vocab, 8),
                        max_new=3, tier=t, arrival=float(i) * 0.01)
                for i, t in enumerate(["exact", "economy", "exact",
                                       "economy", "exact"])]

    kw = dict(tiers=tiers, slots_per_tier=4, max_len=32,
              prompt_buckets=(8,), group_buckets=(1, 2, 4),
              record_logits=True)
    e1 = build_engine(cfg, params, **kw)
    e1.warmup()
    r1 = e1.run(mk_reqs(), clock=SimClock())
    rt1 = e1.steady_retraces()      # before e2 bumps the global probe
    e2 = build_engine(cfg, params, mesh=mesh, **kw)
    e2.warmup()
    r2 = e2.run(mk_reqs(), clock=SimClock())
    rt2 = e2.steady_retraces()
    tokens_ok = all(r1[i].tokens == r2[i].tokens for i in r1)
    logits_ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                    for i in r1
                    for a, b in zip(r1[i].logits, r2[i].logits))
    print(json.dumps({
        "tokens_identical": tokens_ok,
        "logits_bit_identical": logits_ok,
        "retraces_unsharded": rt1,
        "retraces_mesh": rt2,
        "n_done": sum(r.done for r in r2.values()),
    }))
"""


def test_serving_dp_pool_reproduces_lockstep():
    res = run_host_mesh(_SERVE_DP, timeout=560)
    assert res["n_done"] == 5
    assert res["tokens_identical"], res
    assert res["logits_bit_identical"], res
    assert res["retraces_unsharded"] == 0
    assert res["retraces_mesh"] == 0
