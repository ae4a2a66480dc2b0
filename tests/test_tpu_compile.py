"""The served path's Pallas kernels compile for a TPU v5e.

Each test compiles one kernel at qwen3-1.7b widths for a *described*
v5e chip (`jax.experimental.topologies`): nothing runs, but the TPU
compiler accepts or refuses the kernel exactly as it would on the chip,
and the compiled text holds the Mosaic kernel (`tpu_custom_call`).
Every block the autotuner can hand the kernel at those shapes is
compiled.  The lane's donated decode is compiled the same way at
published widths, from abstract shapes, to hold its KV pool in place.
The topology is described in a module fixture, never at import, so
test collection stays identical on every worker.
"""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import autotune
from repro.core.approx_gemm import registered_kernels
from repro.kernels.cim_gemm import cim_gemm_fused
from repro.kernels.mitchell_gemm import mitchell_matmul_fused
from repro.models.transformer import LM
from repro.serving.tiers import build_tiers

# qwen3-1.7b: decode of 8 slots through wi/wg (2048 -> 6144) and a
# prefill of 4 x 256 tokens through wo (6144 -> 2048)
SHAPES = {"decode": (8, 2048, 6144), "prefill": (1024, 6144, 2048)}
TPU_KERNELS = ("pallas_log", "pallas_fused_surrogate")


def _blocks(kernel):
    out = []
    for name, (m, k, n) in SHAPES.items():
        blocks = autotune.candidate_blocks(kernel, m, k, n)
        blocks += [autotune.heuristic_block(kernel, m, k, n)]
        out += [(name, b) for b in dict.fromkeys(blocks)]
    return out


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("shape,block", _blocks("pallas_log"))
def test_pallas_log_compiles(one_chip, shape, block):
    m, k, n = SHAPES[shape]
    f32 = jnp.float32
    txt = _compiled_text(
        lambda x, w, sx, sw: mitchell_matmul_fused(
            x, w, sx, sw, bits=8, compensated=True, block=block,
            interpret=False),
        jax.ShapeDtypeStruct((m, k), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, n), f32, sharding=one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("shape,block", _blocks("pallas_fused_surrogate"))
def test_pallas_fused_surrogate_compiles(one_chip, shape, block):
    m, k, n = SHAPES[shape]
    f32 = jnp.float32
    txt = _compiled_text(
        lambda x, w, eps: cim_gemm_fused(x, w, eps, 0.01, 0.1, 0.1, bits=8,
                                         block=block, interpret=False),
        jax.ShapeDtypeStruct((m, k), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((m, n), f32, sharding=one_chip))
    assert "tpu_custom_call" in txt


def test_every_tpu_block_obeys_the_tiling_rule():
    """Mosaic's rule: the last two dims of every BlockSpec are multiples
    of (8, 128) or cover the whole (padded) array.  The kernels tile x
    as (bm, bk) and w as (bk, bn) and pad each operand to a whole
    number of blocks, so a dim clipped to its bucketed extent covers
    the padded array."""
    tpu = {e.name for e in registered_kernels()
           if e.pallas and (not e.backends or "tpu" in e.backends)}
    assert tpu == set(TPU_KERNELS)      # the kernels compiled above
    dims = (1, 3, 8, 100, 128, 200, 1000, 1024, 2048, 6144, 151936)
    for kernel in TPU_KERNELS:
        for m, k, n in itertools.product(dims, repeat=3):
            blocks = autotune.candidate_blocks(kernel, m, k, n)
            for bm, bk, bn in blocks + [autotune.heuristic_block(
                    kernel, m, k, n)]:
                assert bm % 8 == 0 or bm >= m, (kernel, m, bm)
                assert bk % 128 == 0 or bk >= k, (kernel, k, bk)
                assert bn % 128 == 0 or bn >= n, (kernel, n, bn)


# served decode pools: stablelm-2-1.6b's 6 slots and qwen3-1.7b's 8, each
# 4096 deep, on the exact tier
DECODE_POOLS = {"stablelm-1.6b": (6, 4096), "qwen3-1.7b": (8, 4096)}


@pytest.mark.parametrize("arch", DECODE_POOLS)
def test_lane_decode_writes_kv_in_place(one_chip, arch):
    """The lane's donated decode writes its new K/V rows into the
    stacked pool in place: its scratch is under one layer's K+V, and no
    copy or fresh buffer of the pool's shape is in the compiled text (a
    pool fed through the layer scan's xs/ys took 5.45 GB of scratch at
    stablelm's widths, two fresh pools and two whole-pool copies)."""
    slots, depth = DECODE_POOLS[arch]
    tier = build_tiers(mode="surrogate_fast", families=("exact",))[0]
    lm = LM(dataclasses.replace(get_config(arch), cim=tier.cim))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    caches = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: lm.init_caches(slots, depth, per_slot=True)))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm.decode_step, donate_argnums=(1,)).lower(
        params, caches, tok, pos).compile()

    cfg = lm.cfg
    khd = cfg.n_kv_heads * cfg.head_dim_
    layer_kv = 2 * slots * depth * khd * 2          # bf16 K and V
    assert compiled.memory_analysis().temp_size_in_bytes < layer_kv
    pool = f"bf16[{cfg.n_periods},{slots},{depth},{khd}]"
    moved = [ln for ln in compiled.as_text().splitlines()
             if pool in ln and any(op in ln for op in
                                   (" copy(", " copy-start(",
                                    "AllocateBuffer"))]
    assert not moved, moved[:2]
