"""Decode writes the stacked KV pool in place (DESIGN.md §10).

`LM.decode_step` / `decode_multi` carry the body's dense KV pools
through the layer scan and write only the new rows; every other layer
kind keeps the scan's xs/ys.  Held here, at smoke widths on the CPU:

  * the carried step is bitwise the xs/ys step (`_run_stack` without
    `advance`, the path every cache took before): logits and every
    cache leaf, for per-slot MHA and GQA pools, a lockstep scalar
    `pos`, `decode_multi`'s append, a window (LOCAL) + RG-LRU hybrid,
    xLSTM, MLA latents and a stack that mixes carried and xs/ys layers;
  * the returned cache tree has `init_caches`' structure, shapes and
    dtypes;
  * each lane reports its carried layer count on
    `repro_serving_kv_inplace_layers`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import LM, kv_inplace_layers
from repro.obs import EngineTelemetry, prometheus_text
from repro.serving import ServingEngine
from repro.serving.engine import LMLaneBackend
from repro.serving.tiers import TierRouter, build_tiers
from test_serve_consistency import _batch_for

B, S, MAX_LEN = 2, 12, 20

# (arch, pos, path): "slot" is a per-slot (B,) pos from a ragged prefill,
# "lockstep" a scalar; "step" is decode_step, "multi" decode_multi
CASES = [("stablelm-1.6b", "slot", "step"),       # MHA
         ("qwen3-1.7b", "slot", "step"),          # GQA
         ("qwen3-1.7b", "lockstep", "step"),
         ("qwen3-1.7b", "slot", "multi"),
         ("qwen3-1.7b", "lockstep", "multi"),
         ("recurrentgemma-9b", "lockstep", "step"),     # LOCAL + RG-LRU
         ("xlstm-125m", "lockstep", "step"),
         ("deepseek-v2-lite-16b", "lockstep", "step"),  # MLA latents
         ("llama-3.2-vision-11b", "lockstep", "step")]  # ATTN + CROSS


def _scan_io(lm, params, caches, tokens, pos, append):
    """The same decode with every body cache on the scan's xs/ys."""
    b, k = tokens.shape
    off = jnp.arange(k, dtype=jnp.int32)
    positions = (pos[:, None] + off[None, :] if pos.ndim
                 else jnp.broadcast_to(pos + off, (b, k)))
    x = lm._embed_decode(params, tokens, positions)
    x, caches, _ = lm._run_stack(params, x, positions, caches, None, None,
                                 append=append)
    return lm._logits(params, x), caches


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)


@pytest.mark.parametrize("arch,pos_kind,path", CASES)
def test_carried_decode_bitwise_equals_scan_io(arch, pos_kind, path):
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = dict(_batch_for(cfg, toks), max_len=MAX_LEN)
    if pos_kind == "slot":
        lens = jnp.asarray([S, S - 5], jnp.int32)
        _, caches = lm.prefill(params, dict(batch, lengths=lens))
        pos = lens
    else:
        _, caches = lm.prefill(params, batch)
        pos = jnp.int32(S)
    k = 3 if path == "multi" else 1
    new = jnp.asarray(rng.integers(0, cfg.vocab, (B, k)), jnp.int32)
    decode = lm.decode_multi if path == "multi" else lm.decode_step

    lg, got = jax.jit(decode)(params, caches, new, pos)
    ref_lg, ref = jax.jit(lambda *a: _scan_io(lm, *a, path == "multi"))(
        params, caches, new, pos)

    assert np.array_equal(np.asarray(lg, np.float32),
                          np.asarray(ref_lg, np.float32))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(ref))
    for a, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert a.dtype == r.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(r))
    fresh = jax.eval_shape(lambda: lm.init_caches(
        B, MAX_LEN, per_slot=pos_kind == "slot"))
    assert _shapes(got) == _shapes(fresh)


@pytest.mark.parametrize("arch,layers", [
    ("stablelm-1.6b", 24), ("qwen3-1.7b", 28),
    ("llama-3.2-vision-11b", 32),     # 8 periods of 4 ATTN + 1 CROSS
    ("recurrentgemma-9b", 0), ("xlstm-125m", 0), ("whisper-medium", 0),
    ("deepseek-v2-lite-16b", 0)])     # MLA: latents keep xs/ys
def test_kv_inplace_layers_by_arch(arch, layers):
    assert kv_inplace_layers(get_config(arch)) == layers


def test_lane_reports_kv_inplace_layers():
    """A stablelm-1.6b lane at published depth reads 24 on the gauge
    (the lane is built, never run: its executables compile lazily)."""
    tier = build_tiers(families=("exact",))[0]
    lane = LMLaneBackend(LM(get_config("stablelm-1.6b")), None, n_slots=1,
                         max_len=16, prompt_buckets=(16,),
                         group_buckets=(1,))
    tel = EngineTelemetry(attach=False, energy=False)
    ServingEngine({tier.name: lane}, TierRouter([tier]), telemetry=tel)
    assert tel.kv_inplace_g.value(lane=tier.name) == 24
    assert ('repro_serving_kv_inplace_layers{lane="exact"} 24'
            in prometheus_text(tel.registry))
