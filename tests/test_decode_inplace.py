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
    `repro_serving_kv_inplace_layers`, the layers the `decode_attn`
    kernel serves on `repro_serving_decode_attn_kernel_layers` (0 on
    the CPU), and each decode round's KV pool rows on
    `repro_serving_decode_kv_rows_total`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import LM, kv_inplace_layers
from repro.obs import EngineTelemetry, prometheus_text
from repro.serving import ServingEngine, SimClock, build_engine
from repro.serving.engine import LMLaneBackend
from repro.serving.tiers import TierRouter, build_tiers
from test_serve_consistency import _batch_for
from test_serving import _req

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


def test_cpu_lane_decode_kv_rows():
    """A CPU lane's decode attends on the XLA path: the kernel gauge
    reads 0, and every decode round adds 0 rows read by the kernel and
    slots x max_len per carried layer to the pool rows."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    tel = EngineTelemetry(attach=False, energy=False)
    eng = build_engine(cfg, tiers=build_tiers(families=("exact",)),
                       slots_per_tier=2, max_len=32, prompt_buckets=(8,),
                       group_buckets=(1, 2), telemetry=tel)
    eng.run([_req(i, tier="exact", plen=5 + i, max_new=3)
             for i in range(3)], clock=SimClock())
    lane = "exact"
    rounds = tel.decode_rounds_c.value(tier=lane)
    assert rounds > 0
    assert tel.decode_attn_kernel_g.value(lane=lane) == 0
    assert tel.decode_kv_rows_c.value(lane=lane, kind="read") == 0
    assert (tel.decode_kv_rows_c.value(lane=lane, kind="pool")
            == rounds * kv_inplace_layers(cfg) * 2 * 32)


def test_decode_kv_rows_from_fill_levels():
    """Rows read by the kernel are each slot's live rows (its fill
    level + 1, at most max_len) rounded up to whole 256-row blocks at
    stablelm's 32 x 64 heads, in every kernel layer (here all the
    carried layers, as on a TPU); the counter adds them up per round."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), n_layers=2,
                              n_periods=2)
    lane = LMLaneBackend(LM(cfg), None, n_slots=3, max_len=768,
                         prompt_buckets=(16,), group_buckets=(1,))
    assert lane.decode_attn_kernel_layers == 0          # the CPU
    lane.decode_attn_kernel_layers = lane.kv_inplace_layers
    lane.slot_pos[:] = [0, 300, 767]                    # 1, 301, 768 live
    assert lane.decode_kv_rows() == (2 * (256 + 512 + 768), 2 * 3 * 768)
    tel = EngineTelemetry(attach=False, energy=False)
    for _ in range(2):
        tel.on_decode_round("exact", [], 0.0, lane.decode_kv_rows())
    assert tel.decode_kv_rows_c.value(lane="exact", kind="read") == 6144
    assert tel.decode_kv_rows_c.value(lane="exact", kind="pool") == 9216
