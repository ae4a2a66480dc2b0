"""Every dense KV write goes into the carried pool (DESIGN.md §10).

`LM.prefill_into`, `decode_step` and `decode_multi` carry the body's
dense KV pools through the layer scan and write only their own rows
(`attention._put_kv`); every other layer kind keeps the scan's xs/ys.
Held here, at smoke widths on the CPU, against a plain reference,
`LM.prefill` of the longer sequence (prompt plus decoded tokens):

  * decode and verify logits, and every cache leaf, match it within
    `_tol` (bf16 rounding); rows past each fill level are exactly zero;
    for per-slot MHA and GQA pools, a lockstep scalar `pos`,
    `decode_multi`'s append, a window (LOCAL) + RG-LRU hybrid, xLSTM,
    MLA latents and a stack that mixes carried and xs/ys layers;
  * the returned cache tree has `init_caches`' structure, shapes and
    dtypes;
  * a lane's admission prefills a ragged group straight into the live
    pool: each admitted slot as its prompt prefilled alone, zero past
    the prompt, padding rows dropped, other slots untouched;
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


def _tol(ref, arch=""):
    """4 bf16 ulps (8 significant bits) at the largest |ref|: a decoded
    row is projected from a layer input that carries the earlier
    layers' rounding, which the reference's longer prompt rounds in
    another order (2 ulps cover one layer, as in test_decode_attn).
    MLA's absorbed decode also reassociates the latent attention
    (q . W_uk first), so its layers round differently: 16 ulps."""
    big = float(jnp.abs(jnp.asarray(ref, jnp.float32)).max())
    ulps = 16 if arch.startswith("deepseek") else 4
    return ulps * 2.0 ** (np.floor(np.log2(big)) - 7) if big else 0.0


def _close(got, ref, arch):
    err = float(jnp.abs(jnp.asarray(got, jnp.float32)
                        - jnp.asarray(ref, jnp.float32)).max())
    assert err <= _tol(ref, arch), (err, _tol(ref, arch))


def _zero_past_fill(tree):
    """Every positional K/V row at or past its layer's fill level is
    exactly zero (rings and cross caches are full: none is past)."""
    if isinstance(tree, (list, tuple)):
        return all(_zero_past_fill(c) for c in tree)
    if not isinstance(tree, dict):
        return True
    if "pos" not in tree:
        return all(_zero_past_fill(c) for c in tree.values())
    for name, leaf in tree.items():
        if name not in ("k", "v", "ckv", "kr"):    # (..., B, T, X) rows
            continue
        pos = np.asarray(tree["pos"])
        fill = np.broadcast_to(pos if pos.ndim == leaf.ndim - 2 else
                               pos[..., None], leaf.shape[:-2])
        dead = np.arange(leaf.shape[-2]) >= fill[..., None]
        if np.asarray(leaf, np.float32)[dead].any():
            return False
    return True


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)


@pytest.mark.parametrize("arch,pos_kind,path", CASES)
def test_decode_matches_prefill_of_the_longer_sequence(arch, pos_kind,
                                                       path):
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    k = 3 if path == "multi" else 1
    new = rng.integers(0, cfg.vocab, (B, k)).astype(np.int32)
    lens = np.array([S, S - 5] if pos_kind == "slot" else [S, S], np.int32)

    def prefill(tokens, lengths):
        batch = dict(_batch_for(cfg, jnp.asarray(tokens)), max_len=MAX_LEN)
        if pos_kind == "slot":
            batch["lengths"] = jnp.asarray(lengths)
        return lm.prefill(params, batch)

    _, caches = prefill(toks, lens)
    pos = jnp.asarray(lens) if pos_kind == "slot" else jnp.int32(S)
    decode = lm.decode_multi if path == "multi" else lm.decode_step
    lg, got = jax.jit(decode)(params, caches, jnp.asarray(new), pos)

    # the reference: each prompt with its decoded tokens, prefilled whole
    longer = np.zeros((B, S + k), np.int32)
    for b, n in enumerate(lens):
        longer[b, :n], longer[b, n:n + k] = toks[b, :n], new[b]
    for i in range(k):          # logits after the decoded token i
        ref_lg, _ = prefill(longer[:, :S + i + 1], lens + i + 1)
        _close(lg[:, i], ref_lg[:, -1], arch)
    _, ref = prefill(longer, lens + k)

    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(ref))
    for a, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        if jnp.issubdtype(a.dtype, jnp.integer):
            assert np.array_equal(np.asarray(a), np.asarray(r))
        else:
            _close(a, r, arch)
    assert _zero_past_fill(got)
    fresh = jax.eval_shape(lambda: lm.init_caches(
        B, MAX_LEN, per_slot=pos_kind == "slot"))
    assert _shapes(got) == _shapes(fresh)


def test_admission_prefills_into_the_live_pool():
    """A ragged group of two admitted into a pool whose other slots hold
    live requests, one of them the last slot (where the group bucket's
    two padding rows, aimed at the sentinel slot, would land if they
    were clamped instead of dropped), and one admitted slot held a
    longer request before."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    lane = LMLaneBackend(lm, params, n_slots=4, max_len=MAX_LEN,
                         prompt_buckets=(8, 16), group_buckets=(1, 4))
    rng = np.random.default_rng(3)

    def prompt(n):
        return rng.integers(0, cfg.vocab, n).astype(np.int32)

    lane.admit([prompt(14), prompt(6), prompt(11)], [0, 1, 3])
    lane.decode_round()
    before = jax.tree_util.tree_map(np.asarray, lane.caches)
    group = [prompt(5), prompt(9)]
    lane.admit(group, [1, 2])
    after = lane.caches

    for slot, p in zip((1, 2), group):
        lg, alone = lm.prefill(params, {"tokens": jnp.asarray(p[None]),
                                        "max_len": MAX_LEN})
        _close(lane.last_prefill_logits[slot - 1], lg[0, -1], "")
        for name in ("k", "v"):
            rows = after["body"]["0"][name][:, slot]
            _close(rows, alone["body"]["0"][name][:, 0], "")
            assert not np.asarray(rows[:, len(p):], np.float32).any()
        assert (np.asarray(after["body"]["0"]["pos"][:, slot])
                == len(p)).all()
    for slot in (0, 3):
        for name in ("k", "v", "pos"):
            assert np.array_equal(np.asarray(after["body"]["0"][name]
                                             [:, slot]),
                                  before["body"]["0"][name][:, slot])


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
