"""Cross-tier speculative decoding (DESIGN.md §12).

Coverage layers:

  * **differential bit-identity** — the spec engine's emitted token
    sequences equal the plain per-token exact engine's, token for
    token, for every draft depth k in {1, 2, 4, 8}, over ragged
    mixed-tier Poisson workloads.  ONE pre-warmed backend serves all
    depths via `set_draft_k`, so the sweep doubles as the
    zero-retrace-across-depth-switch assertion;
  * **adversarial drafter** — a scrambled drafter tanks the acceptance
    rate but cannot change a single output token (the verifier owns
    the output; the drafter only owns throughput);
  * **the verify contract at its root** — eager `decode_multi` over
    k+1 positions is BITWISE equal to k+1 sequential `decode_step`s on
    a ragged per-slot pool (the per-token activation-scale property
    the whole scheme stands on);
  * **KV rollback** — the pure cache surgery (window zeroing + pos
    rewind, OOB drop at the pool edge), a served spec engine's pool
    cache byte-identical to the never-drafted baseline's, and the same
    surgery + scatter-insert on a forced 8-device host mesh
    (subprocess) matching the host result byte for byte;
  * **contracts** — spec_pair tier algebra, constructor errors raised
    early, warmup executable accounting.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import LM
from repro.serving import (Request, ServingEngine, SimClock,
                           build_engine, build_tiers, poisson_workload,
                           spec_pair)
from repro.serving.engine import LMLaneBackend
from repro.serving.spec import SpecDecodeBackend, _reset_pos, _rollback
from repro.serving.tiers import TierRouter

ARCH = "qwen3-1.7b"
KS = (1, 2, 4, 8)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = get_config(ARCH, smoke=True)
    return cfg, LM(cfg).init(jax.random.PRNGKey(0))


def _mixed_workload(cfg, n=8, seed=11):
    """Ragged mixed-tier traffic: approximate lanes coexist with the
    speculative exact lane (staggered arrivals, short and long gens)."""
    return poisson_workload(n, rate=500.0, vocab=cfg.vocab,
                            prompt_len=(3, 6), max_new=(2, 10),
                            tier_mix=(("exact", None, 0.6),
                                      ("balanced", None, 0.2),
                                      ("economy", None, 0.2)), seed=seed)


@pytest.fixture(scope="module")
def spec_vs_base(cfg_params):
    """A spec engine (all draft depths pre-warmed) and the per-token
    exact baseline engine it must reproduce, over shared weights."""
    cfg, params = cfg_params
    tiers = build_tiers()
    _, v_tier = spec_pair(tiers)
    base_tiers = tuple(v_tier if t.name == "exact" else t for t in tiers)
    kw = dict(slots_per_tier=2, max_len=32, prompt_buckets=(6,),
              group_buckets=(1, 2))
    base = build_engine(cfg, params, tiers=base_tiers, **kw)
    base.warmup()
    spec = build_engine(cfg, params, tiers=tiers, spec_decode=2,
                        spec_ks=KS, **kw)
    n_warm = spec.warmup()
    # the retrace probe is a GLOBAL trace counter: re-arm the baseline's
    # mark now that the spec engine's warmup compiles are behind us
    base.warmup()
    return cfg, params, base, spec, n_warm


# ---------------------------------------------------------------------------
# differential bit-identity
# ---------------------------------------------------------------------------


def test_spec_tokens_bit_identical_all_depths(spec_vs_base):
    """Every draft depth, same mixed workload: token-for-token equal to
    the exact engine; depth switches are dict lookups (0 retraces)."""
    cfg, _, base, spec, _ = spec_vs_base
    wl = _mixed_workload(cfg)
    base_res = base.run(wl, clock=SimClock())
    sb = spec.lanes["exact"].backend
    for k in KS:
        sb.set_draft_k(k)
        res = spec.run(wl, clock=SimClock())
        for r in wl:
            assert res[r.rid].tokens == base_res[r.rid].tokens, \
                (f"k={k} rid={r.rid} tier={res[r.rid].tier}: spec "
                 f"output diverged from the exact engine")
    assert spec.steady_retraces() == 0, \
        "draft-depth switches retraced after warmup"
    assert base.steady_retraces() == 0
    # the drafter is the real approximate tier: it must actually agree
    # with the verifier often (otherwise spec decode is a no-op)
    assert sb.acceptance_rate > 0.3
    assert sb.tokens_per_round > 1.0


def test_spec_warmup_covers_all_depths(spec_vs_base):
    """Warmup accounting: every (tier x bucket) executable plus one
    fused spec round per configured draft depth."""
    _, _, _, spec, n_warm = spec_vs_base
    n_tiers = len(spec.lanes)
    # per lane: (1 prompt bucket x 2 group buckets) prefills + decode;
    # the spec lane adds one fused round per draft depth
    assert n_warm == n_tiers * (1 * 2 + 1) + len(KS)
    sb = spec.lanes["exact"].backend
    assert sb.draft_ks == KS


def test_spec_eos_truncates_mid_window(spec_vs_base):
    """An EOS landing inside the accept window stops the request at
    exactly the token the exact engine stops at."""
    cfg, _, base, spec, _ = spec_vs_base
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab, (4,))
    probe = base.run([Request(rid=900, prompt=prompt, max_new=8,
                              tier="exact")], clock=SimClock())
    eos = probe[900].tokens[3]       # becomes EOS on the re-run
    spec.lanes["exact"].backend.set_draft_k(4)
    req = lambda rid: [Request(rid=rid, prompt=prompt.copy(), max_new=8,
                               tier="exact", eos_id=eos)]
    r_b = base.run(req(901), clock=SimClock())
    r_s = spec.run(req(902), clock=SimClock())
    assert r_s[902].tokens == r_b[901].tokens
    assert r_s[902].tokens[-1] == eos
    assert len(r_s[902].tokens) <= 4     # truncated, not budget-drained


def test_adversarial_drafter_cannot_change_output(spec_vs_base,
                                                  cfg_params):
    """Scrambling the drafter's logits collapses acceptance to ~0 but
    the emitted tokens stay identical: the verifier owns the output."""
    cfg, params = cfg_params
    _, _, base, _, _ = spec_vs_base
    tiers = build_tiers()
    d_tier, v_tier = spec_pair(tiers)

    class _Scrambled:
        """Drafter double: same cache writes, argmax rotated away."""

        def __init__(self, lm):
            self._lm = lm

        def decode_step(self, params, caches, tok, pos):
            lg, caches = self._lm.decode_step(params, caches, tok, pos)
            return jnp.roll(lg, 1, axis=-1), caches

    vlm = LM(dataclasses.replace(cfg, cim=v_tier.cim))
    dlm = _Scrambled(LM(dataclasses.replace(cfg, cim=d_tier.cim)))
    lane = SpecDecodeBackend(vlm, dlm, params, draft_k=4, n_slots=2,
                             max_len=32, prompt_buckets=(6,),
                             group_buckets=(1, 2))
    eng = ServingEngine({"exact": lane}, TierRouter([v_tier]))
    eng.warmup()
    wl = [r for r in _mixed_workload(cfg) if r.tier == "exact"]
    res = eng.run(wl, clock=SimClock())
    base_res = base.run(wl, clock=SimClock())
    for r in wl:
        assert res[r.rid].tokens == base_res[r.rid].tokens, \
            f"rid={r.rid}: a bad drafter changed the output"
    assert lane.acceptance_rate < 0.1, \
        "scrambled drafts should almost never be accepted"
    assert eng.steady_retraces() == 0


# ---------------------------------------------------------------------------
# the verify contract: batched multi-position == sequential (eager)
# ---------------------------------------------------------------------------


def test_decode_multi_bitwise_equals_sequential(cfg_params):
    """Per-token activation scales make each row of a (B, K) verify
    pass row-pure: eager decode_multi over K positions is BITWISE the
    same logits and cache as K sequential eager decode_steps, on a
    ragged pool.  (Under jit the two are separate XLA programs and may
    differ in float low bits — DESIGN.md §12 documents why the token
    contract survives that.)"""
    cfg, params = cfg_params
    tiers = build_tiers(families=("exact",))
    _, v_tier = spec_pair(tiers)
    lm = LM(dataclasses.replace(cfg, cim=v_tier.cim))
    lane = LMLaneBackend(lm, params, n_slots=3, max_len=16,
                         prompt_buckets=(6,), group_buckets=(3,))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, (l,)) for l in (6, 4, 2)]
    lane.admit(prompts, [0, 1, 2])
    k = 3
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (3, k + 1)), jnp.int32)
    fill = jnp.asarray(lane.slot_pos, jnp.int32)
    snap = jax.tree_util.tree_map(jnp.array, lane.caches)

    lg_m, c_m = lm.decode_multi(params, snap, toks, fill)

    c = jax.tree_util.tree_map(jnp.array, lane.caches)
    rows, pos = [], fill
    for i in range(k + 1):
        lg, c = lm.decode_step(params, c, toks[:, i:i + 1], pos)
        rows.append(lg[:, -1])
        pos = pos + 1
    lg_s = jnp.stack(rows, axis=1)

    assert np.array_equal(np.asarray(lg_m, np.float32),
                          np.asarray(lg_s, np.float32)), \
        "batched verify logits are not bitwise sequential"
    for a, b in zip(jax.tree_util.tree_leaves(c_m),
                    jax.tree_util.tree_leaves(c)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "batched verify cache writes are not bitwise sequential"


# ---------------------------------------------------------------------------
# KV rollback
# ---------------------------------------------------------------------------


def _toy_caches(rng, b=3, t=8, d=4, layers=2):
    """A cache pytree in the real layout: prefix per-layer dicts with
    (B, t, d) leaves, body dict of stacked (L, B, t, d) leaves."""
    mk = lambda *s: rng.normal(size=s).astype(np.float32)
    prefix = [{"k": mk(b, t, d), "v": mk(b, t, d),
               "pos": np.full(b, 5, np.int32)}]
    body = {"0": {"k": mk(layers, b, t, d), "v": mk(layers, b, t, d),
                  "pos": np.full((layers, b), 5, np.int32)}}
    return {"prefix": prefix, "body": body}


def test_rollback_zeroes_window_rewinds_pos():
    """_rollback zeroes exactly [new_fill, new_fill+width) per row (OOB
    entries dropped at the pool edge, other entries untouched) and
    rewinds every pos leaf — prefix and stacked body alike."""
    rng = np.random.default_rng(0)
    caches = _toy_caches(rng, b=3, t=8)
    width = 3
    new_fill = np.asarray([2, 6, 0], np.int32)   # row 1 overhangs t=8
    out = _rollback(jax.tree_util.tree_map(jnp.asarray, caches),
                    jnp.asarray(new_fill), width)

    def expect(arr, batch_axis):
        exp = np.array(arr)
        for b, f in enumerate(new_fill):
            idx = [slice(None)] * exp.ndim
            idx[batch_axis] = b
            idx[batch_axis + 1] = slice(f, min(f + width, exp.shape[
                batch_axis + 1]))
            exp[tuple(idx)] = 0
        return exp

    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(out["prefix"][0][name]),
            expect(caches["prefix"][0][name], 0))
        np.testing.assert_array_equal(
            np.asarray(out["body"]["0"][name]),
            expect(caches["body"]["0"][name], 1))
    np.testing.assert_array_equal(np.asarray(out["prefix"][0]["pos"]),
                                  new_fill)
    np.testing.assert_array_equal(
        np.asarray(out["body"]["0"]["pos"]),
        np.broadcast_to(new_fill, (2, 3)))


def test_reset_pos_touches_only_pos():
    rng = np.random.default_rng(1)
    caches = _toy_caches(rng)
    fill = jnp.asarray([1, 2, 3], jnp.int32)
    out = _reset_pos(jax.tree_util.tree_map(jnp.asarray, caches), fill)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(out["prefix"][0][name]),
                                      caches["prefix"][0][name])
        np.testing.assert_array_equal(np.asarray(out["body"]["0"][name]),
                                      caches["body"]["0"][name])
    np.testing.assert_array_equal(np.asarray(out["prefix"][0]["pos"]),
                                  [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(out["body"]["0"]["pos"]),
                                  np.broadcast_to([1, 2, 3], (2, 3)))


def test_rolled_back_cache_byte_identical_to_never_drafted(cfg_params):
    """After serving the same request, the spec lane's pool cache is
    byte-for-byte the baseline lane's: the rollback restores "entries
    >= fill are zero" exactly, and the verify pass wrote the same K/V
    the sequential decode would have."""
    cfg, params = cfg_params
    tiers = build_tiers(families=("exact", "mitchell"))
    _, v_tier = spec_pair(tiers)
    kw = dict(slots_per_tier=1, max_len=32, prompt_buckets=(6,),
              group_buckets=(1,))
    base = build_engine(cfg, params, tiers=(v_tier,), **kw)
    base.warmup()
    spec = build_engine(cfg, params, tiers=tiers, spec_decode=3, **kw)
    spec.warmup()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, (5,))
    req = lambda: [Request(rid=0, prompt=prompt.copy(), max_new=9,
                           tier="exact")]
    r_b = base.run(req(), clock=SimClock())
    r_s = spec.run(req(), clock=SimClock())
    assert r_s[0].tokens == r_b[0].tokens
    bb = base.lanes["exact"].backend
    sb = spec.lanes["exact"].backend
    np.testing.assert_array_equal(bb.slot_pos, sb.slot_pos)
    leaves_b = jax.tree_util.tree_leaves(bb.caches)
    leaves_s = jax.tree_util.tree_leaves(sb.caches)
    assert len(leaves_b) == len(leaves_s)
    for a, b in zip(leaves_b, leaves_s):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "spec pool cache != never-drafted pool cache"


def test_rollback_and_insert_on_host_mesh():
    """The cache ops spec decoding leans on — the lane's scatter-insert
    and the rollback surgery — produce byte-identical results on a
    forced 8-device host mesh (DP-sharded slot pool) and on one device."""
    from _hostmesh import run_host_mesh

    out = run_host_mesh("""
        import dataclasses, json
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models.transformer import LM
        from repro.serving import build_tiers
        from repro.serving.engine import LMLaneBackend
        from repro.serving.spec import _rollback
        from repro.launch.mesh import make_host_mesh

        cfg = get_config("qwen3-1.7b", smoke=True)
        tier = build_tiers(families=("exact",))[0]
        lm = LM(dataclasses.replace(cfg, cim=tier.cim))
        params = LM(cfg).init(jax.random.PRNGKey(0))
        mesh = make_host_mesh()           # (data=8, model=1)
        kw = dict(n_slots=8, max_len=16, prompt_buckets=(6,),
                  group_buckets=(4,))
        host = LMLaneBackend(lm, params, **kw)
        shrd = LMLaneBackend(lm, params, mesh=mesh, **kw)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab, (l,)) for l in (6, 4, 2)]
        host.admit(prompts, [0, 3, 5])
        shrd.admit(prompts, [0, 3, 5])
        insert_eq = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(host.caches),
                            jax.tree_util.tree_leaves(shrd.caches)))
        new_fill = jnp.asarray(np.maximum(host.slot_pos - 1, 0),
                               jnp.int32)
        rb_h = _rollback(host.caches, new_fill, 3)
        with jax.set_mesh(mesh):
            rb_s = _rollback(shrd.caches, new_fill, 3)
        rollback_eq = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(rb_h),
                            jax.tree_util.tree_leaves(rb_s)))
        print(json.dumps({"insert_equal": insert_eq,
                          "rollback_equal": rollback_eq}))
    """)
    assert out["insert_equal"], "mesh scatter-insert != host"
    assert out["rollback_equal"], "mesh rollback != host"


# ---------------------------------------------------------------------------
# contracts
# ---------------------------------------------------------------------------


def test_spec_pair_contracts():
    tiers = build_tiers()
    d, v = spec_pair(tiers)
    assert v.name == "exact" and v.cim.per_token
    assert v.nmed == 0.0
    approx = [t for t in tiers if t.name != "exact"]
    assert d.name == min(approx,        # cheapest-energy approximate rung
                         key=lambda t: t.energy_per_mac_j).name
    d2, _ = spec_pair(tiers, drafter="economy")
    assert d2.name == "economy"
    with pytest.raises(KeyError):
        spec_pair(tiers, drafter="no-such-tier")
    with pytest.raises(ValueError):
        spec_pair([t for t in tiers if t.name != "exact"])
    d3, v3 = spec_pair(build_tiers(families=("exact",)))
    assert d3.name == "exact" and not d3.cim.per_token   # degenerate
    assert v3.cim.per_token


def test_spec_backend_constructor_contracts(cfg_params):
    cfg, params = cfg_params
    tiers = build_tiers()
    d_tier, v_tier = spec_pair(tiers)
    ex = next(t for t in tiers if t.name == "exact")
    vlm = LM(dataclasses.replace(cfg, cim=v_tier.cim))
    dlm = LM(dataclasses.replace(cfg, cim=d_tier.cim))
    kw = dict(n_slots=1, max_len=16, prompt_buckets=(4,),
              group_buckets=(1,))
    with pytest.raises(ValueError, match="mesh"):
        SpecDecodeBackend(vlm, dlm, params, mesh=object(), **kw)
    with pytest.raises(ValueError, match="per_token"):
        SpecDecodeBackend(LM(dataclasses.replace(cfg, cim=ex.cim)),
                          dlm, params, **kw)
    with pytest.raises(ValueError, match="depth"):
        SpecDecodeBackend(vlm, dlm, params, draft_k=0, **kw)
    b = SpecDecodeBackend(vlm, dlm, params, draft_k=2, draft_ks=(1, 2),
                          **kw)
    assert b.draft_ks == (1, 2)
    with pytest.raises(ValueError, match="not pre-built"):
        b.set_draft_k(3)                 # unwarmed depth would retrace
    b.set_draft_k(1)
    assert b.draft_k == 1
