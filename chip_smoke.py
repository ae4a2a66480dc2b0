"""Bring-up check of the served language model on a TPU.

Serves qwen3-1.7b at its published widths (28 layers, d_model 2048,
16 query / 8 KV heads of 128, d_ff 6144, vocab 151,936) with random
bf16 weights drawn from `--seed`, through the normal entry points:
`build_engine` -> `engine.warmup()` -> `engine.run()`.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4     # four chips: the mesh lane only

One chip runs two phases in one process:

  (a) the default ladder (`build_tiers()`): exact = `mxu_dot`, balanced
      and economy = `xla_surrogate` in `surrogate_fast`;
  (b) the `hardware` ladder's exact and log rungs, so the log tier's
      matmuls run the compiled `pallas_log` kernel.

Each phase prints the widths, requests and tokens served, steady-state
retraces (must be 0), the dispatch counters by routed kernel, warmup
(compile) seconds, tokens/s, peak device memory, and the exact lane's
prefill logits against a plain float32 forward of the same weights
(`cim=None`, float32 matmul precision) as a relative L2 error under
`REF_TOL`.

`--chips 4` serves the exact lane with a data-parallel slot pool and
tensor-parallel weights on a (data 2, model 2) mesh, checks in the
compiled decode HLO that the weights are split, and compares its logits
with the same requests served on one device under `MESH_TOL`.

The last line of stdout is `{"ok": true, "device": {...}}` only when
every check passed; otherwise the script exits non-zero without it.  It
refuses to run on anything but a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "qwen3-1.7b"
MAX_LEN = 512
SLOTS = 4

# Exact lane vs float32 reference, relative L2 over each prompt's
# last-token logits.  The exact lane quantizes every projection to int8
# (per-tensor activations, per-channel weights) and keeps bf16
# activations.  With these random weights its error doubles with each
# doubling of the width: on the CPU, 28 layers, the worst of 3 prompts
# is 0.030 / 0.039 / 0.070 / 0.139 at d_model 64 / 256 / 512 / 1024
# (bf16 alone: 0.012 / 0.011 / 0.013 / 0.022), which extrapolates to
# ~0.28 at the published 2048.  Logits of unrelated prompts are ~1.5
# apart (printed as "apart"), so a wrong lane (bad weights, masks or
# positions) lands far above the tolerance.
REF_TOL = 0.5
# Mesh lane vs one device, same requests.  The tensor-parallel psum
# reassociates float sums of bf16 activations, and the exact lane then
# rounds some activations to a neighbouring int8 level, so two correct
# lanes drift apart by a share of their own quantization error (CPU, 4
# virtual devices, 28 layers: 0.023 at d_model 64, 0.047 at 512; ~0.2
# expected at 2048).  A lane that drops or doubles a shard's partial
# sum lands near the unrelated-logits distance.
MESH_TOL = 0.5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def make_requests(rng, tiers, plen, new, vocab):
    from repro.serving import Request

    return [Request(rid=i, prompt=rng.integers(0, vocab, int(
                        rng.integers(plen[0], plen[1] + 1))).astype(np.int32),
                    max_new=int(rng.integers(new[0], new[1] + 1)), tier=t)
            for i, t in enumerate(tiers)]


def reference_logits(cfg, params, prompts):
    """Last-token logits of a float32 forward of `params` (`cim=None`):
    the embedding and final norm upcast from bf16, so activations are
    float32 throughout and each bf16 layer weight is promoted at its
    matmul, every one at float32 precision.  (Upcasting the stacked
    layer weights too would add ~8 GB to the chip for no change.)"""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import LM

    lm = LM(dataclasses.replace(cfg, cim=None))
    p32 = {k: v if k == "body" else jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), v) for k, v in params.items()}
    width = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.asarray([len(p) for p in prompts], np.int32)
    with jax.default_matmul_precision("float32"):
        fn = jax.jit(lambda p, t, l: lm.prefill(
            p, {"tokens": t, "lengths": l, "max_len": width})[0])
        out = np.asarray(fn(p32, jnp.asarray(toks), jnp.asarray(lens)),
                         np.float32)[:, 0]
    del p32
    return out


def kernel_counts(tel) -> dict:
    return {dict(k)["kernel"]: int(v)
            for k, v in sorted(tel.kernel_calls.values.items())}


def serve(cfg, params, tiers, reqs, *, prompt_buckets, group_buckets,
          mesh=None):
    """Build, warm up and run one engine; returns (engine, results,
    numbers)."""
    import jax

    from repro.obs import EngineTelemetry
    from repro.serving import EngineStats, RealClock, build_engine

    tel = EngineTelemetry(energy=False)
    engine = build_engine(cfg, params, tiers=tiers, slots_per_tier=SLOTS,
                          max_len=MAX_LEN, prompt_buckets=prompt_buckets,
                          group_buckets=group_buckets, record_logits=True,
                          telemetry=tel, mesh=mesh)
    t0 = time.perf_counter()
    n_exec = engine.warmup()
    warm_s = time.perf_counter() - t0
    results = engine.run(reqs, clock=RealClock())
    stats = EngineStats.from_results(results, engine.last_run_s)
    nums = {
        "executables": n_exec, "warmup_s": warm_s,
        "run_s": engine.last_run_s, "tokens": stats.total_tokens,
        "tokens_per_s": stats.tokens_per_s,
        "steady_retraces": engine.steady_retraces(),
        "kernel_calls": kernel_counts(tel),
        "peak_bytes_in_use":
            (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use"),
    }
    tel.detach()
    return engine, results, nums


def check_served(phase, reqs, results, nums):
    for r in reqs:
        rr = results[r.rid]
        check(rr.done and rr.status == "ok" and len(rr.tokens) == r.max_new,
              f"{phase}: request {r.rid} ({rr.tier}) ended {rr.status} "
              f"with {len(rr.tokens)}/{r.max_new} tokens")
        check(all(np.isfinite(lg).all() for lg in rr.logits),
              f"{phase}: request {r.rid} has non-finite logits")
    check(nums["steady_retraces"] == 0,
          f"{phase}: {nums['steady_retraces']} steady-state retraces")


def phase_lines(phase, reqs, results, nums):
    tiers = {}
    for r in reqs:
        tiers[results[r.rid].tier] = tiers.get(results[r.rid].tier, 0) + 1
    print(f"[{phase}] requests {len(reqs)} by tier {tiers}; prompts "
          f"{min(len(r.prompt) for r in reqs)}-"
          f"{max(len(r.prompt) for r in reqs)} tokens; "
          f"{nums['tokens']} tokens served")
    print(f"[{phase}] warmup {nums['warmup_s']:.1f} s over "
          f"{nums['executables']} executables; run {nums['run_s']:.2f} s "
          f"-> {nums['tokens_per_s']:.2f} tokens/s; steady retraces "
          f"{nums['steady_retraces']}; peak_bytes_in_use "
          f"{nums['peak_bytes_in_use']}")
    print(f"[{phase}] dispatch calls by kernel {nums['kernel_calls']}",
          flush=True)


def check_reference(phase, reqs, results, ref):
    errs = {r.rid: rel_l2(results[r.rid].logits[0], ref[r.rid])
            for r in reqs if r.rid in ref}
    check(len(errs) > 1, f"{phase}: fewer than 2 exact-lane requests")
    worst = max(errs.values())
    ids = sorted(errs)
    apart = min(rel_l2(ref[a], ref[b]) for a, b in zip(ids, ids[1:]))
    print(f"[{phase}] exact-lane prefill logits vs float32 reference: "
          f"rel L2 per request "
          f"{ {k: round(v, 4) for k, v in errs.items()} }, max "
          f"{worst:.4f} (tolerance {REF_TOL}; references of different "
          f"prompts are >= {apart:.3f} apart)", flush=True)
    check(worst <= REF_TOL < apart, f"{phase}: exact lane off the "
          f"float32 reference: {worst:.4f} (tolerance {REF_TOL}, "
          f"unrelated prompts {apart:.3f} apart)")


def one_chip(cfg, params, seed):
    import jax

    from repro.serving import build_tiers

    rng = np.random.default_rng(seed)
    reqs_a = make_requests(rng, ["exact", "balanced", "economy"] * 2
                           + ["exact", "balanced"], (64, 256), (16, 32),
                           cfg.vocab)
    reqs_b = make_requests(rng, ["exact", "economy"] * 2, (64, 128),
                           (16, 16), cfg.vocab)
    for r in reqs_b:
        r.rid += 100

    # float32 reference first: its upcast weights (~8 GB) are freed
    # before any engine allocates its slot pools
    t0 = time.perf_counter()
    exact = [r for r in reqs_a + reqs_b if r.tier == "exact"]
    ref = dict(zip([r.rid for r in exact],
                   reference_logits(cfg, params, [r.prompt for r in exact])))
    print(f"[ref] float32 prefill of {len(exact)} prompts in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    tiers = build_tiers()
    print("[a] tiers " + ", ".join(
        f"{t.name}={t.family}/{t.cim.mode}" for t in tiers), flush=True)
    engine, res, nums = serve(cfg, params, tiers, reqs_a,
                              prompt_buckets=(256,), group_buckets=(SLOTS,))
    phase_lines("a", reqs_a, res, nums)
    check_served("a", reqs_a, res, nums)
    check_reference("a", reqs_a, res, ref)
    del engine

    tiers = build_tiers(mode="hardware",
                        families=("exact", "mitchell", "log_our"))
    print("[b] tiers " + ", ".join(
        f"{t.name}={t.family}/{t.cim.mode}" for t in tiers), flush=True)
    engine, res, nums = serve(cfg, params, tiers, reqs_b,
                              prompt_buckets=(128,), group_buckets=(2,))
    phase_lines("b", reqs_b, res, nums)
    check_served("b", reqs_b, res, nums)
    check_reference("b", reqs_b, res, ref)
    check(nums["kernel_calls"].get("pallas_log", 0) > 0,
          "b: no dispatch routed to pallas_log")
    # the log lane's decode step holds the compiled Mosaic kernel (an
    # interpreted kernel would lower to plain HLO ops instead)
    lane = engine.lanes["economy"].backend
    tok = jax.numpy.zeros((lane.n_slots, 1), jax.numpy.int32)
    pos = jax.numpy.zeros((lane.n_slots,), jax.numpy.int32)
    hlo = lane._decode.lower(lane.params, lane.caches, tok, pos).as_text()
    n_tpu = hlo.count("tpu_custom_call")
    print(f"[b] economy decode HLO: {n_tpu} tpu_custom_call sites",
          flush=True)
    check(n_tpu > 0, "b: the log lane's decode holds no compiled kernel")


def four_chips(cfg, params, seed):
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serving import build_tiers

    check(len(jax.devices()) == 4, f"--chips 4 sees {len(jax.devices())} "
          "devices")
    mesh = make_mesh((2, 2), ("data", "model"))
    tiers = build_tiers(families=("exact",))
    rng = np.random.default_rng(seed)
    reqs = make_requests(rng, ["exact"] * SLOTS, (64, 256), (16, 32),
                         cfg.vocab)

    def fresh():
        return [dataclasses.replace(r) for r in reqs]

    e1, r1, n1 = serve(cfg, params, tiers, fresh(),
                       prompt_buckets=(256,), group_buckets=(SLOTS,))
    phase_lines("one-device", reqs, r1, n1)
    check_served("one-device", reqs, r1, n1)
    del e1
    em, rm, nm = serve(cfg, params, tiers, fresh(), prompt_buckets=(256,),
                       group_buckets=(SLOTS,), mesh=mesh)
    phase_lines("mesh", reqs, rm, nm)
    check_served("mesh", reqs, rm, nm)

    # weights split: the compiled decode's parameters carry the
    # per-device shard shapes of the tensor-parallel weights
    lane = em.lanes["exact"].backend
    tok = jax.device_put(jax.numpy.zeros((lane.n_slots, 1),
                                         jax.numpy.int32), lane._tok_shard)
    pos = jax.device_put(jax.numpy.zeros((lane.n_slots,), jax.numpy.int32),
                         lane._pos_shard)
    with jax.set_mesh(mesh):
        hlo = lane._decode.lower(lane.params, lane.caches, tok,
                                 pos).compile().as_text()
    mlp = lane.params["body"]["0"]["mlp"]["wi"].value
    local = mlp.sharding.shard_shape(mlp.shape)
    want = "bf16[" + ",".join(str(d) for d in local) + "]"
    print(f"[mesh] {dict(mesh.shape)}; mlp wi {tuple(mlp.shape)} -> "
          f"per-device {tuple(local)}; '{want}' in compiled decode: "
          f"{want in hlo}", flush=True)
    check(local != tuple(mlp.shape) and want in hlo,
          "mesh: the tensor-parallel weights are not split in the "
          "compiled decode")

    errs, agree, n = [], 0, 0
    for r in reqs:
        a, b = r1[r.rid], rm[r.rid]
        for i, (la, lb) in enumerate(zip(a.logits, b.logits)):
            errs.append(rel_l2(lb, la))
            if a.tokens[i] != b.tokens[i]:
                break          # later steps decode different prefixes
        agree += sum(x == y for x, y in zip(a.tokens, b.tokens))
        n += len(a.tokens)
    worst = max(errs)
    print(f"[mesh] logits vs one device over {len(errs)} steps: max rel "
          f"L2 {worst:.5f} (tolerance {MESH_TOL}); tokens agree "
          f"{agree}/{n}", flush=True)
    check(worst <= MESH_TOL, f"mesh lane off the one-device lane: "
          f"{worst:.5f} > {MESH_TOL}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.transformer import LM, count_params

    print(f"compile cache: {use_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"[{cfg.name}] layers {cfg.n_layers}, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim_}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; {count_params(cfg):,} params "
          f"(bf16, seed {args.seed}) on {len(jax.devices())} x "
          f"{dev.device_kind}", flush=True)
    t0 = time.perf_counter()
    params = jax.jit(LM(cfg).init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"[init] weights in {time.perf_counter() - t0:.1f} s", flush=True)

    if args.chips == 4:
        four_chips(cfg, params, args.seed)
    else:
        one_chip(cfg, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
