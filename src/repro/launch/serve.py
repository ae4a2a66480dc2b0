"""Serving launcher: the continuous-batching CiM engine under a
synthetic Poisson arrival workload (DESIGN.md §10).

`python -m repro.launch.serve --arch qwen3-1.7b --slots 4 --n-requests 16`

`--full` serves the architecture at its published widths (random bf16
weights drawn from `--seed`; qwen3-1.7b takes about 4 GB) instead of
the 64-wide smoke config, e.g. on one TPU v5e:

    python -m repro.launch.serve --full --max-len 512 \
        --prompt-len 64 256 --max-new 16 32 --n-requests 8

Builds the per-tier slot-pool engine (serving/engine.py) over the DSE
accuracy ladder (serving/tiers.py), pre-warms every (tier x bucket)
executable, serves the workload, and prints throughput / latency /
retrace stats.  `--static` degrades admission to lockstep batching (the
baseline bench_serve.py quantifies against).  The smoke config is the
CPU default; the same jitted prefill/decode functions are what the
dry-run lowers for the production mesh.  Compiled executables persist
in JAX's compilation cache (`launch/compile_cache.py`).
"""

from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.serving import (EngineStats, RealClock, build_engine,
                           build_tiers, poisson_workload,
                           servable_archs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=servable_archs())
    ap.add_argument("--full", action="store_true",
                    help="published widths instead of the smoke config")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool size per accuracy tier")
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(8, 16),
                    metavar=("LO", "HI"))
    ap.add_argument("--max-new", type=int, nargs=2, default=(4, 32),
                    metavar=("LO", "HI"))
    ap.add_argument("--mode", default="surrogate_fast",
                    help="execution mode of the approximate tiers")
    ap.add_argument("--static", action="store_true",
                    help="lockstep (static-batching) admission baseline")
    ap.add_argument("--mesh", type=int, default=0, metavar="MP",
                    help="serve data-parallel + MP-way tensor-parallel "
                         "over all visible devices (DESIGN.md §11; force "
                         "host devices via XLA_FLAGS to try on CPU)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decoding on the exact lane "
                         "(DESIGN.md §12): draft K tokens per round on "
                         "the cheapest approximate tier, verify all of "
                         "them in one batched exact pass — output is "
                         "token-for-token unchanged, only faster; 0=off")
    ap.add_argument("--spec-drafter", default=None, metavar="TIER",
                    help="drafter tier name for --spec-decode (default: "
                         "the cheapest-energy approximate rung)")
    ap.add_argument("--spec-rounds", type=int, default=4, metavar="R",
                    help="draft+verify rounds fused into one dispatch "
                         "(amortizes per-call overhead; admission waits "
                         "up to R-1 rounds for a free slot)")
    ap.add_argument("--fault-rate", type=float, default=0.0, metavar="P",
                    help="inject stuck-at faults into the approximate "
                         "tiers' stored tables + weight words at this "
                         "per-bit-cell rate, split evenly SA0/SA1 "
                         "(DESIGN.md §14; needs an integer --mode); "
                         "0 = as-designed")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="defect-map seed for --fault-rate")
    ap.add_argument("--sentinel", action="store_true",
                    help="arm per-approximate-lane accuracy sentinels: "
                         "shadow-score against the exact reference, trip "
                         "+ quarantine + demote on drift (DESIGN.md §14)")
    ap.add_argument("--sentinel-period", type=int, default=2, metavar="N",
                    help="shadow-score every Nth decode round")
    ap.add_argument("--max-queued", type=int, default=0, metavar="Q",
                    help="admission-queue bound (backpressure); "
                         "0 = unbounded")
    ap.add_argument("--retry-budget", type=int, default=3, metavar="R",
                    help="restarts per request across sentinel trips "
                         "before it is marked failed")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write a Prometheus text exposition of the "
                         "run's telemetry at shutdown ('-' = stdout; "
                         "DESIGN.md §15)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the "
                         "per-request lifecycle spans (queue -> prefill "
                         "-> decode, retries, lane rounds)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="serve without the telemetry spine (the "
                         "overhead baseline; disables --metrics/"
                         "--trace-out and the energy columns)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.no_telemetry and (args.metrics or args.trace_out):
        ap.error("--no-telemetry contradicts --metrics/--trace-out")

    if args.spec_decode and args.mesh:
        ap.error("--spec-decode does not compose with --mesh: the "
                 "verifier's per-token activation scales are row-local, "
                 "which the shard_map global-scale path cannot express "
                 "(DESIGN.md §12)")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(model_parallel=args.mesh)
        print(f"mesh: {dict(mesh.shape)}")

    fault = None
    if args.fault_rate > 0:
        from repro.core.faults import FAULT_MODES, FaultConfig

        if args.mode not in FAULT_MODES:
            ap.error(f"--fault-rate needs an integer --mode "
                     f"({'/'.join(FAULT_MODES)}): the surrogate modes "
                     "store no words or tables to fault")
        fault = FaultConfig(p_sa0=args.fault_rate / 2,
                            p_sa1=args.fault_rate / 2,
                            seed=args.fault_seed)

    sentinel_cfg = None
    if args.sentinel:
        from repro.serving import SentinelConfig

        sentinel_cfg = SentinelConfig(period=args.sentinel_period)

    telemetry = None
    if not args.no_telemetry:
        from repro.obs import EngineTelemetry

        telemetry = EngineTelemetry()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    tiers = build_tiers(mode=args.mode)
    pmax = max(args.prompt_len)
    pmin = min(args.prompt_len)
    pbkts = tuple(sorted({b for b in (8, 16, 32, 64, 128, 256)
                          if pmin <= b < pmax} | {pmax}))
    engine = build_engine(
        cfg, tiers=tiers, slots_per_tier=args.slots, max_len=args.max_len,
        prompt_buckets=pbkts,
        group_buckets=(1, 2, args.slots) if args.slots > 2 else (1, 2),
        continuous=not args.static, seed=args.seed, mesh=mesh,
        spec_decode=args.spec_decode or None,
        spec_drafter=args.spec_drafter, spec_rounds=args.spec_rounds,
        fault=fault, sentinel_cfg=sentinel_cfg,
        max_queued=args.max_queued or None,
        retry_budget=args.retry_budget, telemetry=telemetry)

    # ONE clock end to end (DESIGN.md §15): warmup timing, arrivals,
    # scheduler ticks, span timestamps, and throughput all share it
    clock = RealClock()
    t0 = clock.now()
    n_exec = engine.warmup()
    print(f"[{cfg.name}] warmed {n_exec} executables over "
          f"{len(tiers)} tiers in {clock.now() - t0:.1f}s")

    mix = (("exact", None, 0.3), ("balanced", None, 0.4),
           ("economy", None, 0.3))
    wl = poisson_workload(args.n_requests, args.rate, cfg.vocab,
                          prompt_len=tuple(args.prompt_len),
                          max_new=tuple(args.max_new), tier_mix=mix,
                          seed=args.seed)
    base = clock.now()
    for r in wl:
        r.arrival += base        # arrivals on the shared engine clock
    results = engine.run(wl, clock=clock)
    stats = EngineStats.from_results(results, engine.last_run_s)

    policy = "static" if args.static else "continuous"
    print(f"[{cfg.name}] {policy}: {stats.n_requests} requests, "
          f"{stats.total_tokens} tokens in {stats.duration_s:.2f}s "
          f"-> {stats.tokens_per_s:.1f} tok/s")
    print(f"  per-token latency p50 {stats.p50_ms_per_token:.1f}ms "
          f"p95 {stats.p95_ms_per_token:.1f}ms; "
          f"ttft p50 {stats.p50_ttft_ms:.1f}ms")
    if args.spec_decode:
        sb = engine.lanes["exact"].backend
        print(f"  spec-decode k={sb.draft_k} "
              f"(drafter {sb.drafter_lm.cfg.cim.family}): "
              f"{sb.n_rounds} fused rounds")
    if args.sentinel:
        for t in engine.trip_log:
            print(f"  trip [{t['lane']}] {t['reason']} after "
                  f"{t['tokens_before_trip']} tokens "
                  f"({t['in_flight_displaced']} in flight displaced)")

    # closing per-tier summary, sourced from engine.metrics()
    m = engine.metrics()
    print(f"  peak concurrency {m['peak_concurrency']}; steady-state "
          f"retraces {m['steady_retraces']}; {m['n_failed']} failed")
    hdr = (f"  {'tier':<10} {'tokens':>7} {'tok/s':>8} {'J/token':>10} "
           f"{'accept':>7} {'trips':>6} {'retries':>8}")
    print(hdr)
    for name, d in m["lanes"].items():
        tps = f"{d['tokens_per_s']:.1f}" if d["tokens_per_s"] else "-"
        ept = (f"{d['energy_per_token_j']:.3e}"
               if d["energy_per_token_j"] is not None else "-")
        acc = (f"{d['acceptance_rate']:.2f}"
               if d["acceptance_rate"] is not None else "-")
        print(f"  {name:<10} {d['tokens']:>7} {tps:>8} {ept:>10} "
              f"{acc:>7} {d['trips']:>6} {d['retries']:>8}")

    if args.metrics:
        from repro.obs import prometheus_text

        text = prometheus_text(telemetry.registry)
        if args.metrics == "-":
            print(text, end="")
        else:
            with open(args.metrics, "w") as f:
                f.write(text)
            print(f"  metrics -> {args.metrics}")
    if args.trace_out:
        from repro.obs import write_chrome_trace

        write_chrome_trace(telemetry.registry.spans.items(),
                           args.trace_out,
                           tid_names=telemetry.tid_names)
        print(f"  trace -> {args.trace_out} "
              f"({len(telemetry.registry.spans)} spans, "
              f"{telemetry.registry.spans.dropped} dropped)")
    if telemetry is not None:
        telemetry.detach()
    assert engine.steady_retraces() == 0, "serving retraced after warmup"


if __name__ == "__main__":
    main()
