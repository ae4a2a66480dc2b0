"""Benchmark driver: one entry per paper table, the roofline report and
the per-kernel harnesses (bench_kernels -> BENCH_kernels.json +
BENCH_dispatch.json; bench_conv -> BENCH_conv.json; bench_attn ->
BENCH_attn.json; bench_serve -> BENCH_serve.json; bench_faults ->
BENCH_faults.json; bench_dse -> BENCH_dse.json; bench_shard ->
BENCH_shard.json).  Prints ``name,us_per_call,derived`` CSV at the end,
and exits non-zero when any phase failed.

Flags:
  --fast      skip the slow CNN table; smaller kernel shape sweep
  --kernels   run only the kernel harness (still writes the JSONs)
  --smoke     tiny shapes, 1 repeat (CI rot check for the harness)
"""

from __future__ import annotations

import functools
import sys
import traceback


def main() -> None:
    from benchmarks import (bench_attn, bench_conv, bench_dse,
                            bench_faults, bench_kernels, bench_serve,
                            bench_shard, roofline, table2_ppa,
                            table3_psnr, table4_cnn, table5_yield)

    fast = "--fast" in sys.argv
    smoke = "--smoke" in sys.argv
    mods = [table2_ppa, table3_psnr, table4_cnn, table5_yield, roofline]
    if fast:
        mods = [table2_ppa, table3_psnr, table5_yield, roofline]
    if "--kernels" in sys.argv or smoke:
        mods = []
    kw = dict(fast=fast or "--kernels" in sys.argv, smoke=smoke)

    def path(mod, attr="OUT_PATH"):
        return getattr(mod, attr + "_SMOKE" if smoke else attr)

    # (row name on failure, call, output file announced on success)
    phases = [(m.__name__.split(".")[-1], m.run, None) for m in mods]
    for name, fn, out in (
            ("bench_kernels", bench_kernels.run, path(bench_kernels)),
            ("bench_dispatch", bench_kernels.run_dispatch,
             path(bench_kernels, "DISPATCH_PATH")),
            ("bench_conv", bench_conv.run, path(bench_conv)),
            ("bench_attn", bench_attn.run, path(bench_attn)),
            ("bench_serve", bench_serve.run, None),
            ("bench_faults", bench_faults.run, None),
            ("bench_dse", bench_dse.run, None),
            ("bench_shard", bench_shard.run, path(bench_shard))):
        phases.append((name, functools.partial(fn, **kw), out))
    if mods:
        phases.append(("cim_energy", roofline.energy_report, None))

    rows = []
    for name, fn, out in phases:
        try:
            rows.extend(fn())
        except Exception as e:  # noqa: BLE001 — reported, then exit 1
            traceback.print_exc()
            rows.append((name, 0.0, f"ERROR:{type(e).__name__}"))
            continue
        if out:
            print(f"{name} records -> {out}")
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    failed = [name for name, _, derived in rows
              if str(derived).startswith("ERROR")]
    if failed:
        sys.exit(f"benchmark phases failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
