"""Read the numbers a cell's limits are set from, on the chip.

For each seed, in one process (one set-up): new weights from the seed
under the same compiled engine, a short window at the cell's own load,
then the comparison of `bench/correct.py` with the control on.  Each
number is read as the program served it (the lower reading) and as the
4-bit control at the same positions puts its first token (the upper
reading), and both are checked against the cell's limits: the program
has to come out correct and the control not.

With `--fault <name>` (bench/faults.py) the lane's timed path is broken
underneath and the program has to come out not correct; the control is
not read.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \
        [--fault no_kv]

One JSON line per seed.  A limit lies above the largest program reading
over a dozen seeds or more and below the smallest control reading; see
PERF.md for the readings each limit was set from.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()

    from bench import correct, faults
    from bench.harness import Session, serve
    from bench.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    sess = Session(cell, seeds[0], trace=False)
    if args.fault:
        for lane in sess.engine.lanes.values():
            faults.plant(lane.backend, args.fault)
        sess.reset()
    ref = correct.Reference(cell, control=not args.fault)
    print(f"[setup] {time.perf_counter() - T_START:.1f} s", flush=True)
    for i, seed in enumerate(seeds):
        if i:
            sess.swap_weights(seed)
        rec = serve(sess, seed, args.seconds, trace=False)
        got = correct.readings(rec, sess.weights, seed, ref)
        out = {"seed": seed, "fault": args.fault, "readings": got}
        for who, reading in (("program", "value"), ("control", "control")):
            if who == "control" and args.fault:
                continue
            checks = correct.check(got, cell.limits, reading)
            out[who] = {"correct": correct.passed(checks),
                        "checks": {k: [c["value"], c["limit"]]
                                   for k, c in checks.items()}}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
