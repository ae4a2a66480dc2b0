"""Mean self time of one scheduler tick, from the program's own spans
(on in the traced run): each `step` span less the `admit` and
`decode_round` spans that ran inside it, over the ticks that started in
the window.  What is left is the scheduler's own host work: admission,
handing out tokens, eviction and the telemetry's hooks."""

import bisect


def read(rec):
    steps = [s for s in rec.engine_spans
             if s["name"] == "step" and rec.in_window(s["t0"])]
    if not steps:
        return None
    inner = sorted((s["t0"], s["dur"]) for s in rec.engine_spans
                   if s["name"] in ("admit", "decode_round"))
    starts = [t for t, _ in inner]
    total = 0.0
    for s in steps:
        lo = bisect.bisect_left(starts, s["t0"])
        hi = bisect.bisect_right(starts, s["t0"] + s["dur"])
        total += s["dur"] - sum(d for _, d in inner[lo:hi])
    return 1e3 * total / len(steps)
