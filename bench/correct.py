"""The comparison that decides `correct`.

Once the window has closed, a sample of the requests the engine
finished is drawn from the seed: for every lane, the longest finished
request, one from each slot of the lane's pool, and more until
`sample_per_lane`.  The plain float32 reference
(bench/reference/<family>.py) runs once over each prompt followed by its
served tokens.  For every served token the number compared is its gap:
how far the reference's logit of that token lies below the reference's
best logit at that position.  The first served token comes from the
prefill, the rest from cached decode, so both are covered.  Per lane:
`gap_max` (the widest gap), `gap_mean` (the mean gap) and `gap_req`
(the largest of the sampled requests' mean gaps, so that one slot that
serves wrong tokens shows whole).  Each that the cell's limits file
(bench/limits/<cell>.json) names must stay within its limit.

The control puts the reference in the program's place at the next
precision below the one the configuration states: its seven projections
per layer at 4 bits instead of the served 8.  Its gaps are those of the
tokens the 4-bit forward puts first, at the same positions, reduced to
the same numbers and checked against the same limits
(bench/control.py reads it; the benchmark's own runs do not).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

CONTROL_BITS = 4


def sample(rec, per_lane: int, seed: int) -> Dict[str, List]:
    """Finished requests to compare, by lane: the longest, one drawn
    from each slot the lane's requests ran in, and more drawn until
    `per_lane`."""
    done = [s for s in rec.served
            if s.done and s.status == "ok" and s.stamps[-1] <= rec.t_close]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    out: Dict[str, List] = {}
    for lane in sorted(rec.cell.traffic["tiers"]["mix"]):
        mine = sorted((s for s in done if s.tier == lane),
                      key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
        if not mine:
            out[lane] = []
            continue
        picked = [mine[-1]]
        for slot in sorted({s.slot for s in mine} - {mine[-1].slot, None}):
            here = [s for s in mine if s.slot == slot]
            picked.append(here[rng.integers(len(here))])
        taken = {s.rid for s in picked}
        rest = [s for s in mine if s.rid not in taken]
        k = min(per_lane - len(picked), len(rest))
        if k > 0:
            picked += [rest[i] for i in rng.choice(len(rest), size=k,
                                                   replace=False)]
        out[lane] = sorted(picked, key=lambda s: s.rid)
    return out


class Reference:
    """The jitted reference over one padded sequence length; with
    `control`, the 4-bit control beside it."""

    def __init__(self, cell, control: bool = False):
        self.control = control
        import jax
        import jax.numpy as jnp

        ref = cell.reference()
        cfg = cell.config
        self.length = int(cell.traffic["engine"]["max_len"])
        self.rows = int(cell.traffic["output"]["max"])

        def stats(w, toks, rows, targets):
            lg = ref.forward(w, toks, cfg, rows=rows)
            best = lg.max(-1)
            at = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
            out = {"gap": best - at}
            if control:
                low = ref.forward(w, toks, cfg, quant_bits=CONTROL_BITS,
                                  rows=rows)
                first = jnp.argmax(low, -1)
                out["control_gap"] = best - jnp.take_along_axis(
                    lg, first[:, None], -1)[:, 0]
            return out

        self.fn = jax.jit(stats)

    def gaps(self, weights, prompt: np.ndarray,
             served: List[int]) -> Dict[str, np.ndarray]:
        """Gaps at the positions that produced each served token."""
        p, n = len(prompt), len(served)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        if len(seq) > self.length or n > self.rows:
            raise ValueError(f"request of {p}+{n} tokens exceeds the "
                             f"reference's {self.length}/{self.rows}")
        toks = np.zeros(self.length, np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(self.rows, np.int32)
        rows[:n] = np.arange(p - 1, p - 1 + n)
        tgt = np.zeros(self.rows, np.int32)
        tgt[:n] = served
        out = self.fn(weights, toks, rows, tgt)
        return {k: np.asarray(v, np.float64)[:n] for k, v in out.items()}


# number -> its reduction of the per-request gap arrays
NUMBERS = {"gap_max": lambda gs: float(np.max(np.concatenate(gs))),
           "gap_mean": lambda gs: float(np.mean(np.concatenate(gs))),
           "gap_req": lambda gs: float(max(np.mean(g) for g in gs))}


def readings(rec, weights, seed: int, ref: Reference) -> Dict[str, Dict]:
    """Per lane and number (`<number>.<lane>`, see the module docstring):
    the program's reading and, with a control reference, the control's."""
    cell = rec.cell
    control = ref.control
    picked = sample(rec, int(cell.traffic["sample_per_lane"]), seed)
    out: Dict[str, Dict] = {}
    for lane, reqs in picked.items():
        gaps, ctrl, short = [], [], 0
        for s in reqs:
            if len(s.tokens) != s.max_new:
                short += 1                  # a finished request cut short
                continue
            g = ref.gaps(weights, s.prompt, s.tokens)
            gaps.append(g["gap"])
            if control:
                ctrl.append(g["control_gap"])
        for num, f in NUMBERS.items():
            r = {"value": None if short or not gaps else f(gaps),
                 "requests": len(reqs),
                 "tokens": int(sum(len(g) for g in gaps))}
            if control and ctrl:
                r["control"] = f(ctrl)
            out[f"{num}.{lane}"] = r
    return out


def check(got: Dict[str, Dict], limits: Dict[str, float],
          reading: str = "value") -> Dict[str, Dict]:
    """The readings the cell's limits file names, each with its limit;
    `reading="control"` checks the control's readings instead."""
    out = {}
    for k, lim in sorted(limits.items()):
        r = got.get(k, {"requests": 0, "tokens": 0})
        out[k] = {"value": r.get(reading), "limit": lim,
                  "requests": r["requests"], "tokens": r["tokens"]}
    return out


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
