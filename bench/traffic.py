"""One general traffic generator, driven by a traffic file.

A traffic file (`bench/traffic/<name>.json`) holds parameters only:
prompt and output length distributions, the tier mix, the arrival
process, the engine's pool sizes and the lengths of the lead-in and the
traced part of the window.  This module turns one into a request
schedule from the seed.

Every seed draws the *same set* of sizes, tiers and inter-arrival gaps,
in another order.  The set is stratified: requests come in blocks, and
in each block the tiers take turns (each as often as its share), each
tier's requests carry one size from each of `strata_per_tier` quantiles
of the prompt and of the output distribution, and the gaps one from each
quantile of the arrival process.  The seed only permutes inside blocks,
so every lane sees the same work in every block and a measured window
holds the same work whatever the seed.  The schedule depends on the
seed and the window length alone, never on how fast the system serves
it.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator plans it.  `due` is seconds after
    the start of traffic for an open loop, None for a closed loop (a
    client sends it when its previous request completes)."""

    idx: int
    prompt: np.ndarray
    max_new: int
    tier: str
    due: Optional[float]


def lognormal_quantiles(dist: Dict, n: int) -> np.ndarray:
    """n stratified sizes: the lognormal (median, sigma) at the
    mid-points of n equal-probability strata, clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps of a Poisson process."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def tier_cycle(mix: Dict[str, int]) -> List[str]:
    """The tiers of one stratum, each as often as its share."""
    out: List[str] = []
    for name, share in sorted(mix.items()):
        out += [name] * int(share)
    if not out:
        raise ValueError("traffic tier mix is empty")
    return out


def block_size(traffic: Dict) -> int:
    return len(tier_cycle(traffic["tiers"]["mix"])) * int(
        traffic.get("strata_per_tier", 4))


def n_requests(traffic: Dict, seconds: float) -> int:
    """Requests the schedule must hold: for an open loop, all that fall
    due before the window closes (lead-in + window) with room to spare;
    for a closed loop, more than the clients can finish in that time
    at the rate stated as their upper bound."""
    arr = traffic["arrival"]
    horizon = float(traffic["lead_in_s"]) + float(seconds)
    if arr["kind"] == "open":
        n = arr["rate_per_s"] * horizon * 1.5 + 64
    else:
        n = arr["max_requests_per_s"] * horizon * 1.5 + 2 * arr["clients"]
    b = block_size(traffic)
    return int(math.ceil(n / b) * b)


def schedule(traffic: Dict, vocab: int, seed: int,
             seconds: float) -> List[Planned]:
    """The request schedule of one run (see the module docstring)."""
    n = n_requests(traffic, seconds)
    tiers = tier_cycle(traffic["tiers"]["mix"])
    k = int(traffic.get("strata_per_tier", 4))
    b = block_size(traffic)
    plens = lognormal_quantiles(traffic["prompt"], k)
    olens = lognormal_quantiles(traffic["output"], k)
    arr = traffic["arrival"]
    gaps = (exponential_quantiles(arr["rate_per_s"], b)
            if arr["kind"] == "open" else None)
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    out: List[Planned] = []
    t = 0.0
    for start in range(0, n, b):
        turns = rng.permutation(len(tiers))
        p_ord = [rng.permutation(k) for _ in tiers]
        o_ord = [rng.permutation(k) for _ in tiers]
        g_ord = rng.permutation(b)
        for j in range(b):
            i = turns[j % len(tiers)]          # whose turn: tier stream i
            r = j // len(tiers)                # its r-th request here
            due = None
            if gaps is not None:
                t += float(gaps[g_ord[j]])
                due = t
            plen = int(plens[p_ord[i][r]])
            out.append(Planned(
                idx=start + j,
                prompt=rng.integers(0, vocab, plen, dtype=np.int64
                                    ).astype(np.int32),
                max_new=int(olens[o_ord[i][r]]),
                tier=tiers[i], due=due))
    return out
