"""Roofline analysis from the dry-run artifacts (deliverable g).

Reads experiments/dryrun/*.json and derives, per (arch x shape x mesh),
with the peaks of the cell's `device_kind` (`peaks`):

  compute term    = hlo_flops / peak bf16 FLOP/s
  memory term     = hlo_bytes / peak HBM bytes/s
  collective term = collective_bytes / ICI bytes/s per link

hlo_* are per-device already (post-SPMD HLO), so the per-chip division
is folded in; the dominant term is the bottleneck, and
MODEL_FLOPS / HLO_FLOPS measures how much compiled compute is useful
(remat + masked-attention + dispatch overcompute show up here).  A
device kind without published peaks is an error, never a default."""

from __future__ import annotations

import glob
import json
import os
import time

# Published per-chip peaks keyed by jax's `device_kind` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s chip-to-chip interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9, "ici": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s, HBM and per-link ICI bytes/s of one chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") \
            from None


_BASE = os.path.join(os.path.dirname(__file__), "..", "experiments")
# prefer the post-hillclimb matrix when it exists (see EXPERIMENTS.md §Perf)
DRYRUN_DIR = (os.path.join(_BASE, "dryrun_final")
              if os.path.isdir(os.path.join(_BASE, "dryrun_final"))
              else os.path.join(_BASE, "dryrun"))


def load_cells(pattern: str = "*.json", d: str = DRYRUN_DIR):
    cells = []
    for f in sorted(glob.glob(os.path.join(d, pattern))):
        with open(f) as fh:
            r = json.load(fh)
        if r.get("skipped") or "error" in r:
            cells.append(r)
            continue
        n = r["n_devices"]
        hlo = r["hlo"]
        pk = peaks(r.get("device_kind"))
        r["t_compute"] = hlo["flops"] / pk["flops"]
        r["t_memory"] = hlo["bytes"] / pk["hbm"]
        r["t_collective"] = hlo["collective_bytes"] / pk["ici"]
        terms = {"compute": r["t_compute"], "memory": r["t_memory"],
                 "collective": r["t_collective"]}
        r["bottleneck"] = max(terms, key=terms.get)
        r["t_bound"] = max(terms.values())
        # useful-compute ratio: model flops per device vs compiled flops
        r["useful_ratio"] = (r["model_flops"] / n) / max(hlo["flops"], 1.0)
        # roofline fraction: ideal compute time / bound time
        r["roofline_frac"] = (r["model_flops"] / n / pk["flops"]) / \
            max(r["t_bound"], 1e-12)
        cells.append(r)
    return cells


def fmt_table(cells, mesh="pod"):
    lines = [f"{'arch':24s} {'shape':12s} {'comp(s)':>8} {'mem(s)':>8} "
             f"{'coll(s)':>8} {'bneck':>6} {'useful':>7} {'roofl%':>7} "
             f"{'peakGB':>7}"]
    for r in cells:
        if r.get("mesh") != mesh or r.get("skipped") or "error" in r:
            continue
        lines.append(
            f"{r['arch']:24s} {r['shape']:12s} {r['t_compute']:>8.3f} "
            f"{r['t_memory']:>8.3f} {r['t_collective']:>8.3f} "
            f"{r['bottleneck'][:6]:>6} {r['useful_ratio']:>7.2f} "
            f"{100*r['roofline_frac']:>6.1f}% "
            f"{r['memory']['peak_bytes']/1e9:>7.1f}")
    return "\n".join(lines)


def run():
    t0 = time.perf_counter()
    cells = load_cells()
    done = [c for c in cells if not c.get("skipped") and "error" not in c]
    skipped = [c for c in cells if c.get("skipped")]
    errors = [c for c in cells if "error" in c]
    print(f"\nRoofline table (single-pod 16x16; {len(done)} compiled cells, "
          f"{len(skipped)} documented skips, {len(errors)} errors)")
    print(fmt_table(cells, "pod"))
    dt = (time.perf_counter() - t0) * 1e6
    return [("roofline", dt,
             f"cells={len(done)};skips={len(skipped)};errors={len(errors)}")]


if __name__ == "__main__":
    for row in run():
        print(",".join(str(x) for x in row))


def energy_report(cells=None):
    """CiM energy accounting per cell: the paper's J/MAC model applied to
    the dry-run MAC counts — what the accuracy-energy trade buys at scale.
    MACs = MODEL_FLOPS / 2; energies at the 8-bit operating point."""
    from repro.core import energy_model as em

    cells = cells or load_cells()
    e_exact = em.energy_per_mac_j("exact", 8)
    print(f"\nCiM energy per step (8-bit point; exact {e_exact*1e12:.2f} "
          f"pJ/MAC vs log_our "
          f"{em.energy_per_mac_j('log_our', 8)*1e12:.2f}, appro42 "
          f"{em.energy_per_mac_j('appro42', 8)*1e12:.2f})")
    print(f"{'cell':38s} {'MACs':>10} {'exact(J)':>9} {'appro42(J)':>10} "
          f"{'saving':>7}")
    for r in cells:
        if r.get("skipped") or "error" in r or r.get("mesh") != "pod":
            continue
        if r["shape"] != "train_4k":
            continue
        macs = r["model_flops"] / 2
        ej = macs * e_exact
        aj = macs * em.energy_per_mac_j("appro42", 8)
        print(f"{r['arch']+'/'+r['shape']:38s} {macs:10.2e} {ej:9.1f} "
              f"{aj:10.1f} {1-aj/ej:6.1%}")
    return [("cim_energy", 0.0, "per-step J at paper Table II rates")]
