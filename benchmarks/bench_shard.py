"""Mesh-partitioned dispatch benchmark -> BENCH_shard.json.

Measures the DESIGN.md §11 tensor-parallel path on a forced 8-device
host platform (the measurement runs in a subprocess so the parent
process keeps its own jax device view; any pre-existing XLA_FLAGS
content is preserved).  Per (family, layout, shape) row:

  * **bit_identical** — the shard_map executable vs the single-device
    oracle (the §11 contract: integer modes are bitwise).
  * **per-shard bytes** — operand bytes each device touches vs the
    1-device baseline (the real scaling signal: K- or N-sharding cuts
    the per-device operand and LUT-gather volume by the TP degree).
  * **collective bytes per device** — parsed from the compiled HLO
    (launch/hlo_analysis): in the contraction-sharded layout only the
    (M, N) int32 partial accumulator crosses the interconnect; the
    output-sharded layout is collective-free.  An analytic ring
    all-reduce model (2·(tp-1)/tp · M·N·4) is recorded alongside.
  * **wall times** — median-of-reps for the sharded and 1-device
    executables.  On a CPU host mesh the 8 "devices" time-share one
    machine and Pallas runs interpreted, so sharded wall-clock is
    EMULATION ONLY (recorded with ``emulated_on_cpu: true``); on real
    hardware the per-shard volume column is the speedup ceiling.
  * **steady_retraces** — the §8 trace probe across repeated calls and
    layout switches, asserted 0.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)
OUT_PATH = os.path.join(_DIR, "BENCH_shard.json")
OUT_PATH_SMOKE = os.path.join(_DIR, "BENCH_shard.smoke.json")
N_DEVICES = 8

_CHILD = r'''
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import approx_gemm as ag
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh

SMOKE = {smoke}
FAST = {fast}
REPS = {reps}
mesh = make_mesh((1, 8), ("data", "model"))
TP = 8

GEMM_SHAPES = ([(16, 64, 32)] if SMOKE
               else [(64, 256, 128)] if FAST
               else [(64, 256, 128), (128, 512, 256)])
FAMS = ([("exact", "hardware", None), ("log_our", "hardware", None)]
        if SMOKE else
        [("exact", "hardware", None), ("appro42", "hardware", 6),
         ("log_our", "hardware", None)])
LAYOUTS = [("K", P(None, "model"), P("model", None)),
           ("N", P(None, None), P(None, "model"))]


def median_time(fn, reps=REPS):
    fn()                                   # warm (compile outside timing)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)      # us


rows = []
for m, k, n in GEMM_SHAPES:
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    for fam, mode, nac in FAMS:
        gp = ag.GemmParams(family=fam, bits=8, mode=mode,
                           n_approx_cols=nac)
        base = ag.cim_matmul(x, w, gp)
        t_base = median_time(lambda: ag.cim_matmul(x, w, gp))
        for lname, xs, ws in LAYOUTS:
            out = ag.cim_matmul(x, w, gp, mesh=mesh, x_spec=xs,
                                w_spec=ws)
            bit = bool(jnp.all(out == base))
            t_mesh = median_time(
                lambda: ag.cim_matmul(x, w, gp, mesh=mesh, x_spec=xs,
                                      w_spec=ws))
            mark = ag.trace_count()
            for _ in range(3):
                ag.cim_matmul(x, w, gp, mesh=mesh, x_spec=xs, w_spec=ws)
                ag.cim_matmul(x, w, gp)
            retraces = ag.trace_count() - mark
            compiled = jax.jit(
                lambda a, b: ag.cim_matmul(a, b, gp, mesh=mesh,
                                           x_spec=xs, w_spec=ws)
            ).lower(x, w).compile()
            hlo = hlo_analysis.analyze(compiled.as_text())
            kl = k // TP if lname == "K" else k
            nl = n // TP if lname == "N" else n
            rows.append({{
                "op": "gemm", "family": fam, "mode": mode,
                "layout": lname, "m": m, "k": k, "n": n, "tp": TP,
                "bit_identical": bit,
                "bytes_per_shard": 4 * (m * kl + kl * nl + m * nl),
                "bytes_one_device": 4 * (m * k + k * n + m * n),
                "collective_bytes_per_device_hlo":
                    hlo["collective_bytes"],
                "collective_bytes_ring_model":
                    (2 * (TP - 1) / TP * m * n * 4
                     if lname == "K" else 0),
                "t_one_device_us": t_base, "t_mesh_us": t_mesh,
                "emulated_on_cpu": jax.default_backend() != "tpu",
                "steady_retraces": retraces,
            }})

# one conv row per family: input-channel (contraction) sharding
b, h, w_, c, co = (2, 8, 8, 16, 8) if SMOKE else (4, 16, 16, 32, 16)
x4 = jax.random.normal(jax.random.PRNGKey(2), (b, h, w_, c), jnp.float32)
w2 = jax.random.normal(jax.random.PRNGKey(3), (9 * c, co), jnp.float32)
for fam, mode, nac in FAMS:
    gp = ag.GemmParams(family=fam, bits=8, mode=mode, n_approx_cols=nac)
    base = ag.cim_conv2d(x4, w2, gp)
    t_base = median_time(lambda: ag.cim_conv2d(x4, w2, gp))
    out = ag.cim_conv2d(x4, w2, gp, mesh=mesh,
                        x_spec=P(None, None, None, None),
                        w_spec=P("model", None))
    t_mesh = median_time(
        lambda: ag.cim_conv2d(x4, w2, gp, mesh=mesh,
                              x_spec=P(None, None, None, None),
                              w_spec=P("model", None)))
    mark = ag.trace_count()
    for _ in range(3):
        ag.cim_conv2d(x4, w2, gp, mesh=mesh,
                      x_spec=P(None, None, None, None),
                      w_spec=P("model", None))
        ag.cim_conv2d(x4, w2, gp)
    retraces = ag.trace_count() - mark
    compiled = jax.jit(
        lambda a, b2: ag.cim_conv2d(a, b2, gp, mesh=mesh,
                                    x_spec=P(None, None, None, None),
                                    w_spec=P("model", None))
    ).lower(x4, w2).compile()
    hlo = hlo_analysis.analyze(compiled.as_text())
    rows.append({{
        "op": "conv3x3", "family": fam, "mode": mode, "layout": "C",
        "b": b, "h": h, "w": w_, "c": c, "n": co, "tp": TP,
        "bit_identical": bool(jnp.all(out == base)),
        "bytes_per_shard": 4 * (b * h * w_ * (c // TP)
                                + 9 * (c // TP) * co + b * h * w_ * co),
        "bytes_one_device": 4 * (b * h * w_ * c + 9 * c * co
                                 + b * h * w_ * co),
        "collective_bytes_per_device_hlo": hlo["collective_bytes"],
        "collective_bytes_ring_model": 2 * (TP - 1) / TP
                                       * b * h * w_ * co * 4,
        "t_one_device_us": t_base, "t_mesh_us": t_mesh,
        "emulated_on_cpu": jax.default_backend() != "tpu",
        "steady_retraces": retraces,
    }})

print(json.dumps({{"n_devices": len(jax.devices()),
                   "backend": jax.default_backend(), "rows": rows}}))
'''


def run(fast: bool = True, smoke: bool = False, reps: int = 3):
    """Run the sharded-dispatch benchmark in a forced-8-device child
    and write BENCH_shard[.smoke].json.  Returns bench CSV rows.
    `fast` drops the larger GEMM shape (the committed trajectory JSON
    comes from a `fast=False` run)."""
    sys.path.insert(0, _REPO + "/src")
    from repro.launch.hostdev import force_host_devices

    # the child emulates the mesh on host devices and must never compete
    # with this process (or any other) for an accelerator
    env = force_host_devices(N_DEVICES, dict(os.environ, JAX_PLATFORMS="cpu"))
    code = ("import sys; sys.path.insert(0, %r)\n" % (_REPO + "/src")
            + _CHILD.format(smoke=smoke, fast=fast,
                            reps=1 if smoke else reps))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError("bench_shard child failed:\n"
                           + out.stderr[-3000:])
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    bad = [r for r in payload["rows"] if not r.get("bit_identical", True)]
    payload["all_bit_identical"] = not bad
    # strict indexing: a row missing its probe is a harness bug, not a
    # silently-passing property
    payload["zero_steady_state_retraces"] = all(
        r["steady_retraces"] == 0 for r in payload["rows"])
    path = OUT_PATH_SMOKE if smoke else OUT_PATH
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"wrote {path}")
    rows = []
    for r in payload["rows"]:
        label = (f"shard_{r['op']}_{r['family']}_{r['layout']}"
                 + (f"_{r['m']}x{r['k']}x{r['n']}" if r["op"] == "gemm"
                    else ""))
        shrink = r["bytes_one_device"] / max(r["bytes_per_shard"], 1)
        rows.append((label, r["t_mesh_us"],
                     f"bit={r['bit_identical']};"
                     f"bytes/shard÷{shrink:.1f};"
                     f"coll={r.get('collective_bytes_per_device_hlo', 0)}"))
    return rows


if __name__ == "__main__":
    run(fast="--fast" in sys.argv, smoke="--smoke" in sys.argv)
