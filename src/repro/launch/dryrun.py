"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, with NO device allocation (ShapeDtypeStruct
inputs end-to-end):

  * proof the sharded program compiles on the production mesh
    (16x16 single-pod and 2x16x16 multi-pod),
  * ``memory_analysis()``    -> bytes-per-device (fits / doesn't fit),
  * ``cost_analysis()``      -> XLA's aggregate flops/bytes (loop bodies
                                counted once — kept as a cross-check),
  * hlo_analysis             -> loop-scaled flops / bytes / collective
                                bytes per device (the roofline inputs),
  * analytic MODEL_FLOPS     -> 6*N_active*D (train) or 2*N_active*D.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod-only|--singlepod-only]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>[__tag].json.
"""

import os

# Force the 512-device host platform BEFORE jax initializes (appends to
# any user XLA_FLAGS, never clobbers — repro.launch.hostdev is the
# single home of that rule and imports no jax)
from repro.launch.hostdev import force_host_devices

force_host_devices(512)

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import arch_names, get_config, input_specs
from repro.core.compiler import CiMConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES, shape_applicable
from repro.models.transformer import LM, count_params
from repro.optim import adamw
from repro.parallel.sharding import (DECODE_RULES, batch_sharding,
                                     param_shardings, replicated)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _cache_shardings(cache_shape, mesh, model):
    """Resolve the model's logical cache specs against the mesh (batch on
    data axes, KV-head/latent dims on model; divisibility fallback) —
    the shared helper the serving engine's DP slot pool uses too."""
    from repro.parallel.sharding import cache_shardings

    return cache_shardings(cache_shape, mesh, model.cfg)


def make_train_step(model: LM, opt_cfg: adamw.AdamWConfig):
    ga = model.cfg.grad_accum

    def train_step(params, opt_state, batch, key):
        def loss_of(p, b, k):
            return model.loss_fn(p, b, k)[0]

        if ga > 1:
            def split(x):
                return x.reshape((ga, x.shape[0] // ga) + x.shape[1:])

            mb = jax.tree_util.tree_map(split, batch)
            keys = jax.random.split(key, ga)

            def acc(carry, xs):
                g_acc, l_acc = carry
                b, k = xs
                l, g = jax.value_and_grad(loss_of)(params, b, k)
                g_acc = jax.tree_util.tree_map(
                    lambda a, d: a + d.astype(a.dtype), g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
            (grads, loss), _ = jax.lax.scan(acc, (g0, 0.0), (mb, keys))
            grads = jax.tree_util.tree_map(lambda g: g / ga, grads)
            loss = loss / ga
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch, key)
        new_p, new_o, _ = adamw.apply_updates(params, grads, opt_state,
                                              opt_cfg)
        return new_p, new_o, loss

    return train_step


def make_serve_step(model: LM):
    def serve_step(params, caches, tokens, pos, key):
        logits, caches = model.decode_step(params, caches, tokens, pos, key)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], caches

    return serve_step


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cim: str = "log_our:surrogate", tag: str = ""):
    shape = SHAPES[shape_name]
    cim_cfg = None
    if cim and cim != "off":
        fam, mode = cim.split(":")
        cim_cfg = CiMConfig(family=fam, bits=8, mode=mode)
    cfg = get_config(arch, cim=cim_cfg)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "skipped": True, "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = LM(cfg)
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = DECODE_RULES if shape.kind == "decode" else None
    pshard = param_shardings(model, pshape, mesh, rules=rules)
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    specs = input_specs(cfg, shape)
    batch_shd = jax.tree_util.tree_map(
        lambda s: batch_sharding(mesh, len(s.shape), s.shape[0]), specs)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig(state_bits=8)
            oshape = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), pshape)
            state_shd = adamw.moment_shardings(pshape, pshard, mesh)
            oshard = adamw.OptState(step=replicated(mesh), m=state_shd,
                                    v=state_shd)
            step = make_train_step(model, opt_cfg)
            jf = jax.jit(step,
                         in_shardings=(pshard, oshard, batch_shd,
                                       replicated(mesh)),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
            lowered = jf.lower(pshape, oshape, specs, key_spec)
        elif shape.kind == "prefill":
            jf = jax.jit(model.prefill,
                         in_shardings=(pshard, batch_shd, replicated(mesh)))
            lowered = jf.lower(pshape, specs, key_spec)
        else:  # decode
            cshape = jax.eval_shape(
                lambda: model.init_caches(shape.global_batch, shape.seq_len))
            cshard = _cache_shardings(cshape, mesh, model)
            step = make_serve_step(model)
            jf = jax.jit(step,
                         in_shardings=(pshard, cshard, batch_shd["tokens"],
                                       replicated(mesh), replicated(mesh)),
                         out_shardings=(batch_shd["tokens"], cshard),
                         donate_argnums=(1,))
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jf.lower(pshape, cshape, specs["tokens"], pos_spec,
                               key_spec)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = hlo_analysis.xla_cost_dict(compiled)
    hlo_text = compiled.as_text()
    hlo = hlo_analysis.analyze(hlo_text)
    # persist the per-device HLO so the roofline can be re-derived without
    # recompiling (gzip: ~10x)
    import gzip

    os.makedirs(OUT_DIR, exist_ok=True)
    mesh_name = "multipod" if multi_pod else "pod"
    suffix = f"__{tag}" if tag else ""
    with gzip.open(os.path.join(
            OUT_DIR, f"{arch}__{shape_name}__{mesh_name}{suffix}.hlo.gz"),
            "wt") as f:
        f.write(hlo_text)
    n_active = count_params(cfg, active=True)
    tokens = (shape.tokens if shape.kind != "decode" else shape.global_batch)
    factor = 6 if shape.kind == "train" else 2
    n_dev = 512 if multi_pod else 256
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multipod" if multi_pod else "pod",
        "cim": cim, "tag": tag,
        "skipped": False,
        "n_devices": n_dev,
        # the devices the HLO was compiled for (benchmarks/roofline.py
        # takes its peaks from this, and refuses kinds it has none for)
        "device_kind": jax.devices()[0].device_kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "args_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": (ma.argument_size_in_bytes
                           + ma.temp_size_in_bytes
                           + ma.output_size_in_bytes
                           - ma.alias_size_in_bytes),
        },
        "xla_cost": {"flops": ca.get("flops", 0.0),
                     "bytes": ca.get("bytes accessed", 0.0)},
        "hlo": hlo,
        "model_flops": float(factor) * n_active * tokens,
        "n_active_params": n_active,
        "n_total_params": count_params(cfg),
        "tokens": tokens,
        "grad_accum": cfg.grad_accum,
    }


def run_cell(arch, shape_name, multi_pod, cim="log_our:surrogate", tag="",
             out_dir=OUT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    mesh_name = "multipod" if multi_pod else "pod"
    suffix = f"__{tag}" if tag else ""
    fname = os.path.join(out_dir,
                         f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    try:
        res = lower_cell(arch, shape_name, multi_pod, cim=cim, tag=tag)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "tag": tag, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    with open(fname, "w") as f:
        json.dump(res, f, indent=1)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--singlepod-only", action="store_true")
    ap.add_argument("--cim", default="log_our:surrogate")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else arch_names()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = []
    if not args.multipod_only:
        meshes.append(False)
    if not args.singlepod_only:
        meshes.append(True)
    if args.all or not args.arch:
        pass
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.time()
                res = run_cell(arch, shape, mp, cim=args.cim, tag=args.tag,
                               out_dir=args.out)
                status = ("SKIP" if res.get("skipped")
                          else "ERR " if "error" in res else "OK  ")
                mem = res.get("memory", {}).get("peak_bytes", 0) / 1e9
                print(f"{status} {arch:24s} {shape:12s} "
                      f"{'multipod' if mp else 'pod':8s} "
                      f"peak={mem:6.2f}GB/dev  ({time.time()-t0:.0f}s)",
                      flush=True)
                if "error" in res:
                    print("     ", res["error"][:200], flush=True)


if __name__ == "__main__":
    main()
