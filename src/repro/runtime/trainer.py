"""Distributed training runtime: sharded step, grad accumulation,
checkpoint/restart, straggler detection, elastic re-mesh.

Fault-tolerance model (1000+ node posture, DESIGN.md §5):
  * every state that matters (params, optimizer, data cursor, RNG) lives
    in one checkpoint tree with an atomic commit — any step can be
    replayed bit-exactly after a crash (tests/test_runtime.py kills a
    run mid-flight and verifies the resumed loss trace);
  * stragglers: per-step wall time is tracked against a running median;
    a step slower than `straggler_factor` x median is flagged — on a
    real fleet this triggers hot-spare reslicing, here it is surfaced in
    metrics (and exercised in tests with an injected sleep);
  * elastic: `Trainer.remesh(devices)` rebuilds the mesh over however
    many devices are healthy, re-lowers the step, and restores the
    checkpoint under the new shardings (shape-preserving, topology-free).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import TokenStream
from repro.models.transformer import LM
from repro.optim import adamw
from repro.parallel.sharding import (batch_sharding, param_shardings,
                                     replicated, shardings_for_tree)


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
    straggler_factor: float = 3.0
    seed: int = 0
    accum_dtype: str = "float32"


class Trainer:
    def __init__(self, model: LM, opt_cfg: adamw.AdamWConfig, mesh,
                 tcfg: TrainerConfig, data: Optional[TokenStream] = None):
        from repro.launch.mesh import require_auto_axes

        require_auto_axes(mesh)
        self.model = model
        self.opt_cfg = opt_cfg
        self.mesh = mesh
        self.tcfg = tcfg
        self.data = data
        self.ckpt = Checkpointer(tcfg.ckpt_dir)
        self.step_times: list = []
        self.straggler_events = 0
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        model, mesh = self.model, self.mesh
        init_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.p_shardings = param_shardings(model, init_shape, mesh)
        # moments mirror the parameter shardings exactly (zero-reshard
        # Adam update; see optim/adamw.moment_shardings)
        state_shd = adamw.moment_shardings(init_shape, self.p_shardings,
                                           mesh,
                                           state_bits=self.opt_cfg.state_bits)
        self.o_shardings = adamw.OptState(step=replicated(mesh),
                                          m=state_shd, v=state_shd)

        self._init_fn = jax.jit(model.init, out_shardings=self.p_shardings)
        self._opt_init = jax.jit(lambda p: adamw.init(p, self.opt_cfg),
                                 out_shardings=self.o_shardings)
        ga = model.cfg.grad_accum
        accum_dtype = jnp.dtype(self.tcfg.accum_dtype)

        def train_step(params, opt_state, tokens, key):
            def loss_of(p, toks, k):
                return self.model.loss_fn(p, {"tokens": toks}, k)

            if ga > 1:
                b = tokens.shape[0]
                mb = tokens.reshape(ga, b // ga, tokens.shape[1])
                keys = jax.random.split(key, ga)

                def acc_step(carry, xs):
                    g_acc, l_acc = carry
                    toks, k = xs
                    (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(
                        params, toks, k)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, b_: a + b_.astype(accum_dtype), g_acc, g)
                    return (g_acc, l_acc + l), None

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), params)
                (grads, loss), _ = jax.lax.scan(acc_step, (g0, 0.0),
                                                (mb, keys))
                grads = jax.tree_util.tree_map(lambda g: g / ga, grads)
                loss = loss / ga
            else:
                (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(
                    params, tokens, key)
            new_p, new_o, metrics = adamw.apply_updates(
                params, grads, opt_state, self.opt_cfg)
            metrics["loss"] = loss
            return new_p, new_o, metrics

        batch_shd = batch_sharding(mesh, 2)
        self._step_fn = jax.jit(
            train_step,
            in_shardings=(self.p_shardings, self.o_shardings, batch_shd,
                          replicated(mesh)),
            out_shardings=(self.p_shardings, self.o_shardings, None),
            donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def init_state(self):
        with jax.set_mesh(self.mesh):
            params = self._init_fn(jax.random.PRNGKey(self.tcfg.seed))
            opt_state = self._opt_init(params)
        return params, opt_state

    def try_resume(self, params, opt_state):
        step = self.ckpt.latest_step()
        if step is None:
            return 0, params, opt_state
        tree = {"params": params, "opt": opt_state,
                "data": {"step": jnp.zeros((), jnp.int32),
                         "seed": jnp.zeros((), jnp.int32)}}
        shards = {"params": self.p_shardings, "opt": self.o_shardings,
                  "data": {"step": None, "seed": None}}
        restored = self.ckpt.restore(step, jax.eval_shape(lambda: tree),
                                     shards)
        if self.data is not None:
            self.data.restore({"step": int(restored["data"]["step"]),
                               "seed": int(restored["data"]["seed"])})
        return step, restored["params"], restored["opt"]

    def save(self, step: int, params, opt_state, blocking=False):
        data_state = (self.data.state() if self.data is not None
                      else {"step": 0, "seed": 0})
        tree = {"params": params, "opt": opt_state,
                "data": {"step": jnp.int32(data_state["step"]),
                         "seed": jnp.int32(data_state["seed"])}}
        self.ckpt.save(step, tree, blocking=blocking)

    # ------------------------------------------------------------------
    def run(self, inject_failure_at: Optional[int] = None,
            inject_straggler_at: Optional[int] = None) -> Dict[str, Any]:
        params, opt_state = self.init_state()
        start, params, opt_state = self.try_resume(params, opt_state)
        losses = []
        key = jax.random.PRNGKey(self.tcfg.seed + 17)
        with jax.set_mesh(self.mesh):
            for step in range(start, self.tcfg.steps):
                t0 = time.perf_counter()
                tokens = jnp.asarray(self.data.next_batch())
                if inject_straggler_at == step:
                    time.sleep(0.5)  # simulated slow host
                k = jax.random.fold_in(key, step)
                params, opt_state, metrics = self._step_fn(
                    params, opt_state, tokens, k)
                loss = float(metrics["loss"])
                losses.append(loss)
                dt = time.perf_counter() - t0
                self._watch_straggler(dt)
                if (step + 1) % self.tcfg.ckpt_every == 0:
                    self.save(step + 1, params, opt_state)
                if inject_failure_at is not None and step + 1 == inject_failure_at:
                    self.ckpt.wait()
                    raise RuntimeError(f"injected failure at step {step+1}")
        self.ckpt.wait()
        self.save(self.tcfg.steps, params, opt_state, blocking=True)
        return {"losses": losses, "params": params, "opt": opt_state,
                "straggler_events": self.straggler_events}

    def _watch_straggler(self, dt: float):
        self.step_times.append(dt)
        hist = self.step_times[-50:]
        if len(hist) >= 5:
            med = float(np.median(hist))
            if dt > self.tcfg.straggler_factor * med:
                self.straggler_events += 1

    # ------------------------------------------------------------------
    def remesh(self, mesh):
        """Elastic resize: rebuild the step under a new mesh; caller then
        restores the checkpoint (shardings re-derived automatically)."""
        self.mesh = mesh
        self._build()
