"""The plain float32 references against the repo's own float forward
(`cim=None`) at smoke width, on the same weights upcast to float32."""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import weights as wmod  # noqa: E402
from bench.spec import load_module  # noqa: E402
from bench.testdata.cells import TINY_CONFIGS  # noqa: E402


def ref_module(cfg):
    return load_module(os.path.join(ROOT, "bench", "reference",
                                    cfg["reference"] + ".py"),
                       "ref_" + cfg["reference"])


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_reference_matches_program_float_forward(name):
    from repro.models.transformer import LM

    cfg = TINY_CONFIGS[name]
    w = wmod.make(cfg, 2**40 + 3)
    prog_cfg = dataclasses.replace(wmod.program_config(cfg), cim=None)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    wmod.to_program(w, prog_cfg))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(LM(prog_cfg).forward_logits(
            params, {"tokens": jnp.asarray(toks[None], jnp.int32)})[0])
    got = np.asarray(ref_module(cfg).forward(w, jnp.asarray(toks), cfg))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err
    # rows: the same logits at chosen positions only
    rows = jnp.asarray([0, 7, 39])
    part = np.asarray(ref_module(cfg).forward(w, jnp.asarray(toks), cfg,
                                              rows=rows))
    np.testing.assert_allclose(part, got[[0, 7, 39]], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_control_is_the_reference_at_four_bits(name):
    cfg = TINY_CONFIGS[name]
    w = wmod.make(cfg, 11)
    toks = jnp.asarray(np.arange(32) % cfg["vocab_size"])
    ref = ref_module(cfg)
    full = np.asarray(ref.forward(w, toks, cfg))
    low = np.asarray(ref.forward(w, toks, cfg, quant_bits=4))
    err = np.linalg.norm(low - full) / np.linalg.norm(full)
    assert 0.01 < err < 2.0, err


def test_weights_are_the_programs_buffers():
    cfg = TINY_CONFIGS["tiny-qwen3"]
    w = wmod.make(cfg, 5)
    params = wmod.to_program(w, wmod.program_config(cfg))
    assert params["embed"].value is w["embed"]
    assert params["body"]["0"]["mlp"]["wi"].value is w["w_gate"]
    assert "head" not in params               # tied, as published
    again = wmod.make(cfg, 5)
    assert all(np.array_equal(np.asarray(w[k]), np.asarray(again[k]))
               for k in w)
    other = wmod.make(cfg, 2**33 + 5)
    assert not np.array_equal(np.asarray(w["wq"]), np.asarray(other["wq"]))


def test_rotary_qk_bias_is_shared_by_q_and_k():
    cfg = TINY_CONFIGS["test-stablelm"]
    w = wmod.make(cfg, 2**40 + 7)
    rot = wmod.rotary_dims(cfg)
    bq = np.asarray(w["bq"], np.float32)
    bk = np.asarray(w["bk"], np.float32)
    np.testing.assert_array_equal(bq[..., :rot], bk[..., :rot])
    assert np.std(bq[..., :rot]) > 2.0
    assert np.std(bq[..., rot:]) < 0.1
