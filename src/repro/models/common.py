"""Shared building blocks: params with logical sharding specs, norms,
RoPE, MLPs, and the CiM-aware linear layer (the paper's technique as a
first-class execution mode of every matmul in the zoo)."""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.approx_gemm import (NOISE_KIND, GemmParams, model_matmul,
                                    surrogate_noise)
from repro.core.compiler import CiMConfig, CiMMacro, compile_macro
from repro.core.quantization import fake_quant, quant_scale

# ---------------------------------------------------------------------------
# Params with logical partition specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Param:
    """A weight plus its *logical* partition spec (resolved at launch by
    parallel/sharding.py).  Leaves of the params pytree."""

    value: Any
    spec: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.spec),
    lambda spec, ch: Param(ch[0], spec),
)


def _ambient_mesh():
    """The (abstract) mesh installed by an enclosing
    ``jax.set_mesh(mesh)`` block, or None.  The single home of the
    ambient-mesh probe (used by both the GSPMD constraint path `wsc`
    and the §11 mesh dispatch routing); valid inside a jit trace."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def wsc(x, spec: Tuple):
    """with_sharding_constraint against the *ambient* mesh (no-op when
    tracing without one, e.g. in single-device smoke tests).  `spec` is a
    tuple of logical axis names resolved by parallel/sharding rules."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    from repro.parallel.sharding import logical_to_spec

    resolved = logical_to_spec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, resolved))


def fsdp_gather(w: Param):
    """ZeRO-3 use-time gather: weights are *stored* with their d_model
    ('embed') dim sharded on the data axis; before compute we constrain
    them to drop that axis (XLA inserts the per-layer all-gather, which
    its latency-hiding scheduler overlaps with compute on TPU) while
    keeping tensor-parallel axes ('heads'/'ff'/'vocab'/'expert') sharded.
    Without this, GSPMD resolves the data-axis conflict (batch vs d_model)
    by un-sharding the *batch* — catastrophically (see DESIGN.md §5)."""
    if w.spec is None:
        return w.value
    spec = list(w.spec)
    if len(spec) == w.value.ndim + 1 and spec[0] == "layers":
        spec = spec[1:]          # scanned-body slice: leading axis gone
    return wsc(w.value, tuple(None if s == "embed" else s for s in spec))


def param(key, shape, spec, dtype=jnp.bfloat16, scale: float = 0.02,
          init: str = "normal") -> Param:
    if init == "normal":
        v = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    elif init == "zeros":
        v = jnp.zeros(shape, dtype=jnp.float32)
    elif init == "ones":
        v = jnp.ones(shape, dtype=jnp.float32)
    else:
        raise ValueError(init)
    return Param(v.astype(dtype), spec)


def unbox(tree):
    """Param tree -> raw value tree."""
    return jax.tree_util.tree_map(lambda p: p.value, tree,
                                  is_leaf=lambda x: isinstance(x, Param))


def specs_of(tree):
    """Param tree -> logical-spec tree (same structure as unbox)."""
    return jax.tree_util.tree_map(lambda p: p.spec, tree,
                                  is_leaf=lambda x: isinstance(x, Param))


# ---------------------------------------------------------------------------
# Normalization / activations
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    # NOTE (EXPERIMENTS.md §Perf it.3): two "optimizations" of this
    # function were tried and REVERTED after measurement — (a) a
    # custom_vjp keeping big tensors bf16 (custom_vjp residuals are
    # opaque to jax.checkpoint, so norms started SAVING their inputs
    # instead of being rematerialized), and (b) a bf16-square /
    # f32-accumulate mean (same effect through AD). Both raised HBM
    # bytes 19%.  The plain f32-upcast form fuses best under remat.
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layer_norm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w + b


def apply_norm(params, x, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"].value)
    return layer_norm(x, params["scale"].value, params["bias"].value)


def init_norm(key, d, kind: str):
    if kind == "rmsnorm":
        return {"scale": param(key, (d,), (None,), init="ones")}
    return {"scale": param(key, (d,), (None,), init="ones"),
            "bias": param(key, (d,), (None,), init="zeros")}


# ---------------------------------------------------------------------------
# RoPE (fractional; chatglm's 2d-rope == fraction 0.5, stablelm 0.25)
# ---------------------------------------------------------------------------


def rope_tables(positions, head_dim: int, fraction: float, theta: float):
    rot = int(head_dim * fraction)
    rot -= rot % 2
    if rot == 0:
        return None
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, rot/2)
    return jnp.cos(ang), jnp.sin(ang), rot


def apply_rope(x, tables):
    """x: (B, S, H, D); tables from rope_tables (positions (B, S))."""
    if tables is None:
        return x
    cos, sin, rot = tables
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# CiM-aware linear
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CiMParams:
    """Static (trace-time) CiM execution parameters, from a compiled macro.

    Execution is delegated to the kernel dispatch engine in
    core/approx_gemm.py (DESIGN.md §8); this class only carries the
    routing inputs (family/mode/bits) and the calibrated surrogate
    coefficients, plus the per-module allocation filter."""

    mode: str = "off"            # off | one of core.approx_gemm.MODES
    bits: int = 8
    family: str = "exact"        # exact | appro42 | mitchell | log_our
    mu: float = 0.0
    c0: float = 0.0
    c1: float = 0.0
    compressor: str = "yang1"
    n_approx_cols: Optional[int] = None
    apply_to: tuple = ()         # name prefixes; () = every matmul
    per_token: bool = False      # per-row activation scales (DESIGN.md §12)
    attn: bool = False           # fused CiM attention (DESIGN.md §13)
    attn_heads: Optional[tuple] = None   # per-q-head family allocation
    fault: Optional[Any] = None  # as-fabricated defects (DESIGN.md §14)
    # heterogeneous per-module allocation (DESIGN.md §16): compiled
    # (prefix, GemmParams, apply) entries, longest prefix first.  Name
    # routing happens at trace time, so each module pins its own frozen
    # GemmParams — one cached executable per (gp, shape) as usual, zero
    # steady-state retraces.
    alloc: Optional[tuple] = None

    @classmethod
    def from_config(cls, cim: Optional[CiMConfig]) -> "CiMParams":
        if cim is None:
            return cls()
        macro: CiMMacro = compile_macro(cim)
        s = macro.surrogate
        ah = getattr(cim, "attn_heads", None)
        alloc = None
        if getattr(cim, "alloc", None):
            from repro.core.error_model import SurrogateModel
            from repro.core.multipliers import MultiplierSpec

            entries = []
            for prefix, family, compressor, ncols in cim.alloc:
                spec = MultiplierSpec(family, cim.bits, cim.signed,
                                      compressor, ncols)
                sur = (SurrogateModel.exact(spec) if family == "exact"
                       else SurrogateModel.fit(spec))
                gp = GemmParams.from_spec(spec, sur, cim.mode)
                if cim.per_token:
                    gp = dataclasses.replace(gp, per_token=True)
                entries.append((prefix, gp, family != "exact"))
            # longest prefix wins: sort once, match first
            entries.sort(key=lambda e: len(e[0]), reverse=True)
            alloc = tuple(entries)
        return cls(mode=cim.mode, bits=cim.bits, family=cim.family,
                   mu=s.mu_rel, c0=s.c0_abs, c1=s.c1_rel,
                   compressor=cim.compressor,
                   n_approx_cols=cim.n_approx_cols,
                   apply_to=tuple(getattr(cim, "apply_to", ())),
                   per_token=bool(getattr(cim, "per_token", False)),
                   attn=bool(getattr(cim, "attn", False)),
                   attn_heads=tuple(ah) if ah is not None else None,
                   fault=getattr(cim, "fault", None),
                   alloc=alloc)

    def gemm_params(self) -> GemmParams:
        return GemmParams(family=self.family, bits=self.bits,
                          mode=self.mode, mu=self.mu, c0=self.c0,
                          c1=self.c1, compressor=self.compressor,
                          n_approx_cols=self.n_approx_cols,
                          per_token=self.per_token, fault=self.fault)

    def selects(self, name: str) -> bool:
        """Mixed-macro allocation (beyond-paper DSE extension): does the
        approximate family apply to this matmul?  Unselected matmuls run
        the exact int8 macro instead."""
        return not self.apply_to or any(name.startswith(p)
                                        for p in self.apply_to)

    def routing(self, name: str) -> Tuple[GemmParams, bool]:
        """(gemm params, apply) for one named matmul.  With an `alloc`
        table the longest matching prefix picks the module's multiplier
        ("exact" entries and unmatched names run the exact int8 macro,
        apply=False); otherwise the homogeneous (family, apply_to)
        routing applies."""
        if self.alloc is not None:
            for prefix, gp, apply in self.alloc:
                if name.startswith(prefix):
                    return gp, apply
            return self.gemm_params(), False
        return self.gemm_params(), self.selects(name)


@dataclasses.dataclass
class CiMContext:
    """Per-call context: static params + an optional traced noise key."""

    p: CiMParams
    key: Optional[jax.Array] = None

    def child(self, name: str) -> "CiMContext":
        if self.key is None:
            return self
        sub = jax.random.fold_in(self.key, zlib.crc32(name.encode()))
        return CiMContext(self.p, sub)


OFF = CiMContext(CiMParams())

# Trace-time interception of every named linear (core/allocate.py's
# mixing evaluator; DESIGN.md §16).  The hook is called as
# fn(x, wv, ctx, name) AFTER the FSDP gather; returning None falls
# through to normal routing, any other value becomes the layer output
# (bias is still added by cim_linear).  List-of-one so closures see
# swaps without a global statement.
_LINEAR_OVERRIDE = [None]


def set_linear_override(fn) -> None:
    """Install (or clear, with None) the cim_linear interception hook."""
    _LINEAR_OVERRIDE[0] = fn

# NOISE_KIND / surrogate_noise live in core/approx_gemm.py now (they are
# part of the shared dispatch engine) and are re-exported here for
# backward compatibility.  "rademacher" matches the surrogate's first
# two moments at a fraction of a gaussian's cost — sampling a gaussian
# lowers to an erf_inv chain materializing f32 tensors of the full
# activation shape (measured ~20% of HBM bytes at 671B scale), while
# rademacher is one bit-sample + select; downstream contractions
# re-gaussianize the error by CLT (EXPERIMENTS.md §Perf it.2).
_ = (NOISE_KIND, surrogate_noise)


def _tp_mesh_args(x, wv, spec, p: CiMParams):
    """Mesh-execution routing for one integer-mode cim_linear call
    (DESIGN.md §11).  Resolves the weight's compute-time logical spec
    (embed/FSDP axis dropped, exactly like `fsdp_gather`) against the
    ambient mesh; when the result tensor-parallel-shards exactly one
    weight dim, returns (mesh, x_spec, w_spec) for `model_matmul`'s
    shard_map path — replacing the constraint-only GSPMD route for the
    hardware modes.  Returns None (caller keeps the GSPMD path) for
    replicated weights, non-integer modes, or no ambient mesh."""
    from repro.core.approx_gemm import MESH_MODES

    if p.mode not in MESH_MODES or spec is None:
        return None
    if p.per_token:
        return None      # mesh shards quantize against global scales
    mesh = _ambient_mesh()
    if mesh is None:
        return None
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import batch_axes, logical_to_spec

    sp = list(spec)
    if len(sp) == wv.ndim + 1 and sp[0] == "layers":
        sp = sp[1:]                     # scanned-body slice
    if len(sp) != wv.ndim:
        return None
    sp = tuple(None if s == "embed" else s for s in sp)
    wspec = logical_to_spec(sp, wv.shape, mesh)
    if (wspec[0] is not None) == (wspec[1] is not None):
        return None                     # replicated: nothing to partition
    m = 1
    for s in x.shape[:-1]:
        m *= int(s)
    dp = batch_axes(mesh, m)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    return mesh, P(dp_entry, wspec[0]), wspec


def cim_linear(x, w: Param, ctx: CiMContext, name: str = "",
               bias: Optional[Param] = None):
    """y = approx(x @ w) per the CiM context; STE-quantized for training.

    x: (..., K); w.value: (K, N) (higher-rank weights are 2D-ified).
    Routing — which kernel runs this matmul for the context's
    (family, mode, bits, backend) — is delegated to the dispatch engine
    (core/approx_gemm.model_matmul, DESIGN.md §8); this wrapper only
    resolves sharding, the per-name noise key and the per-module
    allocation filter.  model_matmul executes through the engine's
    zero-retrace executable cache, so eager layer calls (serving,
    notebooks) are dict hits after the first touch; inside a jitted
    train step the cached jit inlines into the outer trace.

    Under an ambient mesh, the integer modes (bit_exact/hardware) run
    mesh-partitioned (DESIGN.md §11): the weight's logical spec picks
    the tensor-parallel layout and the matmul executes one per-shard
    Pallas kernel per device under shard_map, bit-identical to the
    single-device path.  Other modes keep the GSPMD constraint route.
    """
    wv = fsdp_gather(w)
    assert wv.ndim == 2, "cim_linear expects 2-D weights (flatten heads)"
    if _LINEAR_OVERRIDE[0] is not None:
        out = _LINEAR_OVERRIDE[0](x, wv, ctx, name)
        if out is not None:
            if bias is not None:
                out = out + bias.value
            return out
    p = ctx.p
    if p.mode == "off":
        out = x @ wv
    else:
        key = ctx.child(name).key if name else ctx.key
        gp, apply = p.routing(name)
        margs = _tp_mesh_args(x, wv, w.spec, p) if apply else None
        if margs is not None:
            mesh, x_spec, w_spec = margs
            out = model_matmul(x, wv, gp, key, apply=True,
                               mesh=mesh, x_spec=x_spec, w_spec=w_spec)
        else:
            out = model_matmul(x, wv, gp, key, apply=apply)
    if bias is not None:
        out = out + bias.value
    return out


def cim_einsum(eqn: str, x, w: Param, ctx: CiMContext, name: str = ""):
    """CiM-aware einsum for >2-D weights (expert banks).  Surrogate noise
    uses the rank-1 (fast) variance estimate; bit_exact is not supported
    here (expert banks are a production-scale path)."""
    wv = fsdp_gather(w)
    p = ctx.p
    if p.mode == "off":
        return jnp.einsum(eqn, x, wv)
    xq = fake_quant(x, p.bits, axis=-1 if p.per_token else None)
    wq = fake_quant(wv, p.bits).astype(x.dtype)
    d = jnp.einsum(eqn, xq, wq)
    gp, apply = p.routing(name)
    if not apply:
        return d                 # mixed allocation: exact int8 macro
    out = (1.0 + gp.mu) * d
    key = ctx.child(name).key if name else ctx.key
    if p.mode in ("surrogate", "surrogate_fast") and key is not None \
            and (gp.c0 > 0.0 or gp.c1 > 0.0):
        k_len = x.shape[-1]
        sx = quant_scale(jax.lax.stop_gradient(x), p.bits)
        sw = quant_scale(jax.lax.stop_gradient(wv), p.bits)
        scale2 = (sx * sw).astype(jnp.float32) ** 2
        var = (gp.c0 + gp.c1 * (0.5 * 127.0 ** 2) ** 1) * k_len * scale2
        eps = surrogate_noise(key, d.shape, d.dtype)
        out = out + jax.lax.stop_gradient(
            jnp.sqrt(jnp.maximum(var, 0.0)).astype(d.dtype) * eps)
    return out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, act: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    p = {"wo": param(ks[2], (d_ff, d_model), ("ff", "embed"), dtype)}
    if act == "swiglu":
        p["wi"] = param(ks[0], (d_model, d_ff), ("embed", "ff"), dtype)
        p["wg"] = param(ks[1], (d_model, d_ff), ("embed", "ff"), dtype)
    else:
        p["wi"] = param(ks[0], (d_model, d_ff), ("embed", "ff"), dtype)
    return p


def apply_mlp(params, x, act: str, ctx: CiMContext):
    if act == "swiglu":
        h = jax.nn.silu(cim_linear(x, params["wi"], ctx, "mlp_wi"))
        g = cim_linear(x, params["wg"], ctx, "mlp_wg")
        h = h * g
    else:
        h = jax.nn.gelu(cim_linear(x, params["wi"], ctx, "mlp_wi"))
    return cim_linear(h, params["wo"], ctx, "mlp_wo")
