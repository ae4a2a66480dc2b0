"""Approximate CiM GEMM — the execution front door and dispatch engine.

Execution modes (per DESIGN.md §2):

  * ``exact``           — quantize-dequantize + float dot (QAT baseline).
  * ``bit_exact``       — every scalar product comes from the compiled
                          multiplier LUT (validation scale; pure-jnp
                          gather, O(M*K*N) memory).
  * ``hardware``        — the same integer semantics executed by the
                          Pallas TPU kernels: nibble-decomposed sub-LUT
                          gather when the family's table factorizes
                          bit-exactly (core/luts.nibble_sub_luts),
                          k-sliced full-LUT gather otherwise, the
                          arithmetic log-domain kernel for
                          mitchell/log_our.  Autotuned block sizes;
                          interpret mode off-TPU.
  * ``surrogate``       — MXU dot + calibrated error model:
                          (1+mu)*D + sigma*sqrt(A^2@B^2)*eps.
                          On TPU this dispatches to the fused Pallas
                          kernel (one HBM pass for D and SQ); elsewhere
                          to the XLA twin (2 matmuls).
  * ``surrogate_fast``  — beyond-paper optimization: rank-1 estimate of
                          the variance term (outer product of squared row/
                          col norms / K), so the overhead over an exact
                          GEMM is O(MK+KN+MN) instead of one extra GEMM.

Every (family, mode, bits, backend) combination is routed by a single
**kernel registry** (DESIGN.md §8): `select_kernel` picks the
highest-priority `KernelEntry` that supports the request (entries may
carry a per-spec predicate, e.g. nibble decomposability), `plan_gemm`
attaches an autotuned block size (core/autotune.py), and the two float
frontends execute the plan:

  * `cim_matmul`   — the macro frontend (`CiMMacro.matmul`): true
                     int-quantization, f32 output, exact-float STE VJP.
  * `model_matmul` — the model-zoo frontend (`models.common.cim_linear`):
                     fake-quant STE (QAT), activation dtype preserved,
                     rademacher surrogate noise (see models/common.py).
  * `cim_conv2d`   — the conv frontend (`models.cnn.conv2d`): implicit-
                     GEMM convolution through a conv-shaped registry
                     universe (`plan_conv`, DESIGN.md §9) — the kh*kw
                     patch gather runs inside the Pallas kernel, no
                     materialized im2col; STE backward is the exact
                     float conv VJP.

**Zero-retrace execution** (DESIGN.md §8): both frontends resolve their
work through a module-level *executable cache* keyed on
(frontend, GemmParams, routed plan, stochasticity/noise flags, operand
dtypes, power-of-two-bucketed shape, backend).  Each cache entry is a
pre-built jitted STE-wrapped function, so a steady-state eager call is
a dict hit + XLA executable-cache hit — no per-call `jax.custom_vjp`
closure construction and no retrace.  `select_kernel`/`plan_gemm` are
memoized for the same reason.  `trace_count()` exposes a probe that
increments once per actual trace (tests assert it stays flat on cache
hits); `cached=False` reproduces the legacy build-a-closure-per-call
path (the benchmark baseline, benchmarks/bench_kernels.py).

The Pallas-backed paths run **fused-quantization kernels**: float
operands in, float out, with symmetric int quantization on tile load
and the `(acc * sx) * sw` dequant epilogue on flush inside one
`pallas_call` (kernels/approx_matmul.py, mitchell_gemm.py,
cim_gemm.py).  The int-in runners (`run_int_kernel`) remain the
registry-oracle surface validated bit-for-bit against kernels/ref.py.

**Mesh-partitioned execution** (DESIGN.md §11): `plan_gemm`/`plan_conv`
accept an optional `(mesh, x_spec, w_spec)` and return a `MeshPlan`
wrapping the shard-local inner plan; the frontends then build a
`shard_map`-wrapped executable that runs one per-shard
LUT-gather/MXU/log kernel per device.  Two tensor-parallel layouts:
contraction-sharded (K for GEMMs, C for convs — the per-shard kernel
returns its raw int32 accumulator via the `*_partial` deferred-epilogue
entry points, a `jax.lax.psum` over the model axis combines them, and
the `(acc * sx) * sw` epilogue runs after the collective) and
output-sharded (N — no collective at all; each shard owns its output
columns).  Quantization scales are always computed *globally* before
the shard_map, so both layouts are bit-identical to the single-device
oracle for the integer modes (`bit_exact`, `hardware`) — integer
addition commutes exactly.  The executable cache key grows the mesh
axis sizes + specs, so mesh switches (like tier switches) stay one
dict hit and `trace_count()` stays flat in steady state.

Backward pass everywhere is a straight-through estimator (exact float
VJP), the standard choice for approximate/quantized training.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import autotune, faults
from .error_model import SurrogateModel
from .faults import FAULT_MODES, FaultConfig
from .luts import MAX_LUT_BITS, nibble_decomposable, signed_product_lut
from .multipliers import MultiplierSpec
from .quantization import dequantize, fake_quant, quant_scale, quantize

MODES = ("exact", "bit_exact", "hardware", "surrogate", "surrogate_fast")
FAMILIES = ("exact", "appro42", "mitchell", "log_our")

# Surrogate noise for the model execution paths.  "normal" is the
# calibration-faithful choice; "rademacher" (+-1 * sigma) matches the
# first two moments at a fraction of the cost (EXPERIMENTS.md §Perf
# it.2) — downstream contractions re-gaussianize the error by CLT.
NOISE_KIND = "rademacher"


# ---------------------------------------------------------------------------
# Kernel registry (DESIGN.md §8)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One executable GEMM/conv implementation and its routing envelope."""

    name: str
    modes: Tuple[str, ...]
    families: Tuple[str, ...]          # () = every family
    backends: Tuple[str, ...]          # () = every backend
    priority: int = 0                  # highest supported entry wins
    max_bits: int = 32
    pallas: bool = False               # real Pallas kernel (interpretable)
    autotuned: bool = False            # block size resolved by autotune
    oracle: str = ""                   # kernels/ref.py oracle it must match
    bound: str = "bit"                 # "bit" | "fp32" | "stochastic"
    description: str = ""
    op: str = "gemm"                   # "gemm" | "conv" | "attn" (universe)
    # Optional per-spec routing gate (beyond family/mode/bits), e.g.
    # nibble decomposability.  Entries with a predicate are only
    # eligible when the caller supplies a MultiplierSpec and the
    # predicate accepts it.  compare=False keeps the dataclass
    # hashable/eq on structural fields only.
    predicate: Optional[Callable[[MultiplierSpec], bool]] = dataclasses.field(
        default=None, compare=False)

    def supports(self, family: str, mode: str, bits: int,
                 backend: str) -> bool:
        return (mode in self.modes
                and (not self.families or family in self.families)
                and (not self.backends or backend in self.backends)
                and bits <= self.max_bits)


_REGISTRY: Dict[str, KernelEntry] = {}


class RoutingError(ValueError):
    """No registered kernel supports the request on this backend.  A
    subclass of ValueError for the callers that treat every rejection
    alike; the models layer tells it apart from a geometry rejection
    (`models/attention._cim_sdpa`) so it is never absorbed by a float
    fallback."""


def register_kernel(entry: KernelEntry) -> KernelEntry:
    if entry.name in _REGISTRY:
        raise ValueError(f"kernel {entry.name!r} already registered")
    _REGISTRY[entry.name] = entry
    try:
        clear_dispatch_caches()    # late registration invalidates routing
    except NameError:
        pass                       # module import: caches not built yet
    return entry


def registered_kernels() -> Tuple[KernelEntry, ...]:
    return tuple(_REGISTRY.values())


register_kernel(KernelEntry(
    name="mxu_dot", modes=("exact",), families=(), backends=(),
    oracle="float dot", bound="fp32",
    description="quantize-dequantize + MXU float dot (QAT baseline)"))
register_kernel(KernelEntry(
    name="jnp_lut", modes=("bit_exact",), families=(), backends=(),
    max_bits=MAX_LUT_BITS, oracle="lut_matmul_ref", bound="bit",
    description="pure-jnp LUT gather oracle (validation scale)"))
# Backends a Pallas entry claims are the ones its kernel is known to
# build for.  The gather kernels (full-LUT and nibble `jnp.take` over
# index tensors) are refused by the TPU compiler — Mosaic does not lower
# the gather — so they claim only the CPU, where they run in interpret
# mode as the bit-exact hardware-mode reference.  On a TPU, a hardware
# request for exact/appro42 raises at routing (`RoutingError`).
INTERPRET_ONLY = ("cpu",)

register_kernel(KernelEntry(
    name="pallas_lut_gather", modes=("hardware",),
    families=("exact", "appro42"), backends=INTERPRET_ONLY, max_bits=8,
    pallas=True, autotuned=True, oracle="lut_matmul_ref", bound="bit",
    description="Pallas k-sliced LUT-gather kernel (any LUT family)"))
register_kernel(KernelEntry(
    name="pallas_lut_nibble", modes=("hardware",),
    families=("exact", "appro42"), backends=INTERPRET_ONLY, priority=20,
    max_bits=8,
    pallas=True, autotuned=True, oracle="lut_matmul_ref", bound="bit",
    predicate=nibble_decomposable,
    description="Pallas nibble-decomposed kernel (4 x 2^{b/2} sub-LUTs; "
                "bit-exactness verified at LUT build time)"))
register_kernel(KernelEntry(
    name="pallas_log", modes=("hardware",),
    families=("mitchell", "log_our"), backends=(), priority=10,
    max_bits=16, pallas=True, autotuned=True,
    oracle="mitchell_matmul_ref", bound="bit",
    description="Pallas arithmetic log-domain kernel (LoD+shift+OR on VPU)"))
register_kernel(KernelEntry(
    name="pallas_fused_surrogate", modes=("surrogate",), families=(),
    backends=("tpu",), priority=10, max_bits=8, pallas=True,
    autotuned=True, oracle="cim_gemm_ref", bound="fp32",
    description="fused D / A^2@B^2 surrogate kernel, one HBM pass"))
register_kernel(KernelEntry(
    name="xla_surrogate", modes=("surrogate", "surrogate_fast"),
    families=(), backends=(), oracle="cim_gemm_ref", bound="stochastic",
    description="XLA dot + calibrated noise epilogue (surrogate twin)"))

# Conv universe (implicit-GEMM convolution, DESIGN.md §9).  The
# materialized im2col + GEMM path stays registered at priority 0 as the
# always-eligible fallback (and the benchmark baseline); the Pallas
# implicit kernels outrank it when the request and the VMEM footprint
# model admit them (`plan_conv`).  The implicit kernels have not been
# built by the TPU compiler yet (ROADMAP), so they claim only the CPU;
# on a TPU every conv runs im2col + the routed GEMM kernel.
register_kernel(KernelEntry(
    name="conv_im2col", op="conv", modes=MODES, families=(), backends=(),
    oracle="im2col + the routed GEMM kernel's oracle", bound="fp32",
    description="materialized-patch fallback: im2col + the GEMM engine "
                "(every mode; also the bench_conv.py baseline)"))
register_kernel(KernelEntry(
    name="pallas_conv_mxu", op="conv", modes=("exact",), families=(),
    backends=INTERPRET_ONLY, priority=10, max_bits=8, pallas=True,
    autotuned=True,
    oracle="float conv (lax.conv_general_dilated)", bound="fp32",
    description="implicit-GEMM fused-quantization conv, dequantized MXU "
                "dot per kernel tap"))
register_kernel(KernelEntry(
    name="pallas_conv_lut", op="conv", modes=("hardware",),
    families=("exact", "appro42"), backends=INTERPRET_ONLY, priority=10,
    max_bits=8,
    pallas=True, autotuned=True, oracle="im2col + lut_matmul_ref",
    bound="bit",
    description="implicit-GEMM full-LUT gather conv (k-sliced)"))
register_kernel(KernelEntry(
    name="pallas_conv_nibble", op="conv", modes=("hardware",),
    families=("exact", "appro42"), backends=INTERPRET_ONLY, priority=20,
    max_bits=8,
    pallas=True, autotuned=True, oracle="im2col + lut_matmul_ref",
    bound="bit", predicate=nibble_decomposable,
    description="implicit-GEMM nibble sub-LUT conv (4 x 2^{b/2} tables)"))
register_kernel(KernelEntry(
    name="pallas_conv_log", op="conv", modes=("hardware",),
    families=("mitchell", "log_our"), backends=INTERPRET_ONLY, priority=10,
    max_bits=16, pallas=True, autotuned=True,
    oracle="im2col + mitchell_matmul_ref", bound="bit",
    description="implicit-GEMM log-domain conv (LoD+shift+OR per tap)"))

# Attention universe (flash-style CiM attention, DESIGN.md §13).  The
# pure-jnp `attn_xla` twin stays registered at priority 0 as the
# always-eligible fallback (same tiled numerics, so still bound="bit"
# against the materialized oracle); the Pallas kernels outrank it when
# the VMEM footprint and bit-safety predicates admit them (`plan_attn`).
# The TPU compiler refuses the Pallas kernels' (1, bk) position and
# validity blocks over (B, Skv) operands (ROADMAP), so they claim only
# the CPU; on a TPU the integer attention modes run `attn_xla`.
# Modes: the quantized integer cores only — float/surrogate attention
# stays on the models-layer `_chunked_attn` path.
ATTN_MODES = ("exact", "bit_exact", "hardware")

register_kernel(KernelEntry(
    name="attn_xla", op="attn", modes=ATTN_MODES, families=(),
    backends=(), max_bits=12, oracle="attn_materialized", bound="bit",
    description="pure-jnp flash twin (same bk-tiled online softmax; "
                "fallback + validation scale)"))
register_kernel(KernelEntry(
    name="pallas_attn_mxu", op="attn", modes=("exact",), families=(),
    backends=INTERPRET_ONLY, priority=10, max_bits=8, pallas=True,
    autotuned=True,
    oracle="attn_materialized", bound="bit",
    description="flash attention, integer-valued f32 MXU dots (exact "
                "in-kernel baseline; qmax^2*K < 2^24 gated)"))
register_kernel(KernelEntry(
    name="pallas_attn_lut", op="attn", modes=("hardware",),
    families=("exact", "appro42"), backends=INTERPRET_ONLY, priority=10,
    max_bits=8,
    pallas=True, autotuned=True, oracle="attn_materialized", bound="bit",
    description="flash attention, k-sliced full-LUT gather QK^T/PV"))
register_kernel(KernelEntry(
    name="pallas_attn_nibble", op="attn", modes=("hardware",),
    families=("exact", "appro42"), backends=INTERPRET_ONLY, priority=20,
    max_bits=8,
    pallas=True, autotuned=True, oracle="attn_materialized", bound="bit",
    predicate=nibble_decomposable,
    description="flash attention, nibble sub-LUT QK^T/PV (4 x 2^{b/2} "
                "tables)"))
register_kernel(KernelEntry(
    name="pallas_attn_log", op="attn", modes=("hardware",),
    families=("mitchell", "log_our"), backends=INTERPRET_ONLY, priority=10,
    max_bits=12, pallas=True, autotuned=True,
    oracle="attn_materialized", bound="bit",
    description="flash attention, log-domain QK^T/PV (LoD+shift+OR)"))


@functools.lru_cache(maxsize=1024)
def _select_kernel_cached(family: str, mode: str, bits: int, backend: str,
                          spec: Optional[MultiplierSpec]) -> KernelEntry:
    matches = [e for e in _REGISTRY.values()
               if e.op == "gemm" and e.supports(family, mode, bits, backend)
               and (e.predicate is None
                    or (spec is not None and e.predicate(spec)))]
    if not matches:
        raise RoutingError(
            f"no kernel for family={family!r} mode={mode!r} bits={bits} "
            f"backend={backend!r}; registered: "
            f"{sorted(_REGISTRY)}")
    return max(matches, key=lambda e: e.priority)


def select_kernel(family: str, mode: str, bits: int = 8,
                  backend: Optional[str] = None,
                  spec: Optional[MultiplierSpec] = None) -> KernelEntry:
    """Route one (family, mode, bits, backend) request to a kernel.

    `spec` unlocks predicate-gated entries (the nibble kernel); without
    it routing is conservative and predicate entries are skipped.
    Memoized — steady-state routing is a dict hit, not a registry scan.
    """
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not in {FAMILIES}")
    backend = backend or jax.default_backend()
    return _select_kernel_cached(family, mode, bits, backend, spec)


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """A routed GEMM: which kernel, which block, interpret or not."""

    entry: KernelEntry
    block: Optional[Tuple[int, int, int]]
    interpret: bool
    backend: str


@functools.lru_cache(maxsize=2048)
def _plan_gemm_cached(family: str, mode: str, bits: int, mb: int, kb: int,
                      nb: int, backend: str, interpret: Optional[bool],
                      block: Optional[Tuple[int, int, int]],
                      spec: Optional[MultiplierSpec]) -> GemmPlan:
    entry = _select_kernel_cached(family, mode, bits, backend, spec)
    if interpret is None:
        # only meaningful for real Pallas kernels; XLA/jnp executors run
        # natively everywhere (the bench JSON relies on this distinction)
        interpret = entry.pallas and backend != "tpu"
    if block is None and entry.autotuned:
        block = autotune.best_block(entry.name, bits, mb, kb, nb,
                                    backend=backend)
    return GemmPlan(entry=entry, block=block, interpret=interpret,
                    backend=backend)


def plan_gemm(family: str, mode: str, bits: int, m: int, k: int, n: int,
              backend: Optional[str] = None,
              interpret: Optional[bool] = None,
              block: Optional[Tuple[int, int, int]] = None,
              spec: Optional[MultiplierSpec] = None,
              mesh: Optional[Mesh] = None, x_spec=None, w_spec=None):
    """select_kernel + autotuned block size for the concrete shape.

    Memoized on the power-of-two-bucketed shape (autotune.bucket): one
    plan serves a whole family of nearby GEMMs, and block resolution is
    bucket-invariant by construction (autotune keys the same way).

    With `mesh` (+ PartitionSpec-style `x_spec` over (M, K) rows /
    `w_spec` over (K, N)) the result is a `MeshPlan`: the inner plan is
    resolved for the *shard-local* extents (so autotuned blocks fit the
    per-device problem) and the frontends execute it under shard_map
    (DESIGN.md §11).  Only the integer modes (`MESH_MODES`) qualify.
    """
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not in {FAMILIES}")
    backend = backend or jax.default_backend()
    if mesh is None:
        return _plan_gemm_cached(family, mode, bits, autotune.bucket(m),
                                 autotune.bucket(k), autotune.bucket(n),
                                 backend, interpret, block, spec)
    _check_mesh_gemm(mode, m, k, n, mesh, x_spec, w_spec)
    dp, wk, wn, (ml, kl, nl) = _mesh_gemm_layout(m, k, n, mesh, x_spec,
                                                 w_spec)
    return _plan_gemm_mesh_cached(family, mode, bits, autotune.bucket(ml),
                                  autotune.bucket(kl), autotune.bucket(nl),
                                  backend, interpret, block, spec, mesh,
                                  dp, wk, wn)


# ---------------------------------------------------------------------------
# Conv routing: implicit-GEMM convolution plans (DESIGN.md §9)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConvParams:
    """Static conv geometry: kernel taps + stride, kh//2 zero padding
    (SAME for stride 1).  Odd kernels only — an even kernel under
    symmetric `kh//2` padding silently computes the wrong conv (the
    pre-PR-3 `_im2col` bug this class's validation retires)."""

    kh: int = 3
    kw: int = 3
    stride: int = 1

    def __post_init__(self):
        if self.kh % 2 != 1 or self.kw % 2 != 1:
            raise ValueError(
                f"even conv kernels ({self.kh}x{self.kw}) need asymmetric "
                "padding, which the symmetric kh//2 scheme cannot express")
        if self.stride < 1:
            raise ValueError(f"stride must be >= 1, got {self.stride}")


def conv_out_hw(h: int, w: int, kh: int, kw: int,
                stride: int = 1) -> Tuple[int, int]:
    """Output plane of a (kh, kw, stride) conv under kh//2 zero padding
    (SAME for stride 1).  The single home of this formula — the Pallas
    kernels (kernels/conv_gemm.py) size their grids with it too."""
    return ((h + 2 * (kh // 2) - kh) // stride + 1,
            (w + 2 * (kw // 2) - kw) // stride + 1)


def im2col_nhwc(x, conv: ConvParams):
    """(B,H,W,C) -> (B,OH,OW,kh*kw*C) materialized patch matrix
    (tap-major columns, then channel) — the HBM-resident oracle the
    implicit-GEMM kernels replace, and the `conv_im2col` fallback."""
    kh, kw, s = conv.kh, conv.kw, conv.stride
    h, w = x.shape[1], x.shape[2]
    oh, ow = conv_out_hw(h, w, kh, kw, s)
    xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2),
                     (0, 0)))
    cols = [xp[:, i:i + (oh - 1) * s + 1:s, j:j + (ow - 1) * s + 1:s]
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(cols, axis=-1)


# VMEM footprint budget for one implicit-conv grid step.  Grid input
# blocks (plane, weight tap-stack, LUT) are double-buffered by the
# Pallas pipeline; the accumulator is a single-buffered scratch; the
# bounded (M, k_slice, bn) gather/product temporary is live once.
# Shapes that exceed it fall back to the materialized im2col path
# (row-tiled halo DMA is the known follow-up).
CONV_VMEM_BUDGET = 8 * 1024 * 1024
_CONV_K_SLICE = 16                     # kernels/conv_gemm.DEFAULT_K_SLICE


def _conv_lut_vmem(entry_name: str, bits: int) -> int:
    if entry_name == "pallas_conv_lut":
        return 4 * (1 << (2 * bits))           # full signed-product table
    if entry_name == "pallas_conv_nibble":
        return 4 * 4 * (1 << bits)             # four 2^{b/2} sub-tables
    return 0


def _conv_kernel_fits(entry_name: str, bits: int,
                      block: Tuple[int, int, int], h: int, w: int,
                      conv: ConvParams) -> bool:
    bb, bc, bn = block
    oh, ow = conv_out_hw(h, w, conv.kh, conv.kw, conv.stride)
    m_blk = bb * oh * ow
    plane = bb * (h + 2 * (conv.kh // 2)) * (w + 2 * (conv.kw // 2)) * bc * 4
    wtile = conv.kh * conv.kw * bc * bn * 4
    lut = _conv_lut_vmem(entry_name, bits)
    acc = m_blk * bn * 4
    temp = m_blk * _CONV_K_SLICE * bn * 4
    return 2 * (plane + wtile + lut) + acc + temp <= CONV_VMEM_BUDGET


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """A routed conv: which kernel, geometry, block, interpret or not."""

    entry: KernelEntry
    conv: ConvParams
    block: Optional[Tuple[int, int, int]]
    interpret: bool
    backend: str


@functools.lru_cache(maxsize=1024)
def _conv_entries_cached(family: str, mode: str, bits: int, backend: str,
                         spec: Optional[MultiplierSpec]
                         ) -> Tuple[KernelEntry, ...]:
    matches = [e for e in _REGISTRY.values()
               if e.op == "conv" and e.supports(family, mode, bits, backend)
               and (e.predicate is None
                    or (spec is not None and e.predicate(spec)))]
    if not matches:
        raise RoutingError(
            f"no conv kernel for family={family!r} mode={mode!r} "
            f"bits={bits} backend={backend!r}; registered: "
            f"{sorted(e.name for e in _REGISTRY.values() if e.op == 'conv')}")
    return tuple(sorted(matches, key=lambda e: -e.priority))


def select_conv_kernel(family: str, mode: str, bits: int = 8,
                       backend: Optional[str] = None,
                       spec: Optional[MultiplierSpec] = None) -> KernelEntry:
    """Highest-priority conv entry for the request (no footprint gate —
    `plan_conv` applies that against the concrete plane)."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not in {FAMILIES}")
    backend = backend or jax.default_backend()
    return _conv_entries_cached(family, mode, bits, backend, spec)[0]


def _conv_bit_exact_safe(h: int, w: int, conv: ConvParams) -> bool:
    """True iff the implicit kernels are bit-identical to the im2col
    oracle at this geometry.  The implicit path quantizes with
    quant_scale(x), the oracle with quant_scale(im2col(x)); the
    max-based scales agree iff every input pixel reaches >= 1 patch:
    stride <= min(kh, kw) keeps tap coverage contiguous, and the
    sampling residue (Hp - kh) % stride must not exceed the padding —
    otherwise trailing real rows/cols are never sampled.  Computed on
    the *actual* dims (bucketing would mask the residue)."""
    s = conv.stride
    if s > min(conv.kh, conv.kw):
        return False
    return ((h + 2 * (conv.kh // 2) - conv.kh) % s <= conv.kh // 2
            and (w + 2 * (conv.kw // 2) - conv.kw) % s <= conv.kw // 2)


@functools.lru_cache(maxsize=1024)
def _plan_conv_cached(family: str, mode: str, bits: int, bb: int, hb: int,
                      wb: int, cb: int, nb: int, conv: ConvParams,
                      bit_safe: bool, backend: str,
                      interpret: Optional[bool],
                      block: Optional[Tuple[int, int, int]],
                      spec: Optional[MultiplierSpec]) -> ConvPlan:
    for entry in _conv_entries_cached(family, mode, bits, backend, spec):
        if entry.bound == "bit" and not bit_safe:
            continue
        blk = None
        if entry.pallas:
            blk = block
            if blk is None and entry.autotuned:
                blk = autotune.best_conv_block(
                    entry.name, bits, bb, hb, wb, cb, nb, conv.kh,
                    conv.kw, conv.stride, backend=backend)
                if not _conv_kernel_fits(entry.name, bits, blk, hb, wb,
                                         conv):
                    continue           # plane too large: try lower priority
        interp = interpret
        if interp is None:
            interp = entry.pallas and backend != "tpu"
        return ConvPlan(entry=entry, conv=conv, block=blk,
                        interpret=interp, backend=backend)
    raise ValueError(                  # conv_im2col always matches
        f"no eligible conv kernel for family={family!r} mode={mode!r}")


def plan_conv(family: str, mode: str, bits: int, b: int, h: int, w: int,
              c: int, n: int, conv: ConvParams,
              backend: Optional[str] = None,
              interpret: Optional[bool] = None,
              block: Optional[Tuple[int, int, int]] = None,
              spec: Optional[MultiplierSpec] = None,
              mesh: Optional[Mesh] = None, x_spec=None, w_spec=None):
    """Route one conv to an entry + autotuned (bb, bc, bn) block.

    Memoized on the conv-bucketed shape (autotune.bucket_conv): powers
    of two on the data dims, kernel taps and stride exact — plus the
    geometry's exact bit-safety flag (`_conv_bit_exact_safe`, which
    bucketing would mask).  Entries declaring a "bit" bound are skipped
    when the flag is False (the materialized fallback IS the oracle, so
    the declared bound is honored by construction), and Pallas entries
    are additionally gated on the VMEM footprint model
    (`_conv_kernel_fits`); oversize planes fall back to `conv_im2col`.

    With `mesh`, `x_spec` shards the batch dim of (B, H, W, C) and
    `w_spec` is the (K, N)-style pair over the (kh*kw*C, N) weight —
    P("model", None) = input-channel (contraction) sharding with psum,
    P(None, "model") = out-channel sharding, no collective.  Returns a
    `MeshPlan` over the shard-local geometry (DESIGN.md §11); only the
    integer modes and bit-safe geometries qualify (a non-bit-safe
    geometry's per-tensor scale depends on the materialized patch
    matrix, which no shard can see whole).
    """
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not in {FAMILIES}")
    backend = backend or jax.default_backend()
    if mesh is None:
        bb, hb, wb, cb, _, _, _ = autotune.bucket_conv(b, h, w, c, conv.kh,
                                                       conv.kw, conv.stride)
        return _plan_conv_cached(family, mode, bits, bb, hb, wb, cb,
                                 autotune.bucket(n), conv,
                                 _conv_bit_exact_safe(h, w, conv), backend,
                                 interpret, block, spec)
    _check_mesh_conv(mode, h, w, conv, b, c, n, mesh, x_spec, w_spec)
    dp, wk, wn, _ = _mesh_gemm_layout(b, c, n, mesh, P(_one_spec(x_spec)),
                                      w_spec)
    return _plan_conv_mesh_cached(family, mode, bits, b, h, w, c, n, conv,
                                  backend, interpret, block, spec, mesh,
                                  dp, wk, wn)


@functools.lru_cache(maxsize=64)
def _fault_conv_plan(conv: ConvParams, backend: str) -> ConvPlan:
    """The forced materialized-fallback plan for as-fabricated convs
    (`cim_conv2d` with a fault config): `conv_im2col` is always
    registered and always eligible, and its inner GEMM re-routes
    through the faultable integer paths."""
    return ConvPlan(entry=_REGISTRY["conv_im2col"], conv=conv,
                    block=None, interpret=False, backend=backend)


def _one_spec(x_spec):
    """First entry of a conv x_spec (the batch dim); rest must be
    unsharded — H/W tiling needs halo exchange (known follow-up)."""
    if x_spec is None:
        return None
    xs = tuple(x_spec)
    if any(e is not None for e in xs[1:]):
        raise ValueError(
            f"mesh conv shards batch (and C via w_spec) only; got {xs}")
    return xs[0] if xs else None


@functools.lru_cache(maxsize=512)
def _plan_conv_mesh_cached(family: str, mode: str, bits: int, b: int,
                           h: int, w: int, c: int, n: int,
                           conv: ConvParams, backend: str,
                           interpret: Optional[bool],
                           block: Optional[Tuple[int, int, int]],
                           spec: Optional[MultiplierSpec], mesh: Mesh,
                           dp: Tuple[str, ...], wk: Tuple[str, ...],
                           wn: Tuple[str, ...]) -> MeshPlan:
    bl = b // _axes_size(mesh, dp)
    cl = c // _axes_size(mesh, wk)
    nl = n // _axes_size(mesh, wn)
    bb, hb, wb, cb, _, _, _ = autotune.bucket_conv(bl, h, w, cl, conv.kh,
                                                   conv.kw, conv.stride)
    inner = _plan_conv_cached(family, mode, bits, bb, hb, wb, cb,
                              autotune.bucket(nl), conv, True, backend,
                              interpret, block, spec)
    x_spec = P(_spec_entry(dp), None, None, _spec_entry(wk))
    w3_spec = P(None, _spec_entry(wk), _spec_entry(wn))
    sw_spec = P(None, _spec_entry(wn))
    out_spec = P(_spec_entry(dp), None, None, _spec_entry(wn))
    return MeshPlan(plan=inner, mesh=mesh,
                    in_specs=(x_spec, w3_spec, P(), sw_spec),
                    out_spec=out_spec, reduce_axes=wk,
                    local_shape=(bl, h, w, cl, nl))


# ---------------------------------------------------------------------------
# Attention planning universe (flash-style CiM attention, DESIGN.md §13)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnParams:
    """Static attention geometry: the masking contract.

    ``causal`` gates ``kpos <= qpos``; ``window`` additionally gates
    ``kpos > qpos - window`` (sliding-window attention).  Ragged
    validity rides in the runtime ``kv_valid`` operand, not here — it
    changes per call, never the executable."""

    causal: bool = True
    window: Optional[int] = None

    def __post_init__(self):
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")


# Entry name -> inner-dot datapath of kernels/attn_gemm.py.  attn_xla
# resolves per-request (_attn_path): it mirrors whichever datapath the
# request's mode/family would run, so falling back never changes the
# multiplier semantics, only the execution engine.
_ATTN_PATHS = {
    "pallas_attn_mxu": "mxu",
    "pallas_attn_lut": "lut",
    "pallas_attn_nibble": "nibble",
    "pallas_attn_log": "log",
}


def _attn_path(entry_name: str, family: str, mode: str) -> str:
    path = _ATTN_PATHS.get(entry_name)
    if path is not None:
        return path
    if mode == "exact":
        return "mxu"
    if family in ("mitchell", "log_our"):
        return "log"
    return "lut"


# VMEM footprint budget for one flash-attention grid step: the q/k/v
# operand tiles (+ table) are double-buffered by the Pallas pipeline,
# the m/l/acc scratch is single-buffered, the (bq, bk) score tile and
# its mask/probability twins are live once, and the gather/product
# paths materialize a bounded (bq, k_slice, max(bk, dp)) temporary.
ATTN_VMEM_BUDGET = 8 * 1024 * 1024
_ATTN_K_SLICE = 16                     # kernels/approx_matmul.DEFAULT_K_SLICE


def _attn_lut_vmem(entry_name: str, bits: int) -> int:
    if entry_name == "pallas_attn_lut":
        return 4 * (1 << (2 * bits))           # full signed-product table
    if entry_name == "pallas_attn_nibble":
        return 4 * 4 * (1 << bits)             # four 2^{b/2} sub-tables
    return 0


def _attn_kernel_fits(entry_name: str, bits: int, block: Tuple[int, int],
                      head_dim: int) -> bool:
    bq, bk = block
    dp = max(128, -(-head_dim // 128) * 128)   # lane-padded head dim
    operands = (bq + 2 * bk) * dp * 4 + _attn_lut_vmem(entry_name, bits)
    scratch = bq * dp * 4 + 2 * bq * 128 * 4
    score = 3 * bq * bk * 4                    # s, mask-widened p, pq
    temp = 2 * bq * _ATTN_K_SLICE * max(bk, dp) * 4
    return 2 * operands + scratch + score + temp <= ATTN_VMEM_BUDGET


def _attn_bit_safe(bits: int, path: str, head_dim: int, bk: int) -> bool:
    """True iff every inner-dot partial sum is exactly representable.

    QK^T contracts the lane-padded head dim, PV contracts the kv tile
    (probabilities quantize to [0, qmax] at fixed scale), so the worst
    accumulator magnitude is qmax^2 * max(dp, bk).  The MXU path sums
    in f32 (exact below 2^24); the integer paths accumulate int32."""
    qm = (1 << (bits - 1)) - 1
    dp = max(128, -(-head_dim // 128) * 128)
    worst = qm * qm * max(dp, bk)
    return worst < ((1 << 24) if path == "mxu" else (1 << 31))


@dataclasses.dataclass(frozen=True)
class AttnPlan:
    """A routed attention: kernel, masking, (bq, bk) block, backend."""

    entry: KernelEntry
    attn: AttnParams
    block: Tuple[int, int]
    interpret: bool
    backend: str


@functools.lru_cache(maxsize=1024)
def _attn_entries_cached(family: str, mode: str, bits: int, backend: str,
                         spec: Optional[MultiplierSpec]
                         ) -> Tuple[KernelEntry, ...]:
    matches = [e for e in _REGISTRY.values()
               if e.op == "attn" and e.supports(family, mode, bits, backend)
               and (e.predicate is None
                    or (spec is not None and e.predicate(spec)))]
    if not matches:
        raise RoutingError(
            f"no attention kernel for family={family!r} mode={mode!r} "
            f"bits={bits} backend={backend!r}; registered: "
            f"{sorted(e.name for e in _REGISTRY.values() if e.op == 'attn')}")
    return tuple(sorted(matches, key=lambda e: -e.priority))


def select_attn_kernel(family: str, mode: str, bits: int = 8,
                       backend: Optional[str] = None,
                       spec: Optional[MultiplierSpec] = None) -> KernelEntry:
    """Highest-priority attention entry for the request (no footprint /
    bit-safety gate — `plan_attn` applies those against the geometry)."""
    if mode not in ATTN_MODES:
        raise ValueError(f"mode {mode!r} not in {ATTN_MODES}")
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not in {FAMILIES}")
    backend = backend or jax.default_backend()
    return _attn_entries_cached(family, mode, bits, backend, spec)[0]


@functools.lru_cache(maxsize=1024)
def _plan_attn_cached(family: str, mode: str, bits: int, bb: int,
                      heads: int, kv_heads: int, sqb: int, skvb: int,
                      head_dim: int, attn: AttnParams, backend: str,
                      interpret: Optional[bool],
                      block: Optional[Tuple[int, int]],
                      spec: Optional[MultiplierSpec]) -> AttnPlan:
    for entry in _attn_entries_cached(family, mode, bits, backend, spec):
        path = _attn_path(entry.name, family, mode)
        blk = block
        if blk is None:
            if entry.autotuned:
                blk = autotune.best_attn_block(
                    entry.name, bits, bb, heads, kv_heads, sqb, skvb,
                    head_dim, backend=backend)
            else:
                blk = autotune.heuristic_attn_block(entry.name, sqb, skvb)
        if entry.pallas and not _attn_kernel_fits(entry.name, bits, blk,
                                                  head_dim):
            continue                   # tile too large: try lower priority
        if not _attn_bit_safe(bits, path, head_dim, blk[1]):
            continue                   # accumulator could overflow
        interp = interpret
        if interp is None:
            interp = entry.pallas and backend != "tpu"
        return AttnPlan(entry=entry, attn=attn, block=tuple(blk),
                        interpret=interp, backend=backend)
    raise ValueError(
        f"no eligible attention kernel for family={family!r} "
        f"mode={mode!r} bits={bits} head_dim={head_dim} (bit-safety / "
        "VMEM predicates rejected every entry)")


def plan_attn(family: str, mode: str, bits: int, b: int, heads: int,
              kv_heads: int, sq: int, skv: int, head_dim: int,
              attn: AttnParams = AttnParams(),
              backend: Optional[str] = None,
              interpret: Optional[bool] = None,
              block: Optional[Tuple[int, int]] = None,
              spec: Optional[MultiplierSpec] = None) -> AttnPlan:
    """Route one attention call to an entry + (bq, bk) block.

    Memoized on the attention-bucketed shape (autotune.bucket_attn):
    powers of two on batch and the sequence axes; heads, kv_heads and
    head_dim exact.  Entries are gated by the VMEM footprint model
    (`_attn_kernel_fits`) and the accumulator bit-safety predicate
    (`_attn_bit_safe`); a request no entry accepts raises, and the
    models layer falls back to the float `_chunked_attn` path.
    """
    if mode not in ATTN_MODES:
        raise ValueError(f"mode {mode!r} not in {ATTN_MODES}")
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not in {FAMILIES}")
    if heads % kv_heads:
        raise ValueError(
            f"GQA needs heads % kv_heads == 0, got {heads} % {kv_heads}")
    backend = backend or jax.default_backend()
    bb, hh, kh, sqb, skvb, hd = autotune.bucket_attn(
        b, heads, kv_heads, sq, skv, head_dim)
    return _plan_attn_cached(family, mode, bits, bb, hh, kh, sqb, skvb,
                             hd, attn, backend, interpret,
                             tuple(block) if block is not None else None,
                             spec)


# ---------------------------------------------------------------------------
# Mesh-partitioned planning (DESIGN.md §11)
# ---------------------------------------------------------------------------

# Modes the mesh path supports.  They are exactly the integer-core modes:
# per-shard int32 accumulators psum bit-exactly, so the sharded result is
# bit-identical to the single-device oracle.  Float modes (exact MXU dot,
# surrogates) would reassociate float partial sums across shards — those
# keep the GSPMD constraint path (models/common.wsc).
MESH_MODES = ("bit_exact", "hardware")


def _norm_axes(entry) -> Tuple[str, ...]:
    """One PartitionSpec entry -> tuple of mesh axis names."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod([mesh.shape[a] for a in axes]) if axes else 1


def _spec_entry(axes: Tuple[str, ...]):
    return None if not axes else (axes[0] if len(axes) == 1 else axes)


def _canon_spec(spec) -> Optional[Tuple]:
    """Hashable canonical form of a user-supplied PartitionSpec/tuple
    (front-cache key component)."""
    return None if spec is None else tuple(spec)


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A mesh-partitioned GEMM/conv: shard-local inner plan + placement.

    `in_specs` are the shard_map specs for (x, w, sx, sw) — for convs, w
    is the rank-3 (kh*kw, C, N) tap-stack form so a C row-shard is a
    plain dimension shard.  `reduce_axes` names the mesh axes the int32
    partial accumulator psums over (empty for the output-sharded
    layout).  `local_shape` carries the conv shard-local (b, h, w, c, n)
    for the materialized-fallback inner-GEMM resolution.
    """

    plan: Union[GemmPlan, ConvPlan]
    mesh: Mesh
    in_specs: Tuple
    out_spec: P
    reduce_axes: Tuple[str, ...]
    local_shape: Optional[Tuple[int, ...]] = None

    @property
    def entry(self) -> KernelEntry:
        return self.plan.entry


def _plan_token(plan) -> Tuple:
    """Hashable routing identity of a plan, for executable-cache keys."""
    if isinstance(plan, MeshPlan):
        return (_plan_token(plan.plan) + ("mesh",)
                + (tuple(sorted(plan.mesh.shape.items())), plan.mesh,
                   plan.in_specs, plan.out_spec, plan.reduce_axes))
    return (plan.entry.name, getattr(plan, "conv", None),
            getattr(plan, "attn", None), plan.block, plan.interpret,
            plan.backend)


def _mesh_gemm_layout(m: int, k: int, n: int, mesh: Mesh, x_spec, w_spec):
    """Validate + canonicalize a GEMM mesh request.

    Returns (dp, wk, wn) axis tuples and the shard-local (m, k, n).
    `w_spec` must shard exactly one of {K (contraction, psum layout),
    N (output columns, collective-free layout)}; `x_spec` may shard the
    flattened row dim on the batch axes (rides along either layout).

    Runs on the RAW shape, never a bucketed one — the frontends call it
    on every mesh request, including front-cache hits, because two
    shapes in one bucket can differ in divisibility (m=32 divides a
    2-way axis, m=31 in the same bucket does not).
    """
    w_spec = P(*w_spec) if w_spec is not None else P(None, None)
    x_spec = P(*x_spec) if x_spec is not None else P(None, None)
    dp = _norm_axes(x_spec[0] if len(x_spec) > 0 else None)
    wk = _norm_axes(w_spec[0] if len(w_spec) > 0 else None)
    wn = _norm_axes(w_spec[1] if len(w_spec) > 1 else None)
    if wk and wn:
        raise ValueError(
            f"mesh GEMM: w sharded on both K ({wk}) and N ({wn}); pick "
            "one tensor-parallel layout")
    for ax in (*dp, *wk, *wn):
        if ax not in mesh.shape:
            raise ValueError(f"axis {ax!r} not in mesh {dict(mesh.shape)}")
    if set(dp) & (set(wk) | set(wn)):
        raise ValueError(f"row axes {dp} collide with weight axes")
    for what, dim, axes in (("M", m, dp), ("K", k, wk), ("N", n, wn)):
        size = _axes_size(mesh, axes)
        if dim % size:
            raise ValueError(
                f"mesh GEMM: {what}={dim} not divisible by axes "
                f"{axes} (size {size})")
    return dp, wk, wn, (m // _axes_size(mesh, dp),
                        k // _axes_size(mesh, wk),
                        n // _axes_size(mesh, wn))


def _check_mesh_gemm(mode: str, m: int, k: int, n: int, mesh: Mesh,
                     x_spec, w_spec) -> None:
    """Exact-shape validation of one mesh GEMM request: mode + layout +
    divisibility.  The frontends run this BEFORE consulting the
    bucketed front cache — a warm entry must never serve a shape the
    planner would have rejected."""
    if mode not in MESH_MODES:
        raise ValueError(
            f"mesh execution supports the integer modes {MESH_MODES}; "
            f"mode {mode!r} keeps the GSPMD constraint path")
    _mesh_gemm_layout(m, k, n, mesh, x_spec, w_spec)


def _check_mesh_conv(mode: str, h: int, w: int, conv: "ConvParams",
                     b: int, c: int, n: int, mesh: Mesh, x_spec,
                     w_spec) -> None:
    """Exact-geometry validation of one mesh conv request (mode,
    bit-safety — which bucketing would mask — layout, divisibility);
    run on every call for the same reason as `_check_mesh_gemm`."""
    if mode not in MESH_MODES:
        raise ValueError(
            f"mesh execution supports the integer modes {MESH_MODES}; "
            f"mode {mode!r} keeps the GSPMD constraint path")
    if not _conv_bit_exact_safe(h, w, conv):
        raise ValueError(
            f"mesh conv: geometry (h={h}, w={w}, {conv.kh}x{conv.kw} "
            f"s{conv.stride}) is not bit-safe — the oracle's scale needs "
            "the whole materialized patch matrix; run unsharded")
    _mesh_gemm_layout(b, c, n, mesh, P(_one_spec(x_spec)), w_spec)


@functools.lru_cache(maxsize=512)
def _plan_gemm_mesh_cached(family: str, mode: str, bits: int, mbl: int,
                           kbl: int, nbl: int, backend: str,
                           interpret: Optional[bool],
                           block: Optional[Tuple[int, int, int]],
                           spec: Optional[MultiplierSpec], mesh: Mesh,
                           dp: Tuple[str, ...], wk: Tuple[str, ...],
                           wn: Tuple[str, ...]) -> MeshPlan:
    inner = _plan_gemm_cached(family, mode, bits, mbl, kbl, nbl, backend,
                              interpret, block, spec)
    if inner.entry.name not in PARTIAL_RUNNERS:
        raise ValueError(
            f"kernel {inner.entry.name!r} has no shard-local (partial) "
            f"runner; mesh execution supports {sorted(PARTIAL_RUNNERS)}")
    x_spec = P(_spec_entry(dp), _spec_entry(wk))
    w_spec = P(_spec_entry(wk), _spec_entry(wn))
    sw_spec = P(None, _spec_entry(wn))
    out_spec = P(_spec_entry(dp), _spec_entry(wn))
    return MeshPlan(plan=inner, mesh=mesh,
                    in_specs=(x_spec, w_spec, P(), sw_spec),
                    out_spec=out_spec, reduce_axes=wk)


# ---------------------------------------------------------------------------
# Static GEMM parameters (shared by both frontends)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GemmParams:
    """Trace-time description of one approximate GEMM."""

    family: str = "exact"
    bits: int = 8
    mode: str = "surrogate"
    mu: float = 0.0                    # calibrated relative bias
    c0: float = 0.0                    # variance floor (int^2 units)
    c1: float = 0.0                    # variance slope on p^2
    compressor: str = "yang1"
    n_approx_cols: Optional[int] = None
    # per-row (per-token) activation scales instead of the macro's
    # per-tensor scale: each activation row quantizes against its own
    # max, so a row's result is a pure function of that row — the
    # M-invariance the speculative-decoding verify pass needs (a
    # (B, K) batched verify must agree bitwise with K sequential
    # single-token steps).  Integer/fake-quant XLA paths only: the
    # fused Pallas runners and the mesh shard_map route carry the
    # scalar per-tensor scale in SMEM and are gated off.
    per_token: bool = False
    # as-fabricated stuck-at defects (core/faults.py, DESIGN.md §14):
    # faults the stored LUT tables and the quantized weight words of the
    # integer datapaths.  Part of the frozen params, so every executable
    # / front-cache key (they all embed `gp`) distinguishes faulted from
    # clean executables — flipping a lane between the two never
    # retraces.  Integer/exact modes only; fused Pallas runners and the
    # mesh path quantize in-kernel from float and are gated off.
    fault: Optional[FaultConfig] = None

    def __post_init__(self):
        if self.fault is not None and self.mode not in FAULT_MODES:
            raise ValueError(
                f"fault injection needs an integer storage domain "
                f"(modes {FAULT_MODES}); mode {self.mode!r} stores no "
                "words or tables to fault")

    @property
    def spec(self) -> MultiplierSpec:
        return MultiplierSpec(self.family, self.bits, True,
                              self.compressor, self.n_approx_cols)

    @property
    def routing_spec(self) -> Optional[MultiplierSpec]:
        """The spec the planners should route with.  Under fault it is
        None: predicate-gated entries (the nibble GEMM/conv kernels)
        resolve their clean sub-LUTs inside `kernels/ops.py` and cannot
        see the defect map, so routing falls to the full-LUT gather —
        whose table operand IS faultable (`_lut_for`)."""
        return None if self.fault is not None else self.spec

    @classmethod
    def from_spec(cls, spec: MultiplierSpec, surrogate: SurrogateModel,
                  mode: str,
                  fault: Optional[FaultConfig] = None) -> "GemmParams":
        return cls(family=spec.family, bits=spec.bits, mode=mode,
                   mu=surrogate.mu_rel, c0=surrogate.c0_abs,
                   c1=surrogate.c1_rel, compressor=spec.compressor,
                   n_approx_cols=spec.n_approx_cols, fault=fault)


# ---------------------------------------------------------------------------
# Integer-domain kernel runners (the registry-oracle surface)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _signed_lut_flat(spec_key):
    # cache the NUMPY table, never a jnp array: a jnp constant created
    # while tracing (e.g. first touch inside a scanned layer) is a
    # tracer, and caching it leaks it out of the trace.  jnp.asarray at
    # use time is free under jit (constants are deduped by XLA).
    family, bits, compressor, n_approx = spec_key
    spec = MultiplierSpec(family, bits, True, compressor, n_approx)
    return signed_product_lut(spec).ravel()


def _lut_for(gp: GemmParams) -> jnp.ndarray:
    spec_key = (gp.family, gp.bits, gp.compressor, gp.n_approx_cols)
    if gp.fault is not None:
        return jnp.asarray(
            faults.faulted_signed_lut_flat(spec_key, gp.fault))
    return jnp.asarray(_signed_lut_flat(spec_key))


def _run_jnp_lut(xq, wq, gp: GemmParams, plan: GemmPlan):
    """Bit-exact signed LUT GEMM (pure jnp oracle; O(M*K*N) gathers)."""
    half = 1 << (gp.bits - 1)
    n = 1 << gp.bits
    ia = (xq.astype(jnp.int32) + half)[..., :, :, None]    # (M, K, 1)
    ib = (wq.astype(jnp.int32) + half)[None, :, :]         # (1, K, N)
    idx = ia * n + ib                                      # (M, K, N)
    prods = jnp.take(_lut_for(gp), idx, axis=0)
    return prods.sum(axis=-2)                              # (M, N)


def _run_pallas_lut(xq, wq, gp: GemmParams, plan: GemmPlan):
    from repro.kernels.approx_matmul import lut_matmul

    return lut_matmul(xq, wq, _lut_for(gp), bits=gp.bits,
                      block=plan.block, interpret=plan.interpret)


def _run_pallas_nibble(xq, wq, gp: GemmParams, plan: GemmPlan):
    from repro.kernels import ops

    return ops.nibble_matmul_bit_exact(xq, wq, gp.spec, block=plan.block,
                                       interpret=plan.interpret)


def _run_pallas_log(xq, wq, gp: GemmParams, plan: GemmPlan):
    from repro.kernels.mitchell_gemm import mitchell_matmul

    return mitchell_matmul(xq, wq, bits=gp.bits,
                           compensated=(gp.family == "log_our"),
                           block=plan.block, interpret=plan.interpret)


# entry name -> int8 (M,K) x int8 (K,N) -> int32 (M,N)
INT_RUNNERS: Dict[str, Callable] = {
    "jnp_lut": _run_jnp_lut,
    "pallas_lut_gather": _run_pallas_lut,
    "pallas_lut_nibble": _run_pallas_nibble,
    "pallas_log": _run_pallas_log,
}


def run_int_kernel(plan: GemmPlan, xq, wq, gp: GemmParams):
    """Execute the integer core of a routed bit_exact/hardware GEMM."""
    try:
        runner = INT_RUNNERS[plan.entry.name]
    except KeyError:
        raise ValueError(
            f"kernel {plan.entry.name!r} has no integer runner") from None
    return runner(xq, wq, gp, plan)


# ---------------------------------------------------------------------------
# Fused-quantization runners (f32 in -> f32 out, one pallas_call)
# ---------------------------------------------------------------------------


def _run_fused_lut(xf, wf, gp: GemmParams, plan: GemmPlan):
    from repro.kernels import ops

    return ops.approx_matmul_fused(xf, wf, gp.spec, block=plan.block,
                                   interpret=plan.interpret)


def _run_fused_nibble(xf, wf, gp: GemmParams, plan: GemmPlan):
    from repro.kernels import ops

    return ops.nibble_matmul_fused(xf, wf, gp.spec, block=plan.block,
                                   interpret=plan.interpret)


def _run_fused_log(xf, wf, gp: GemmParams, plan: GemmPlan):
    from repro.kernels import ops

    return ops.log_matmul_fused(xf, wf, bits=gp.bits,
                                compensated=(gp.family == "log_our"),
                                block=plan.block, interpret=plan.interpret)


# entry name -> f32 (M,K) x f32 (K,N) -> f32 (M,N); quantization and the
# (acc * sx) * sw epilogue run inside the kernel (DESIGN.md §8)
FUSED_RUNNERS: Dict[str, Callable] = {
    "pallas_lut_gather": _run_fused_lut,
    "pallas_lut_nibble": _run_fused_nibble,
    "pallas_log": _run_fused_log,
}


# ---------------------------------------------------------------------------
# Implicit-GEMM conv runners (f32 in -> f32 out, one pallas_call; §9)
# ---------------------------------------------------------------------------


def _run_conv_mxu(x4, w2, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_mxu_fused(x4, w2, bits=gp.bits, kh=plan.conv.kh,
                                kw=plan.conv.kw, stride=plan.conv.stride,
                                block=plan.block, interpret=plan.interpret)


def _run_conv_lut(x4, w2, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_lut_fused(x4, w2, gp.spec, kh=plan.conv.kh,
                                kw=plan.conv.kw, stride=plan.conv.stride,
                                block=plan.block, interpret=plan.interpret)


def _run_conv_nibble(x4, w2, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_nibble_fused(x4, w2, gp.spec, kh=plan.conv.kh,
                                   kw=plan.conv.kw, stride=plan.conv.stride,
                                   block=plan.block,
                                   interpret=plan.interpret)


def _run_conv_log(x4, w2, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_log_fused(x4, w2, bits=gp.bits,
                                compensated=(gp.family == "log_our"),
                                kh=plan.conv.kh, kw=plan.conv.kw,
                                stride=plan.conv.stride, block=plan.block,
                                interpret=plan.interpret)


# entry name -> f32 (B,H,W,C) x f32 (kh*kw*C,N) -> f32 (B,OH,OW,N); the
# patch gather, quantization and dequant epilogue all run inside one
# pallas_call — no im2col tensor ever touches HBM (DESIGN.md §9)
CONV_RUNNERS: Dict[str, Callable] = {
    "pallas_conv_mxu": _run_conv_mxu,
    "pallas_conv_lut": _run_conv_lut,
    "pallas_conv_nibble": _run_conv_nibble,
    "pallas_conv_log": _run_conv_log,
}


# ---------------------------------------------------------------------------
# Attention runners (DESIGN.md §13).  Kernel-native layout: q
# (B, H, Sq, D), k/v (B, KH, Skv, D) float, qpos (B, Sq) + kpos/kval
# (B, Skv) int32 -> f32 (B, H, Sq, D).  Tables/scales resolve inside
# ops.* so the runners stay pure functions of (operands, gp, plan).
# ---------------------------------------------------------------------------


def _attn_run_kwargs(gp: GemmParams, plan: AttnPlan) -> Dict:
    path = _attn_path(plan.entry.name, gp.family, gp.mode)
    kw = dict(path=path, bits=gp.bits, causal=plan.attn.causal,
              window=plan.attn.window,
              compensated=(gp.family == "log_our"), block=plan.block)
    if path in ("lut", "nibble"):
        kw["spec"] = gp.spec
    return kw


def _run_attn_pallas(qh, kh_, vh, qpos, kpos, kval, gp: GemmParams,
                     plan: AttnPlan):
    from repro.kernels import ops

    return ops.cim_attn_fused(qh, kh_, vh, qpos, kpos, kval,
                              interpret=plan.interpret,
                              **_attn_run_kwargs(gp, plan))


def _run_attn_xla(qh, kh_, vh, qpos, kpos, kval, gp: GemmParams,
                  plan: AttnPlan):
    from repro.kernels import ops

    return ops.cim_attn_reference(qh, kh_, vh, qpos, kpos, kval,
                                  **_attn_run_kwargs(gp, plan))


ATTN_RUNNERS: Dict[str, Callable] = {
    "attn_xla": _run_attn_xla,
    "pallas_attn_mxu": _run_attn_pallas,
    "pallas_attn_lut": _run_attn_pallas,
    "pallas_attn_nibble": _run_attn_pallas,
    "pallas_attn_log": _run_attn_pallas,
}


def attn_materialized_oracle(q, k, v, gp: GemmParams, plan: AttnPlan,
                             qpos, kpos, kval):
    """The bit-exact oracle surface for a routed attention: identical
    math to the fused kernel, with the full (B, H, Sq, Skv) score
    tensor materialized through HBM (tests + bench_attn baseline)."""
    from repro.kernels import ops

    # a non-Pallas plan (attn_xla) carries interpret=False, which only
    # applies to its jnp twin; the oracle's pallas_calls resolve their
    # own default (interpret off-TPU)
    interp = plan.interpret if plan.entry.pallas else None
    return ops.cim_attn_materialized(q, k, v, qpos, kpos, kval,
                                     interpret=interp,
                                     **_attn_run_kwargs(gp, plan))


# ---------------------------------------------------------------------------
# Shard-local (partial) runners — one per-device kernel inside shard_map
# (DESIGN.md §11).  f32 shard operands + the GLOBAL quantization scales
# in, raw int32 partial accumulator out; the caller psums over the model
# axis and applies the (acc * sx) * sw epilogue after the collective.
# ---------------------------------------------------------------------------


def _partial_jnp_lut(xb, wb, sx, sw, gp: GemmParams, plan):
    xq = quantize(xb, sx, gp.bits)
    wq = quantize(wb, sw, gp.bits)
    return _run_jnp_lut(xq, wq, gp, plan)


def _partial_lut(xb, wb, sx, sw, gp: GemmParams, plan):
    from repro.kernels import ops

    return ops.lut_partial_acc(xb, wb, gp.spec, sx, sw, block=plan.block,
                               interpret=plan.interpret)


def _partial_nibble(xb, wb, sx, sw, gp: GemmParams, plan):
    from repro.kernels import ops

    return ops.nibble_partial_acc(xb, wb, gp.spec, sx, sw,
                                  block=plan.block,
                                  interpret=plan.interpret)


def _partial_log(xb, wb, sx, sw, gp: GemmParams, plan):
    from repro.kernels import ops

    return ops.log_partial_acc(xb, wb, sx, sw, bits=gp.bits,
                               compensated=(gp.family == "log_our"),
                               block=plan.block, interpret=plan.interpret)


# entry name -> shard-local f32 (M, K_shard) x (K_shard, N) -> int32 (M, N)
PARTIAL_RUNNERS: Dict[str, Callable] = {
    "jnp_lut": _partial_jnp_lut,
    "pallas_lut_gather": _partial_lut,
    "pallas_lut_nibble": _partial_nibble,
    "pallas_log": _partial_log,
}


def _scaled_lut(xb, wb, sx, sw, gp: GemmParams, plan):
    from repro.kernels import ops

    return ops.lut_fused_scaled(xb, wb, gp.spec, sx, sw, block=plan.block,
                                interpret=plan.interpret)


def _scaled_nibble(xb, wb, sx, sw, gp: GemmParams, plan):
    from repro.kernels import ops

    return ops.nibble_fused_scaled(xb, wb, gp.spec, sx, sw,
                                   block=plan.block,
                                   interpret=plan.interpret)


def _scaled_log(xb, wb, sx, sw, gp: GemmParams, plan):
    from repro.kernels import ops

    return ops.log_fused_scaled(xb, wb, sx, sw, bits=gp.bits,
                                compensated=(gp.family == "log_our"),
                                block=plan.block, interpret=plan.interpret)


# Output-sharded layout (no psum between quantize and dequant): the
# epilogue runs INSIDE the kernel — one HBM pass per shard, no int32
# accumulator round trip.  Same float ops as partial + jnp epilogue,
# so bit-identity is unchanged.  jnp_lut has no fused form and keeps
# the partial + explicit-epilogue path.
SCALED_FUSED_RUNNERS: Dict[str, Callable] = {
    "pallas_lut_gather": _scaled_lut,
    "pallas_lut_nibble": _scaled_nibble,
    "pallas_log": _scaled_log,
}


def _partial_conv_lut(xb, wb3, sx, sw, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_lut_partial(xb, wb3, gp.spec, sx, sw,
                                  kh=plan.conv.kh, kw=plan.conv.kw,
                                  stride=plan.conv.stride, nibble=False,
                                  block=plan.block,
                                  interpret=plan.interpret)


def _partial_conv_nibble(xb, wb3, sx, sw, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_lut_partial(xb, wb3, gp.spec, sx, sw,
                                  kh=plan.conv.kh, kw=plan.conv.kw,
                                  stride=plan.conv.stride, nibble=True,
                                  block=plan.block,
                                  interpret=plan.interpret)


def _partial_conv_log(xb, wb3, sx, sw, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_log_partial(xb, wb3, sx, sw, bits=gp.bits,
                                  compensated=(gp.family == "log_our"),
                                  kh=plan.conv.kh, kw=plan.conv.kw,
                                  stride=plan.conv.stride,
                                  block=plan.block,
                                  interpret=plan.interpret)


# entry name -> shard-local f32 (B, H, W, C_shard) x (kh*kw, C_shard, N)
# -> int32 (B, OH, OW, N) partial accumulator
CONV_PARTIAL_RUNNERS: Dict[str, Callable] = {
    "pallas_conv_lut": _partial_conv_lut,
    "pallas_conv_nibble": _partial_conv_nibble,
    "pallas_conv_log": _partial_conv_log,
}


def _scaled_conv_lut(xb, wb3, sx, sw, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_lut_fused_scaled(xb, wb3, gp.spec, sx, sw,
                                       kh=plan.conv.kh, kw=plan.conv.kw,
                                       stride=plan.conv.stride,
                                       nibble=False, block=plan.block,
                                       interpret=plan.interpret)


def _scaled_conv_nibble(xb, wb3, sx, sw, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_lut_fused_scaled(xb, wb3, gp.spec, sx, sw,
                                       kh=plan.conv.kh, kw=plan.conv.kw,
                                       stride=plan.conv.stride,
                                       nibble=True, block=plan.block,
                                       interpret=plan.interpret)


def _scaled_conv_log(xb, wb3, sx, sw, gp: GemmParams, plan: ConvPlan):
    from repro.kernels import ops

    return ops.conv2d_log_fused_scaled(xb, wb3, sx, sw, bits=gp.bits,
                                       compensated=(gp.family
                                                    == "log_our"),
                                       kh=plan.conv.kh, kw=plan.conv.kw,
                                       stride=plan.conv.stride,
                                       block=plan.block,
                                       interpret=plan.interpret)


# the conv twin of SCALED_FUSED_RUNNERS (output-sharded layout)
SCALED_CONV_RUNNERS: Dict[str, Callable] = {
    "pallas_conv_lut": _scaled_conv_lut,
    "pallas_conv_nibble": _scaled_conv_nibble,
    "pallas_conv_log": _scaled_conv_log,
}


# ---------------------------------------------------------------------------
# Surrogate variance law (shared by both frontends; DESIGN.md §2/§3)
# ---------------------------------------------------------------------------


def surrogate_variance(gp: GemmParams, scale2, k_len: int,
                       xf=None, wf=None, fast: bool = False):
    """var[out] = c0 * K * s^2 + c1 * (A^2 @ B^2) * s-units.

    `scale2` is the squared product of quantization scales broadcastable
    to the output; `xf`/`wf` are the (dequantized or integer) operands
    for the c1 term — in integer units the caller folds s^2 itself.
    Returns None when the family carries no noise.
    """
    if gp.c0 <= 0.0 and gp.c1 <= 0.0:
        return None
    var = gp.c0 * k_len * scale2
    if gp.c1 > 0.0 and xf is not None and wf is not None:
        if fast:
            a2 = jnp.sum(xf * xf, axis=-1, keepdims=True)      # (M, 1)
            b2 = jnp.sum(wf * wf, axis=0, keepdims=True)       # (1, N)
            sq = a2 * b2 / k_len
        else:
            sq = (xf * xf) @ (wf * wf)
        var = var + gp.c1 * sq
    return var


def surrogate_noise(key, shape, dtype, kind: str = NOISE_KIND):
    if kind == "rademacher":
        return jax.random.rademacher(key, shape, jnp.int8).astype(dtype)
    return jax.random.normal(key, shape, dtype=dtype)


# ---------------------------------------------------------------------------
# Quantization + STE plumbing (shared by both frontends)
# ---------------------------------------------------------------------------


def _quantize_operands(x, w, bits, per_token: bool = False):
    # activations: per-tensor scale (the macro's ADC view) by default,
    # or per-row when the caller needs batch-size-invariant numerics
    # (GemmParams.per_token); weights are always per-out-channel
    sx = quant_scale(x, bits, axis=-1 if per_token else None)
    sw = quant_scale(w, bits, axis=0)              # per-out-channel (weights)
    xq = quantize(x, sx, bits)
    wq = quantize(w, sw, bits)
    return xq, sx, wq, sw


def _ste_matmul(forward):
    """Wrap a (xf, wf) -> out forward with an exact-float STE VJP."""

    @jax.custom_vjp
    def f(xf, wf):
        return forward(xf, wf)

    def fwd(xf, wf):
        return forward(xf, wf), (xf, wf)

    def bwd(res, g):
        xf, wf = res
        return (g @ wf.T).astype(xf.dtype), (xf.T @ g).astype(wf.dtype)

    f.defvjp(fwd, bwd)
    return f


def _ste_matmul_eps(forward):
    """STE wrapper for a (xf, wf, eps) -> out forward; the pre-drawn
    surrogate noise rides through with a zero cotangent."""

    @jax.custom_vjp
    def f(xf, wf, eps):
        return forward(xf, wf, eps)

    def fwd(xf, wf, eps):
        return forward(xf, wf, eps), (xf, wf, eps)

    def bwd(res, g):
        xf, wf, eps = res
        return ((g @ wf.T).astype(xf.dtype), (xf.T @ g).astype(wf.dtype),
                jnp.zeros_like(eps))

    f.defvjp(fwd, bwd)
    return f


def _float_conv(x4, w2, conv: ConvParams):
    """Exact float conv (the STE gradient reference): x4 (B,H,W,C),
    w2 (kh*kw*C, N) tap-major -> (B,OH,OW,N)."""
    c = x4.shape[-1]
    wk = w2.reshape(conv.kh, conv.kw, c, -1)
    return jax.lax.conv_general_dilated(
        x4, wk, (conv.stride, conv.stride),
        [(conv.kh // 2, conv.kh // 2), (conv.kw // 2, conv.kw // 2)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _ste_conv(forward, conv: ConvParams):
    """STE wrapper for a (x4, w2) -> out4 conv forward: backward is the
    exact float convolution's VJP (the conv analogue of g @ w.T /
    x.T @ g in `_ste_matmul`)."""

    @jax.custom_vjp
    def f(x4, w2):
        return forward(x4, w2)

    def fwd(x4, w2):
        return forward(x4, w2), (x4, w2)

    def bwd(res, g):
        x4, w2 = res
        _, vjp = jax.vjp(lambda a, b: _float_conv(a, b, conv),
                         x4.astype(jnp.float32), w2.astype(jnp.float32))
        gx, gw = vjp(g.astype(jnp.float32))
        return gx.astype(x4.dtype), gw.astype(w2.dtype)

    f.defvjp(fwd, bwd)
    return f


def _ste_conv_eps(forward, conv: ConvParams):
    """STE conv wrapper for a (x4, w2, eps) forward; pre-drawn surrogate
    noise rides through with a zero cotangent."""

    @jax.custom_vjp
    def f(x4, w2, eps):
        return forward(x4, w2, eps)

    def fwd(x4, w2, eps):
        return forward(x4, w2, eps), (x4, w2, eps)

    def bwd(res, g):
        x4, w2, eps = res
        _, vjp = jax.vjp(lambda a, b: _float_conv(a, b, conv),
                         x4.astype(jnp.float32), w2.astype(jnp.float32))
        gx, gw = vjp(g.astype(jnp.float32))
        return (gx.astype(x4.dtype), gw.astype(w2.dtype),
                jnp.zeros_like(eps))

    f.defvjp(fwd, bwd)
    return f


# Trace probe: bumps once per actual trace of a frontend forward (i.e.
# per executable build / shape specialization), never on a steady-state
# cache-hit call.  tests/test_dispatch.py asserts it stays flat.
_TRACE_COUNT = [0]


def trace_count() -> int:
    return _TRACE_COUNT[0]


def _mark_trace() -> None:
    _TRACE_COUNT[0] += 1
    sink = _OBS_SINK[0]
    if sink is not None:
        sink.retrace()


# Observability sink (obs/, DESIGN.md §15): a host-side object notified
# at dispatch boundaries — once per *frontend call* (eager calls and
# outer-jit traces; a jitted steady-state replay never re-enters the
# Python frontends, which is exactly the zero-overhead contract) — and
# once per executable trace.  `None` (the default) short-circuits to a
# single list-load + branch.
_OBS_SINK: List[Optional[object]] = [None]
_OBS_MAC_SCALE: List[float] = [1.0]


def set_obs_sink(sink) -> Optional[object]:
    """Install the dispatch-boundary telemetry sink; returns the
    previous one so scoped captures (obs/energy.py) can restore it.
    The sink must expose ``dispatch(op, family, mode, bits, macs,
    cache_hit)`` and ``retrace()``."""
    prev = _OBS_SINK[0]
    _OBS_SINK[0] = sink
    return prev


@contextlib.contextmanager
def obs_mac_scale(factor: float):
    """Multiply the ambient MAC attribution scale for dispatches issued
    inside the context.  `models/transformer.py` wraps its scanned body
    in ``obs_mac_scale(cfg.n_periods)``: a `lax.scan` body traces ONCE
    but executes `n_periods` times, so trace-time MAC capture would
    otherwise undercount the stack by the body depth."""
    prev = _OBS_MAC_SCALE[0]
    _OBS_MAC_SCALE[0] = prev * float(factor)
    try:
        yield
    finally:
        _OBS_MAC_SCALE[0] = prev


def _obs_dispatch(op: str, gp: "GemmParams", macs: float,
                  cache_hit: bool, kernel: str) -> None:
    _OBS_SINK[0].dispatch(op=op, family=gp.family, mode=gp.mode,
                          bits=gp.bits,
                          macs=macs * _OBS_MAC_SCALE[0],
                          cache_hit=cache_hit, kernel=kernel)


def _plan_kernel(plan) -> str:
    """Registry name of the kernel a (mesh) plan routes to.  Every
    frontend runs the kernel under `jax.named_scope(<this name>)`, so
    the device ops it lowers to carry the name in their metadata."""
    return (plan.plan if isinstance(plan, MeshPlan) else plan).entry.name


# ---------------------------------------------------------------------------
# Forward builders (shared by the cached and legacy-uncached paths)
# ---------------------------------------------------------------------------


def _cim_forward(gp: GemmParams, plan: GemmPlan, noise_kind: str,
                 stochastic: bool, fused: bool):
    """(forward, takes_eps) for the macro frontend.  `fused=False`
    reproduces the pre-cache pipeline (separate quantize/epilogue XLA
    passes around the int kernels) — kept as the benchmark baseline."""
    mode = gp.mode
    if mode == "exact":
        def forward(xf, wf):
            _mark_trace()
            xq, sx, wq, sw = _quantize_operands(xf, wf, gp.bits,
                                                gp.per_token)
            if gp.fault is not None:
                wq = faults.apply_weight_faults(wq, gp.fault, gp.bits)
            return dequantize(xq, sx) @ dequantize(wq, sw)
        return forward, False

    if mode in ("bit_exact", "hardware"):
        # the fused runners carry the per-tensor sx as an SMEM scalar;
        # per-token (per-row) scales must take the unfused path where
        # the (M, 1) scale applies in the XLA epilogue.  Faulted
        # executables also go unfused: the fused kernels quantize on
        # tile load, so the stored-word surgery has to happen in the
        # XLA prologue around the int kernel.
        if (fused and not gp.per_token and gp.fault is None
                and plan.entry.name in FUSED_RUNNERS):
            runner = FUSED_RUNNERS[plan.entry.name]

            def forward(xf, wf):
                _mark_trace()
                return runner(xf.astype(jnp.float32),
                              wf.astype(jnp.float32), gp, plan)
        else:
            def forward(xf, wf):
                _mark_trace()
                xq, sx, wq, sw = _quantize_operands(xf, wf, gp.bits,
                                                    gp.per_token)
                if gp.fault is not None:
                    wq = faults.apply_weight_faults(wq, gp.fault,
                                                    gp.bits)
                acc = run_int_kernel(plan, xq, wq, gp)
                return (acc.astype(jnp.float32) * sx) * sw
        return forward, False

    # surrogate / surrogate_fast
    if plan.entry.name == "pallas_fused_surrogate":
        from repro.kernels.cim_gemm import cim_gemm_fused

        def forward(xf, wf, eps=None):
            _mark_trace()
            return cim_gemm_fused(xf.astype(jnp.float32),
                                  wf.astype(jnp.float32), eps, gp.mu,
                                  gp.c0, gp.c1, bits=gp.bits,
                                  block=plan.block,
                                  interpret=plan.interpret)
        return forward, stochastic

    def forward(xf, wf, eps=None):
        _mark_trace()
        xq, sx, wq, sw = _quantize_operands(xf, wf, gp.bits)
        xdq = dequantize(xq, sx)
        wdq = dequantize(wq, sw)
        out = (1.0 + gp.mu) * (xdq @ wdq)
        if eps is not None:
            scale2 = (sx * sw) ** 2                # (1, N): per-out-channel
            var = surrogate_variance(gp, scale2, xf.shape[-1], xdq, wdq,
                                     fast=(gp.mode == "surrogate_fast"))
            if var is not None:
                out = out + jnp.sqrt(jnp.maximum(var, 0.0)) * eps
        return out

    return forward, stochastic


def _model_forward(gp: GemmParams, plan: GemmPlan, noise_kind: str,
                   stochastic: bool, apply: bool, fused: bool):
    """Model-frontend forward.  Returns ("ste", forward, takes_eps) for
    kernel-backed rank-2 paths or ("plain", fn, needs_key) for the
    fake-quant XLA paths (gradients flow through the quantizer)."""
    if apply and gp.mode in ("bit_exact", "hardware"):
        if (fused and not gp.per_token and gp.fault is None
                and plan.entry.name in FUSED_RUNNERS):
            runner = FUSED_RUNNERS[plan.entry.name]

            def forward(x2, wf):
                _mark_trace()
                out = runner(x2.astype(jnp.float32),
                             wf.astype(jnp.float32), gp, plan)
                return out.astype(x2.dtype)
        else:
            def forward(x2, wf):
                _mark_trace()
                xq, sx, wq, sw = _quantize_operands(
                    x2.astype(jnp.float32), wf.astype(jnp.float32),
                    gp.bits, gp.per_token)
                if gp.fault is not None:
                    wq = faults.apply_weight_faults(wq, gp.fault,
                                                    gp.bits)
                acc = run_int_kernel(plan, xq, wq, gp)
                out = (acc.astype(jnp.float32) * sx) * sw
                return out.astype(x2.dtype)
        return "ste", forward, False

    if apply and plan.entry.name == "pallas_fused_surrogate":
        # TPU production path: one HBM pass computes D and A^2@B^2 fused
        from repro.kernels.cim_gemm import cim_gemm_fused

        def forward(x2, wf, eps=None):
            _mark_trace()
            out = cim_gemm_fused(x2.astype(jnp.float32),
                                 wf.astype(jnp.float32), eps, gp.mu,
                                 gp.c0, gp.c1, bits=gp.bits,
                                 block=plan.block, interpret=plan.interpret)
            return out.astype(x2.dtype)
        return "ste", forward, stochastic

    # exact / surrogate paths: fake-quant QAT form.  fake-quant the
    # weight in ITS dtype: an f32 upcast here gets hoisted out of the
    # layer scan by XLA and materializes the whole stacked weight in f32
    # (54 GB/instance at 671B, EXPERIMENTS.md §Perf).
    def fn(x, w, key=None):
        _mark_trace()
        xq = fake_quant(x, gp.bits, axis=-1 if gp.per_token else None)
        if apply and gp.fault is not None:
            # as-fabricated exact macro: true-quantize the weight,
            # fault the stored words, dequantize — STE around the whole
            # read path so QAT gradients still flow to w
            sw = quant_scale(jax.lax.stop_gradient(w).astype(jnp.float32),
                             gp.bits, axis=0)
            wi = quantize(jax.lax.stop_gradient(w).astype(jnp.float32),
                          sw, gp.bits)
            wi = faults.apply_weight_faults(wi, gp.fault, gp.bits)
            wdq = dequantize(wi, sw).astype(w.dtype)
            wq = w + jax.lax.stop_gradient(wdq - w)
        else:
            wq = fake_quant(w, gp.bits, axis=0).astype(x.dtype)
        d = xq @ wq
        if not apply or gp.mode == "exact":
            # mixed-macro allocation / QAT baseline: exact int8 macro
            return d
        out = (1.0 + gp.mu) * d
        if stochastic and key is not None:
            k_len = x.shape[-1]
            sx = quant_scale(jax.lax.stop_gradient(x), gp.bits)
            sw = quant_scale(jax.lax.stop_gradient(w), gp.bits, axis=0)
            scale2 = (sx * sw).astype(jnp.float32) ** 2
            xf = wf = None
            if gp.c1 > 0.0:
                xf = jax.lax.stop_gradient(xq).astype(jnp.float32)
                wf = jax.lax.stop_gradient(wq).astype(jnp.float32)
            var = surrogate_variance(gp, scale2, k_len, xf, wf,
                                     fast=(gp.mode == "surrogate_fast"))
            if var is not None:
                eps = surrogate_noise(key, d.shape, d.dtype, noise_kind)
                out = out + jax.lax.stop_gradient(
                    jnp.sqrt(jnp.maximum(var, 0.0)).astype(d.dtype) * eps)
        return out

    return "plain", fn, stochastic


def _conv_forward(gp: GemmParams, plan: ConvPlan, noise_kind: str,
                  stochastic: bool, shape: Tuple[int, int, int, int, int]):
    """(forward, takes_eps) for the conv frontend.  Implicit-GEMM Pallas
    kernels for the routed hardware/exact families; the `conv_im2col`
    fallback materializes patches and reuses the GEMM forward (every
    mode, including the surrogates)."""
    conv = plan.conv
    if plan.entry.name in CONV_RUNNERS:
        runner = CONV_RUNNERS[plan.entry.name]

        def forward(x4, w2):
            _mark_trace()
            return runner(x4.astype(jnp.float32), w2.astype(jnp.float32),
                          gp, plan)
        return forward, False

    # conv_im2col fallback: the inner GEMM plan is resolved once at
    # build time from the conv-BUCKETED dims (the executable is cached
    # per conv bucket, so deriving the plan from the first caller's
    # concrete shape would make block selection call-order-dependent
    # within a bucket).
    b, h, w_, c, n = shape
    hb, wb = autotune.bucket(h), autotune.bucket(w_)
    oh, ow = conv_out_hw(hb, wb, conv.kh, conv.kw, conv.stride)
    gplan = plan_gemm(gp.family, gp.mode, gp.bits,
                      autotune.bucket(b) * oh * ow,
                      conv.kh * conv.kw * autotune.bucket(c),
                      autotune.bucket(n), backend=plan.backend,
                      spec=gp.routing_spec)
    inner, takes_eps = _cim_forward(gp, gplan, noise_kind, stochastic,
                                    fused=True)
    if takes_eps:
        def forward(x4, w2, eps):
            _mark_trace()
            cols = im2col_nhwc(x4.astype(jnp.float32), conv)
            out2 = inner(cols.reshape(-1, cols.shape[-1]),
                         w2.astype(jnp.float32), eps)
            return out2.reshape(cols.shape[:3] + (w2.shape[-1],))
    else:
        def forward(x4, w2):
            _mark_trace()
            cols = im2col_nhwc(x4.astype(jnp.float32), conv)
            out2 = inner(cols.reshape(-1, cols.shape[-1]),
                         w2.astype(jnp.float32))
            return out2.reshape(cols.shape[:3] + (w2.shape[-1],))
    return forward, takes_eps


# ---------------------------------------------------------------------------
# Mesh forwards: one shard-local kernel per device under shard_map (§11)
# ---------------------------------------------------------------------------


def _shard_map(fn, mp: MeshPlan):
    return jax.shard_map(fn, mesh=mp.mesh, in_specs=mp.in_specs,
                         out_specs=mp.out_spec, check_vma=False)


def _mesh_forward(gp: GemmParams, mp: MeshPlan, preserve_dtype: bool):
    """(M, K) x (K, N) mesh-partitioned forward.  Global scales are
    computed OUTSIDE the shard_map (cheap max-reductions; XLA lowers
    them to an all-reduce over the sharded operand) so every shard
    quantizes against the oracle's values.  Contraction-sharded: the
    int32 partial accumulators psum exactly, dequant epilogue after
    the collective.  Output-sharded: nothing separates quantize from
    dequant, so the shard runs the FUSED kernel (epilogue in-kernel,
    no accumulator round trip).  Bit-identical to the unsharded
    executable either way."""
    red = mp.reduce_axes
    fused = None if red else SCALED_FUSED_RUNNERS.get(mp.plan.entry.name)
    if fused is not None:
        def shard_fn(xb, wb, sx, sw):
            return fused(xb, wb, sx, sw, gp, mp.plan)
    else:
        runner = PARTIAL_RUNNERS[mp.plan.entry.name]

        def shard_fn(xb, wb, sx, sw):
            acc = runner(xb, wb, sx, sw, gp, mp.plan)
            if red:
                acc = jax.lax.psum(acc, red)
            return (acc.astype(jnp.float32) * sx) * sw

    sharded = _shard_map(shard_fn, mp)

    def forward(xf, wf):
        _mark_trace()
        x32 = xf.astype(jnp.float32)
        w32 = wf.astype(jnp.float32)
        sx = quant_scale(x32, gp.bits)                 # global per-tensor
        sw = quant_scale(w32, gp.bits, axis=0)         # global (1, N)
        out = sharded(x32, w32, sx, sw)
        return out.astype(xf.dtype) if preserve_dtype else out

    return forward


def _mesh_conv_forward(gp: GemmParams, mp: MeshPlan):
    """(B, H, W, C) mesh-partitioned conv forward.  The weight travels
    as the rank-3 (kh*kw, C, N) tap stack so an input-channel shard is
    a plain dimension shard of every tap.  Entries without an implicit
    partial kernel (the `conv_im2col` fallback: bit_exact mode, or a
    VMEM-gated hardware plane) materialize the SHARD-LOCAL patch matrix
    and run the routed integer GEMM kernel on it — the local column
    order permutes K within the shard, which the int32 sum erases."""
    plan, conv = mp.plan, mp.plan.conv
    red = mp.reduce_axes
    fused = None if red else SCALED_CONV_RUNNERS.get(plan.entry.name)
    runner = CONV_PARTIAL_RUNNERS.get(plan.entry.name)
    if fused is None and runner is None:
        bl, h, w_, cl, nl = mp.local_shape
        hb, wb_ = autotune.bucket(h), autotune.bucket(w_)
        oh, ow = conv_out_hw(hb, wb_, conv.kh, conv.kw, conv.stride)
        gplan = plan_gemm(gp.family, gp.mode, gp.bits,
                          autotune.bucket(bl) * oh * ow,
                          conv.kh * conv.kw * autotune.bucket(cl),
                          autotune.bucket(nl), backend=plan.backend,
                          spec=gp.spec)

        def runner(xb, wb3, sx, sw, gp_, _plan):
            cols = im2col_nhwc(xb, conv)
            xq = quantize(cols.reshape(-1, cols.shape[-1]), sx, gp_.bits)
            wq = quantize(wb3.reshape(-1, wb3.shape[-1]), sw, gp_.bits)
            acc = run_int_kernel(gplan, xq, wq, gp_)
            return acc.reshape(cols.shape[:3] + (wb3.shape[-1],))

    if fused is not None:
        def shard_fn(xb, wb3, sx, sw):
            return fused(xb, wb3, sx, sw, gp, plan)
    else:
        def shard_fn(xb, wb3, sx, sw):
            acc = runner(xb, wb3, sx, sw, gp, plan)
            if red:
                acc = jax.lax.psum(acc, red)
            return (acc.astype(jnp.float32) * sx) * sw  # (1,N) broadcasts

    sharded = _shard_map(shard_fn, mp)

    def forward(x4, w2):
        _mark_trace()
        x32 = x4.astype(jnp.float32)
        w32 = w2.astype(jnp.float32)
        sx = quant_scale(x32, gp.bits)
        sw = quant_scale(w32, gp.bits, axis=0)
        w3 = w32.reshape(conv.kh * conv.kw, x32.shape[-1], -1)
        return sharded(x32, w3, sx, sw)

    return forward


# ---------------------------------------------------------------------------
# Executable cache (zero-retrace steady state, DESIGN.md §8)
# ---------------------------------------------------------------------------

_EXEC_CACHE: Dict[Tuple, Callable] = {}
_EXEC_LOCK = threading.Lock()


def _exec_key(frontend: str, gp: GemmParams, plan, stochastic: bool,
              noise_kind: str, apply: bool, x, w, m: int, k: int,
              n: int) -> Tuple:
    return (frontend, gp, _plan_token(plan), stochastic, noise_kind,
            apply, x.dtype, w.dtype, x.ndim,
            autotune.bucket(m), autotune.bucket(k), autotune.bucket(n))


def _wrap_ste(forward: Callable, takes_eps: bool,
              noise_kind: str) -> Callable:
    """Jit an STE-wrapped rank-2 forward behind a flatten/restore shell;
    stochastic variants draw the noise from an explicit key argument
    (zero-cotangent through the STE).  Shared by both frontends."""
    if takes_eps:
        ste = _ste_matmul_eps(forward)

        @jax.jit
        def run(x, w, key):
            x2 = x.reshape((-1, x.shape[-1]))
            eps = surrogate_noise(key, (x2.shape[0], w.shape[-1]),
                                  jnp.float32, noise_kind)
            out = ste(x2, w, eps)
            return out.reshape(x.shape[:-1] + (w.shape[-1],))
    else:
        ste = _ste_matmul(forward)

        @jax.jit
        def run(x, w):
            x2 = x.reshape((-1, x.shape[-1]))
            out = ste(x2, w)
            return out.reshape(x.shape[:-1] + (w.shape[-1],))
    return run


def _build_executable(frontend: str, gp: GemmParams, plan,
                      stochastic: bool, noise_kind: str,
                      apply: bool) -> Callable:
    if isinstance(plan, MeshPlan):
        forward = _mesh_forward(gp, plan,
                                preserve_dtype=(frontend == "model"))
        return _wrap_ste(forward, False, noise_kind)
    if frontend == "cim":
        forward, takes_eps = _cim_forward(gp, plan, noise_kind, stochastic,
                                          fused=True)
        return _wrap_ste(forward, takes_eps, noise_kind)

    kind, f, flag = _model_forward(gp, plan, noise_kind, stochastic, apply,
                                   fused=True)
    if kind == "plain":
        if flag:                       # stochastic fake-quant path
            @jax.jit
            def run(x, w, key):
                return f(x, w, key)
        else:
            @jax.jit
            def run(x, w):
                return f(x, w)
        return run
    return _wrap_ste(f, flag, noise_kind)


def _executable_for(frontend: str, gp: GemmParams, plan: GemmPlan,
                    stochastic: bool, noise_kind: str, apply: bool,
                    x, w, m: int, k: int, n: int) -> Callable:
    key = _exec_key(frontend, gp, plan, stochastic, noise_kind, apply,
                    x, w, m, k, n)
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        with _EXEC_LOCK:
            fn = _EXEC_CACHE.get(key)
            if fn is None:
                fn = _build_executable(frontend, gp, plan, stochastic,
                                       noise_kind, apply)
                _EXEC_CACHE[key] = fn
    return fn


def _conv_exec_key(gp: GemmParams, plan, stochastic: bool,
                   noise_kind: str, x, w, b: int, h: int, w_: int, c: int,
                   n: int) -> Tuple:
    conv = plan.plan.conv if isinstance(plan, MeshPlan) else plan.conv
    return ("conv", gp, _plan_token(plan), stochastic, noise_kind,
            x.dtype, w.dtype) + autotune.bucket_conv(
                b, h, w_, c, conv.kh, conv.kw,
                conv.stride) + (autotune.bucket(n),)


def _build_conv_executable(gp: GemmParams, plan, stochastic: bool,
                           noise_kind: str, shape) -> Callable:
    if isinstance(plan, MeshPlan):
        forward, takes_eps = _mesh_conv_forward(gp, plan), False
        conv = plan.plan.conv
    else:
        forward, takes_eps = _conv_forward(gp, plan, noise_kind,
                                           stochastic, shape)
        conv = plan.conv
    if takes_eps:
        ste = _ste_conv_eps(forward, conv)

        @jax.jit
        def run(x, w, key):
            oh, ow = conv_out_hw(x.shape[1], x.shape[2], conv.kh,
                                 conv.kw, conv.stride)
            eps = surrogate_noise(key, (x.shape[0] * oh * ow, w.shape[-1]),
                                  jnp.float32, noise_kind)
            return ste(x, w, eps)
    else:
        ste = _ste_conv(forward, conv)

        @jax.jit
        def run(x, w):
            return ste(x, w)
    return run


def _conv_executable_for(gp: GemmParams, plan: ConvPlan, stochastic: bool,
                         noise_kind: str, x, w, b: int, h: int, w_: int,
                         c: int, n: int) -> Callable:
    key = _conv_exec_key(gp, plan, stochastic, noise_kind, x, w, b, h, w_,
                         c, n)
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        with _EXEC_LOCK:
            fn = _EXEC_CACHE.get(key)
            if fn is None:
                fn = _build_conv_executable(gp, plan, stochastic,
                                            noise_kind, (b, h, w_, c, n))
                _EXEC_CACHE[key] = fn
    return fn


def _attn_exec_key(gp: GemmParams, plan: AttnPlan, q, k, b: int,
                   heads: int, kv_heads: int, sq: int, skv: int,
                   head_dim: int) -> Tuple:
    return ("attn", gp, _plan_token(plan), q.dtype, k.dtype) + \
        autotune.bucket_attn(b, heads, kv_heads, sq, skv, head_dim)


def _build_attn_executable(gp: GemmParams, plan: AttnPlan) -> Callable:
    """One jitted attention executable (model layout in/out).

    Forward = the routed integer kernel; backward = exact float VJP
    through ``attn_float`` (STE semantics, matching the GEMM/conv
    contract).  The position/validity operands are explicit custom_vjp
    arguments (closing over tracers is illegal under transforms); being
    integer, their cotangents are the mandated float0 zeros.

    Bit-identity discipline: the jitted core is EXACTLY the kernel
    entry-point graph — the layout transposes and the per-head scale
    reductions run eagerly in the `run` shell, mirroring the ops-layer
    oracle surface call for call.  Fused into the core graph, XLA's
    algebraic rewrites (e.g. x / (m / qmax) -> x * qmax / m) perturb
    the attn_xla path by 1 ulp against the standalone oracle."""
    import numpy as np

    from repro.kernels.attn_gemm import (attn_float, attn_fused,
                                         attn_reference, attn_scales)
    from repro.kernels.ops import _attn_table

    kw = _attn_run_kwargs(gp, plan)
    kw.pop("spec", None)
    path, causal, window = kw["path"], plan.attn.causal, plan.attn.window
    table_spec = gp.spec if path in ("lut", "nibble") else None
    pallas = plan.entry.pallas
    if pallas:
        kw["interpret"] = plan.interpret

    spec_key = (gp.family, gp.bits, gp.compressor, gp.n_approx_cols)
    fault = gp.fault

    @jax.custom_vjp
    def f(a, b_, c, sq_s, sk_s, sv_s, qpos, kpos, kval):
        _mark_trace()
        # table resolved at use time, not closed over: a build-time jnp
        # constant hoisted into scan consts leaks as a tracer under
        # grad-through-scan partial-eval (same rule as _signed_lut_flat;
        # the numpy table is cached, asarray is free under jit)
        if fault is not None and path in ("lut", "nibble"):
            # the table is an explicit kernel operand here, so attention
            # runs as-fabricated with NO kernel changes: swap in the
            # faulted stored form (full signed table rebuilt from the
            # faulted magnitude array, or the four faulted sub-LUTs).
            # mxu/log paths store no table — they are fault-transparent
            # and the projection GEMMs carry the defects (DESIGN.md §14)
            if path == "lut":
                table = jnp.asarray(
                    faults.faulted_signed_lut_flat(spec_key, fault))
            else:
                table = jnp.asarray(
                    faults.faulted_nibble_subs_flat(spec_key, fault))
        else:
            table = _attn_table(path, table_spec)
        entry_point = attn_fused if pallas else attn_reference
        return entry_point(a, b_, c, sq_s, sk_s, sv_s, qpos, kpos, kval,
                           table, **kw)

    def fwd(a, b_, c, sq_s, sk_s, sv_s, qpos, kpos, kval):
        out = f(a, b_, c, sq_s, sk_s, sv_s, qpos, kpos, kval)
        return out, (a, b_, c, qpos, kpos, kval)

    def bwd(res, g):
        a, b_, c, qpos, kpos, kval = res
        _, vjp = jax.vjp(
            lambda x, y, z: attn_float(x, y, z, qpos, kpos, kval,
                                       causal=causal, window=window),
            a, b_, c)
        izero = lambda t: np.zeros(t.shape, jax.dtypes.float0)  # noqa: E731
        da, db, dc = vjp(g.astype(jnp.float32))
        return (da, db, dc, jnp.zeros((a.shape[0], a.shape[1])),
                jnp.zeros((b_.shape[0], b_.shape[1])),
                jnp.zeros((c.shape[0], c.shape[1])),
                izero(qpos), izero(kpos), izero(kval))

    f.defvjp(fwd, bwd)
    core = jax.jit(f)

    def run(q, k, v, qpos, kpos, kval):
        # model layout (B, S, H, D) -> kernel layout (B, H, S, D)
        qh = jnp.transpose(q.astype(jnp.float32), (0, 2, 1, 3))
        kh_ = jnp.transpose(k.astype(jnp.float32), (0, 2, 1, 3))
        vh = jnp.transpose(v.astype(jnp.float32), (0, 2, 1, 3))
        sq_s, sk_s, sv_s = attn_scales(qh, kh_, vh, gp.bits)
        return jnp.transpose(
            core(qh, kh_, vh, sq_s, sk_s, sv_s, qpos, kpos, kval),
            (0, 2, 1, 3))

    return run


def _attn_executable_for(gp: GemmParams, plan: AttnPlan, q, k, b: int,
                         heads: int, kv_heads: int, sq: int, skv: int,
                         head_dim: int) -> Callable:
    key = _attn_exec_key(gp, plan, q, k, b, heads, kv_heads, sq, skv,
                         head_dim)
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        with _EXEC_LOCK:
            fn = _EXEC_CACHE.get(key)
            if fn is None:
                fn = _build_attn_executable(gp, plan)
                _EXEC_CACHE[key] = fn
    return fn


def executable_cache_size() -> int:
    return len(_EXEC_CACHE)


# Front cache: collapses a steady-state eager call's full resolution
# (plan_gemm -> _exec_key -> executable) into ONE dict hit on a key of
# cheap hashables — the per-call overhead on top of the jitted
# executable is a tuple hash + dict get.  Values are (run, stochastic).
_FAST_CACHE: Dict[Tuple, Tuple[Callable, bool, str]] = {}


def clear_dispatch_caches() -> None:
    """Drop the executable cache and the memoized routing tables (tests;
    also invoked when the registry mutates)."""
    with _EXEC_LOCK:
        _EXEC_CACHE.clear()
        _FAST_CACHE.clear()
    _select_kernel_cached.cache_clear()
    _plan_gemm_cached.cache_clear()
    _conv_entries_cached.cache_clear()
    _plan_conv_cached.cache_clear()
    _attn_entries_cached.cache_clear()
    _plan_attn_cached.cache_clear()
    _plan_gemm_mesh_cached.cache_clear()
    _plan_conv_mesh_cached.cache_clear()
    _fault_conv_plan.cache_clear()


# ---------------------------------------------------------------------------
# Macro frontend: cim_matmul / approx_matmul (f32 out, true quantization)
# ---------------------------------------------------------------------------


def cim_matmul(x: jnp.ndarray, w: jnp.ndarray, gp: GemmParams,
               key: Optional[jax.Array] = None, *,
               noise_kind: str = "normal",
               interpret: Optional[bool] = None,
               block: Optional[Tuple[int, int, int]] = None,
               cached: bool = True,
               mesh: Optional[Mesh] = None,
               x_spec=None, w_spec=None) -> jnp.ndarray:
    """Dispatch + execute one approximate GEMM (macro semantics).

    x: (..., K) float; w: (K, N) float.  Returns float32 (..., N) with
    straight-through exact gradients.  `cached=True` (default) executes
    a pre-built jitted STE function from the module-level executable
    cache — a steady-state eager call never retraces.  `cached=False`
    rebuilds the closure per call (legacy behavior; benchmark baseline).

    With `mesh` (+ `x_spec`/`w_spec`, see `plan_gemm`) the executable
    is shard_map-partitioned over the mesh (DESIGN.md §11) —
    bit-identical to the unsharded call for the integer modes, one
    per-shard kernel per device, only the (M, N) partial accumulator
    crossing the interconnect in the contraction-sharded layout.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = 1
    for s in lead:
        m *= int(s)
    if mesh is not None:
        if gp.per_token:
            raise ValueError(
                "per-token activation scales are not supported on the "
                "mesh shard_map path; drop the mesh or per_token")
        if gp.fault is not None:
            raise ValueError(
                "fault injection is not supported on the mesh shard_map "
                "path (the partial/fused shard kernels quantize their "
                "words in-kernel); drop the mesh or the fault config")
        # exact-shape validation on EVERY call: the front cache keys on
        # bucketed shapes, and a warm entry must never serve a shape
        # the planner would reject (divisibility is not bucket-stable)
        _check_mesh_gemm(gp.mode, m, k, n, mesh, x_spec, w_spec)
    if cached:
        fkey = ("cim", gp, x.dtype, w.dtype, x.ndim, autotune.bucket(m),
                autotune.bucket(k), autotune.bucket(n), key is not None,
                noise_kind, interpret, block, jax.default_backend(),
                mesh, _canon_spec(x_spec), _canon_spec(w_spec))
        hit = _FAST_CACHE.get(fkey)
        if hit is not None:
            run, stochastic, kernel = hit
            if _OBS_SINK[0] is not None:
                _obs_dispatch("gemm", gp, float(m) * k * n, True, kernel)
            with jax.named_scope(kernel):
                return run(x, w, key) if stochastic else run(x, w)
    if gp.mode not in MODES:
        raise ValueError(f"mode {gp.mode!r} not in {MODES}")
    plan = plan_gemm(gp.family, gp.mode, gp.bits, m, k, n,
                     interpret=interpret, block=block,
                     spec=gp.routing_spec, mesh=mesh, x_spec=x_spec,
                     w_spec=w_spec)
    stochastic = (gp.mode in ("surrogate", "surrogate_fast")
                  and key is not None and (gp.c0 > 0.0 or gp.c1 > 0.0))
    if cached:
        run = _executable_for("cim", gp, plan, stochastic, noise_kind,
                              True, x, w, m, k, n)
        kernel = _plan_kernel(plan)
        with _EXEC_LOCK:
            _FAST_CACHE[fkey] = (run, stochastic, kernel)
        if _OBS_SINK[0] is not None:
            _obs_dispatch("gemm", gp, float(m) * k * n, False, kernel)
        with jax.named_scope(kernel):
            return run(x, w, key) if stochastic else run(x, w)

    xf2 = x.reshape((-1, k))
    with jax.named_scope(_plan_kernel(plan)):
        if isinstance(plan, MeshPlan):
            forward = _mesh_forward(gp, plan, preserve_dtype=False)
            out = _ste_matmul(forward)(xf2, w)
            return out.reshape(lead + (n,))
        forward, takes_eps = _cim_forward(gp, plan, noise_kind, stochastic,
                                          fused=False)
        if takes_eps:
            eps = surrogate_noise(key, (xf2.shape[0], n), jnp.float32,
                                  noise_kind)
            out = _ste_matmul_eps(forward)(xf2, w, eps)
        else:
            out = _ste_matmul(forward)(xf2, w)
    return out.reshape(lead + (n,))


def approx_matmul(x: jnp.ndarray, w: jnp.ndarray, spec: MultiplierSpec,
                  surrogate: SurrogateModel, mode: str = "surrogate",
                  key: Optional[jax.Array] = None,
                  interpret: Optional[bool] = None,
                  block: Optional[Tuple[int, int, int]] = None) -> jnp.ndarray:
    """Approximate x @ w with straight-through exact gradients.

    Back-compat wrapper over `cim_matmul` (the dispatch engine entry).
    """
    gp = GemmParams.from_spec(spec, surrogate, mode)
    return cim_matmul(x, w, gp, key, interpret=interpret, block=block)


# ---------------------------------------------------------------------------
# Conv frontend: cim_conv2d (implicit-GEMM convolution, DESIGN.md §9)
# ---------------------------------------------------------------------------


def cim_conv2d(x: jnp.ndarray, w: jnp.ndarray, gp: GemmParams,
               key: Optional[jax.Array] = None, *,
               kh: int = 3, kw: int = 3, stride: int = 1,
               noise_kind: str = "normal",
               interpret: Optional[bool] = None,
               block: Optional[Tuple[int, int, int]] = None,
               cached: bool = True,
               mesh: Optional[Mesh] = None,
               x_spec=None, w_spec=None) -> jnp.ndarray:
    """Dispatch + execute one approximate convolution (macro semantics).

    x: (B, H, W, C) float; w: (kh*kw*C, N) float with tap-major rows
    (the `im2col_nhwc` column order, i.e. the same weight layout
    `models/cnn.py` has always used).  Returns float32 (B, OH, OW, N)
    with straight-through exact-float-conv gradients.

    Hardware/exact modes run the implicit-GEMM Pallas kernels
    (kernels/conv_gemm.py): the kh*kw patch gather happens inside the
    pallas_call via index arithmetic, so the (M, kh*kw*C) im2col tensor
    never exists in HBM — ~kh*kw x less activation traffic than the
    materialized path.  The integer (hardware-mode) result is
    bit-identical to `im2col + cim_matmul`; that holds when
    stride <= min(kh, kw) (every input pixel reaches >= 1 patch, so the
    max-based per-tensor scale agrees), and `plan_conv` *enforces* it —
    larger strides, other modes, and planes too large for the VMEM
    footprint model all fall back to `conv_im2col`
    (materialize + the GEMM engine).  Executes through the same
    zero-retrace executable cache as the GEMM frontends, keyed on the
    conv-bucketed (B, H, W, C, kh, kw, stride) shape.

    With `mesh`, execution is shard_map-partitioned (DESIGN.md §11):
    `x_spec` shards the batch dim, `w_spec` (a (K, N)-style pair over
    the (kh*kw*C, N) weight) picks input-channel (psum) or out-channel
    (collective-free) tensor parallelism — bit-identical to the
    unsharded call for the integer modes on bit-safe geometries.
    """
    conv = ConvParams(kh, kw, stride)
    b, h, w_, c = x.shape
    n = w.shape[-1]
    if w.shape[0] != kh * kw * c:
        raise ValueError(
            f"weight rows {w.shape[0]} != kh*kw*C = {kh}*{kw}*{c}")
    if mesh is not None:
        if gp.fault is not None:
            raise ValueError(
                "fault injection is not supported on the mesh shard_map "
                "path (the partial/fused shard kernels quantize their "
                "words in-kernel); drop the mesh or the fault config")
        # every call: bit-safety and divisibility depend on the EXACT
        # geometry, which the conv-bucketed front-cache key masks
        _check_mesh_conv(gp.mode, h, w_, conv, b, c, n, mesh, x_spec,
                         w_spec)
    if cached:
        fkey = (("conv2d", gp, conv, x.dtype, w.dtype, key is not None,
                 noise_kind, interpret, block, jax.default_backend(),
                 mesh, _canon_spec(x_spec), _canon_spec(w_spec))
                + autotune.bucket_conv(b, h, w_, c, kh, kw, stride)
                + (autotune.bucket(n),))
        hit = _FAST_CACHE.get(fkey)
        oh_, ow_ = conv_out_hw(h, w_, kh, kw, stride)
        macs = float(b) * oh_ * ow_ * kh * kw * c * n
        if hit is not None:
            run, stochastic, kernel = hit
            if _OBS_SINK[0] is not None:
                _obs_dispatch("conv", gp, macs, True, kernel)
            with jax.named_scope(kernel):
                return run(x, w, key) if stochastic else run(x, w)
    if gp.mode not in MODES:
        raise ValueError(f"mode {gp.mode!r} not in {MODES}")
    if gp.fault is not None:
        # every implicit conv kernel quantizes in-kernel from float, so
        # the stored-word fault surgery cannot reach it; as-fabricated
        # convs run the materialized fallback, whose inner GEMM routes
        # through the faultable LUT/log paths (gp.routing_spec)
        plan = _fault_conv_plan(conv, jax.default_backend())
    else:
        plan = plan_conv(gp.family, gp.mode, gp.bits, b, h, w_, c, n,
                         conv, interpret=interpret, block=block,
                         spec=gp.spec, mesh=mesh, x_spec=x_spec,
                         w_spec=w_spec)
    stochastic = (gp.mode in ("surrogate", "surrogate_fast")
                  and key is not None and (gp.c0 > 0.0 or gp.c1 > 0.0))
    if cached:
        run = _conv_executable_for(gp, plan, stochastic, noise_kind, x, w,
                                   b, h, w_, c, n)
        kernel = _plan_kernel(plan)
        with _EXEC_LOCK:
            _FAST_CACHE[fkey] = (run, stochastic, kernel)
        if _OBS_SINK[0] is not None:
            _obs_dispatch("conv", gp, macs, False, kernel)
        with jax.named_scope(kernel):
            return run(x, w, key) if stochastic else run(x, w)

    with jax.named_scope(_plan_kernel(plan)):
        if isinstance(plan, MeshPlan):
            return _ste_conv(_mesh_conv_forward(gp, plan), conv)(x, w)
        forward, takes_eps = _conv_forward(gp, plan, noise_kind,
                                           stochastic, (b, h, w_, c, n))
        if takes_eps:
            oh, ow = conv_out_hw(h, w_, conv.kh, conv.kw, conv.stride)
            eps = surrogate_noise(key, (b * oh * ow, n), jnp.float32,
                                  noise_kind)
            return _ste_conv_eps(forward, conv)(x, w, eps)
        return _ste_conv(forward, conv)(x, w)


# ---------------------------------------------------------------------------
# Attention frontend: cim_attention (DESIGN.md §13)
# ---------------------------------------------------------------------------


def cim_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  gp: GemmParams, *,
                  causal: bool = True, window: Optional[int] = None,
                  q_positions: Optional[jnp.ndarray] = None,
                  kv_positions: Optional[jnp.ndarray] = None,
                  kv_valid: Optional[jnp.ndarray] = None,
                  interpret: Optional[bool] = None,
                  block: Optional[Tuple[int, int]] = None,
                  cached: bool = True) -> jnp.ndarray:
    """Dispatch + execute one approximate attention (macro semantics).

    q: (B, Sq, H, D) float; k/v: (B, Skv, KH, D) float with
    H % KH == 0 (GQA; KH == H is plain MHA).  Returns float32
    (B, Sq, H, D) with straight-through exact-float-attention
    gradients (`attn_float` VJP).

    Both inner dots (QK^T and PV) run through the approximate CiM
    datapath selected by `gp` — the same quantize-on-load LUT-gather /
    nibble / log-domain machinery as the GEMM kernels, under
    online-softmax tiling so the (B, H, Sq, Skv) score tensor never
    touches HBM.  Masking: `causal`/`window` are static plan geometry;
    `q_positions` (B, Sq), `kv_positions` + `kv_valid` (B, Skv) are
    runtime operands defaulting to dense [0, S) positions / all-valid —
    ragged prefill and single-token decode reuse the dense executable.

    Integer modes only (`ATTN_MODES`); per-token scale requests and
    geometries every registry predicate rejects raise ValueError, and
    the models layer (`models/attention.py`) catches that and falls
    back to the float `_chunked_attn` path.  Executes through the same
    zero-retrace executable cache as the GEMM/conv frontends, keyed on
    `autotune.bucket_attn`.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(
            f"cim_attention wants (B, S, H, D) operands; got q.ndim="
            f"{q.ndim} k.ndim={k.ndim} v.ndim={v.ndim}")
    b, sq, heads, hd = q.shape
    skv, kv_heads = k.shape[1], k.shape[2]
    if k.shape != (b, skv, kv_heads, hd) or v.shape != k.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if heads % kv_heads:
        raise ValueError(
            f"GQA needs H % KH == 0, got {heads} % {kv_heads}")
    if gp.mode not in ATTN_MODES:
        raise ValueError(
            f"cim_attention runs the integer modes {ATTN_MODES}; "
            f"mode {gp.mode!r} stays on the float attention path")
    if gp.per_token:
        raise ValueError(
            "cim_attention quantizes per-(batch, head); per_token scale "
            "requests stay on the float attention path")
    ap = AttnParams(causal=causal, window=window)
    if q_positions is None:
        q_positions = jnp.broadcast_to(
            jnp.arange(sq, dtype=jnp.int32)[None], (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(
            jnp.arange(skv, dtype=jnp.int32)[None], (b, skv))
    if kv_valid is None:
        kv_valid = jnp.ones((b, skv), jnp.int32)
    if cached:
        fkey = (("attn", gp, ap, q.dtype, k.dtype, interpret, block,
                 jax.default_backend())
                + autotune.bucket_attn(b, heads, kv_heads, sq, skv, hd))
        hit = _FAST_CACHE.get(fkey)
        # QK^T + PV: two Skv-deep dots per (batch, head, query)
        macs = 2.0 * b * heads * sq * skv * hd
        if hit is not None:
            run, _, kernel = hit
            if _OBS_SINK[0] is not None:
                _obs_dispatch("attn", gp, macs, True, kernel)
            with jax.named_scope(kernel):
                return run(q, k, v, q_positions, kv_positions, kv_valid)
    plan = plan_attn(gp.family, gp.mode, gp.bits, b, heads, kv_heads, sq,
                     skv, hd, ap, interpret=interpret, block=block,
                     spec=gp.spec)
    if cached:
        run = _attn_executable_for(gp, plan, q, k, b, heads, kv_heads,
                                   sq, skv, hd)
        with _EXEC_LOCK:
            _FAST_CACHE[fkey] = (run, False, plan.entry.name)
        if _OBS_SINK[0] is not None:
            _obs_dispatch("attn", gp, macs, False, plan.entry.name)
    else:
        run = _build_attn_executable(gp, plan)
    with jax.named_scope(plan.entry.name):
        return run(q, k, v, q_positions, kv_positions, kv_valid)


# ---------------------------------------------------------------------------
# Model frontend: model_matmul (dtype-preserving, fake-quant STE)
# ---------------------------------------------------------------------------


def model_matmul(x: jnp.ndarray, w: jnp.ndarray, gp: GemmParams,
                 key: Optional[jax.Array] = None, *,
                 apply: bool = True,
                 noise_kind: str = NOISE_KIND,
                 cached: bool = True,
                 mesh: Optional[Mesh] = None,
                 x_spec=None, w_spec=None) -> jnp.ndarray:
    """The model-zoo execution path (cim_linear core), dispatcher-routed.

    Differences from `cim_matmul` (both deliberate, DESIGN.md §8):
    fake-quant STE (QAT: gradients flow through the quantizer), the
    activation dtype is preserved end-to-end (a bf16 stream stays bf16),
    and surrogate noise defaults to rademacher.  `apply=False` runs the
    exact int8 macro (mixed-macro allocation, DESIGN.md §4).  Executes
    through the same zero-retrace executable cache as `cim_matmul`.

    With `mesh` (integer modes with `apply=True` only — `cim_linear`
    routes here when an ambient mesh is present, DESIGN.md §11) the
    executable is shard_map-partitioned; the f32 mesh output is cast
    back to the activation dtype, preserving the model contract.
    """
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= int(s)
    k = x.shape[-1]
    n = w.shape[-1]
    if mesh is not None and not apply:
        mesh, x_spec, w_spec = None, None, None     # exact macro: GSPMD
    if mesh is not None and gp.per_token:
        raise ValueError(
            "per-token activation scales are not supported on the mesh "
            "shard_map path (global per-tensor scales are computed "
            "outside the shard); drop the mesh or per_token")
    if mesh is not None and gp.fault is not None:
        raise ValueError(
            "fault injection is not supported on the mesh shard_map "
            "path (the partial/fused shard kernels quantize their "
            "words in-kernel); drop the mesh or the fault config")
    if mesh is not None:
        # divisibility is not bucket-stable: validate the raw shape
        # before the bucketed front cache can answer
        _check_mesh_gemm(gp.mode, m, k, n, mesh, x_spec, w_spec)
    if cached:
        fkey = ("model", gp, x.dtype, w.dtype, x.ndim, autotune.bucket(m),
                autotune.bucket(k), autotune.bucket(n), key is not None,
                noise_kind, apply, jax.default_backend(),
                mesh, _canon_spec(x_spec), _canon_spec(w_spec))
        hit = _FAST_CACHE.get(fkey)
        if hit is not None:
            run, stochastic, kernel = hit
            if _OBS_SINK[0] is not None:
                _obs_dispatch("model_gemm", gp, float(m) * k * n, True,
                              kernel)
            with jax.named_scope(kernel):
                return run(x, w, key) if stochastic else run(x, w)
    mode = gp.mode if apply else "exact"
    plan = plan_gemm(gp.family, mode, gp.bits, m, k, n,
                     spec=gp.routing_spec, mesh=mesh, x_spec=x_spec,
                     w_spec=w_spec)
    stochastic = (apply and gp.mode in ("surrogate", "surrogate_fast")
                  and key is not None and (gp.c0 > 0.0 or gp.c1 > 0.0))
    if cached:
        run = _executable_for("model", gp, plan, stochastic, noise_kind,
                              apply, x, w, m, k, n)
        kernel = _plan_kernel(plan)
        with _EXEC_LOCK:
            _FAST_CACHE[fkey] = (run, stochastic, kernel)
        if _OBS_SINK[0] is not None:
            _obs_dispatch("model_gemm", gp, float(m) * k * n, False,
                          kernel)
        with jax.named_scope(kernel):
            return run(x, w, key) if stochastic else run(x, w)

    with jax.named_scope(_plan_kernel(plan)):
        if isinstance(plan, MeshPlan):
            forward = _mesh_forward(gp, plan, preserve_dtype=True)
            x2 = x.reshape((-1, k))
            return _ste_matmul(forward)(x2, w).reshape(lead + (n,))
        kind, f, flag = _model_forward(gp, plan, noise_kind, stochastic,
                                       apply, fused=False)
        if kind == "plain":
            return f(x, w, key)
        # STE kernel-backed paths must see a rank-2 x: the custom_vjp
        # backward does xf.T @ g, so flatten leading dims OUTSIDE the vjp
        x2 = x.reshape((-1, k))
        if flag:
            eps = surrogate_noise(key, (x2.shape[0], n), jnp.float32,
                                  noise_kind)
            out = _ste_matmul_eps(f)(x2, w, eps)
        else:
            out = _ste_matmul(f)(x2, w)
    return out.reshape(lead + (n,))
