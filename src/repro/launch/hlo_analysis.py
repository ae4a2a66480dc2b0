"""Post-SPMD HLO cost model for the dry-run roofline.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, which
under-reports a 61-layer scanned model by ~60x (verified empirically in
EXPERIMENTS.md §Methodology).  This module parses ``compiled.as_text()``
(per-device, post-partitioning HLO) and computes:

  * flops            — 2 * |out| * contracted for every dot, with while
                       bodies multiplied by their trip counts (parsed
                       from the loop-condition constant), recursively
                       through fusions/calls/nested loops;
  * bytes            — sum over non-trivial ops of (operands + outputs),
                       the HBM-traffic proxy, same loop scaling;
  * collective_bytes — per-kind byte totals for all-gather / all-reduce
                       (x2 for the ring) / reduce-scatter / all-to-all /
                       collective-permute, same loop scaling.

This is a first-order model: fusion means `bytes` over-counts
intermediate traffic that stays in registers/VMEM, so we report it as an
upper bound; `flops` for dots is exact.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple


def xla_cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` (one dict; empty when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}


_DT_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "u1": 1,
}

_SHAPE_RE = re.compile(r"([a-z]\d*[a-z]*\d*)\[([\d,]*)\]")
# opcode = first `word(` token after the `=`; the type prefix may contain
# nested tuples and /*index=N*/ comments (which contain `=`), but never a
# `word(` pattern, so a non-greedy scan is safe.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)([a-z][\w\-]*)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


class HloModule:
    def __init__(self, text: str):
        self.comps: Dict[str, List[dict]] = {}
        self.shapes: Dict[str, str] = {}
        self._parse(text)
        self._cost_cache: Dict[str, Tuple[float, float, Dict[str, float]]] = {}

    # ---------------- parsing ----------------
    def _parse(self, text: str):
        cur: Optional[str] = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line or line.lstrip().startswith("//"):
                continue
            if not line.startswith(" ") and ("{" in line):
                m = _COMP_RE.match(line.strip())
                if m:
                    cur = m.group(1)
                    self.comps[cur] = []
                    continue
            m = _INSTR_RE.match(line)
            if m and cur is not None:
                name, type_str, opcode, rest = m.groups()
                instr = {"name": name, "type": type_str, "op": opcode,
                         "rest": rest}
                self.comps[cur].append(instr)
                self.shapes[name] = type_str
        # ENTRY computation name: jax uses main*
        self.entry = next((c for c in self.comps if c.startswith("main")),
                          list(self.comps)[-1] if self.comps else None)

    def _operands(self, instr) -> List[str]:
        # operand names up to the closing paren of the op call
        head = instr["rest"].split(")")[0]
        return re.findall(r"%([\w.\-]+)", head)

    def _called(self, instr) -> List[str]:
        out = []
        for key in ("calls=", "body=", "condition=", "branch_computations={"):
            for m in re.finditer(re.escape(key) + r"\{?%?([\w.\-]+)",
                                 instr["rest"]):
                out.append(m.group(1))
        return out

    def _trip_count(self, cond_comp: str) -> int:
        consts = []
        for instr in self.comps.get(cond_comp, []):
            if instr["op"] == "constant" and "s32" in instr["type"]:
                m = re.search(r"constant\((-?\d+)", "constant(" + instr["rest"])
                if m:
                    consts.append(int(m.group(1)))
            # constants may be folded into a fused compare computation
            for sub in self._called(instr):
                for i2 in self.comps.get(sub, []):
                    if i2["op"] == "constant" and "s32" in i2["type"]:
                        m = re.search(r"\((-?\d+)", i2["rest"])
                        if m:
                            consts.append(int(m.group(1)))
        return max([c for c in consts if c > 0], default=1)

    # ---------------- costing ----------------
    def _dot_flops(self, instr) -> float:
        out_elems = 1
        for d in _shape_dims(instr["type"]):
            out_elems *= d
        ops = self._operands(instr)
        if not ops:
            return 0.0
        lhs_dims = _shape_dims(self.shapes.get(ops[0], ""))
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr["rest"])
        contracted = 1
        if m and lhs_dims:
            for i in m.group(1).split(","):
                if i and int(i) < len(lhs_dims):
                    contracted *= lhs_dims[int(i)]
        return 2.0 * out_elems * contracted

    _SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast", "after-all", "partition-id", "replica-id"}

    def comp_cost(self, comp: str):
        """Returns (flops, bytes, {collective kind: bytes}) for one call."""
        if comp in self._cost_cache:
            return self._cost_cache[comp]
        self._cost_cache[comp] = (0.0, 0.0, {})  # cycle guard
        flops = 0.0
        byts = 0.0
        colls: Dict[str, float] = {}
        for instr in self.comps.get(comp, []):
            op = instr["op"]
            if op == "while":
                body, cond = None, None
                mb = re.search(r"body=%?([\w.\-]+)", instr["rest"])
                mc = re.search(r"condition=%?([\w.\-]+)", instr["rest"])
                trip = self._trip_count(mc.group(1)) if mc else 1
                if mb:
                    f, b, c = self.comp_cost(mb.group(1))
                    flops += trip * f
                    byts += trip * b
                    for k, v in c.items():
                        colls[k] = colls.get(k, 0.0) + trip * v
                continue
            if op == "conditional":
                subs = self._called(instr)
                if subs:
                    costs = [self.comp_cost(s) for s in subs]
                    f = max(c[0] for c in costs)
                    b = max(c[1] for c in costs)
                    flops += f
                    byts += b
                continue
            if op in ("fusion", "call", "custom-call", "async-start"):
                # fusion internals stay in registers: count their flops and
                # collectives, but HBM bytes come from the fusion op's own
                # operands/output (the generic branch below)
                for sub in self._called(instr):
                    f, b, c = self.comp_cost(sub)
                    flops += f
                    if op in ("call", "async-start"):
                        byts += b
                    for k, v in c.items():
                        colls[k] = colls.get(k, 0.0) + v
            if op == "dot":
                flops += self._dot_flops(instr)
            base = op.replace("-start", "")
            if base in _COLLECTIVES:
                sz = _shape_bytes(instr["type"])
                for o in self._operands(instr):
                    sz = max(sz, _shape_bytes(self.shapes.get(o, "")))
                factor = 2.0 if base == "all-reduce" else 1.0
                colls[base] = colls.get(base, 0.0) + factor * sz
                byts += sz
                continue
            if op not in self._SKIP_BYTES:
                sz = _shape_bytes(instr["type"])
                seen = set()
                for o in self._operands(instr):
                    if o not in seen:
                        sz += _shape_bytes(self.shapes.get(o, ""))
                        seen.add(o)
                byts += sz
        self._cost_cache[comp] = (flops, byts, colls)
        return self._cost_cache[comp]

    def totals(self):
        f, b, c = self.comp_cost(self.entry)
        return {"flops": f, "bytes": b, "collectives": c,
                "collective_bytes": sum(c.values())}


def analyze(hlo_text: str) -> dict:
    return HloModule(hlo_text).totals()
