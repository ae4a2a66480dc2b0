"""Reduce a profiler trace (`.xplane.pb`) to device busy and idle time,
time by device operation, and idle gaps named by what the host did.

Device operations are the events of the "XLA Ops" line of each
`/device:<kind>:<n>` plane.  A trace recorded on the CPU has no device
plane; there the operations are the host events that carry an `hlo_op`
stat, so the same reduction can be checked on a CPU recording.  A TPU
event is named by its HLO text (`%mitchell_matmul_fused.42 = f32[...]
custom-call(...)`); operations are grouped by kind, the instruction
name without its number (`mitchell_matmul_fused`).  Control-flow ops
(`while`, `conditional`, `call`) enclose the ops they run: they count
towards busy time but not as an operation of their own.

The traced window is the harness's `window` annotation on the host.
Busy time is the union of operation intervals inside it; an idle gap
is a stretch of the window with no operation running, named by the
innermost harness annotation (`tick`, `admit`, `decode_round`, ...)
open on the host at the gap's midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
WINDOW = "window"
CONTAINERS = {"while", "conditional", "call"}
_HLO_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = ")


def op_kind(name: str) -> str:
    """`%fusion.12 = ...` and `fusion.12` -> `fusion`."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else re.sub(r"\.\d+$", "", name)


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]                # ns, on the trace's clock
    n_devices: int
    busy_ns: float                          # mean over devices
    op_ns: Dict[str, float]                 # by op kind, all devices
    idle_by_host: Dict[str, float]          # ns, mean over devices

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _stats(ev) -> Dict[str, str]:
    try:
        return {str(k): str(v) for k, v in ev.stats}
    except Exception:          # a stat the reader cannot decode
        return {}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def reduce(path: str, host_names: Sequence[str],
           min_gap_ns: int = 10_000) -> Reduced:
    """Reduce one trace file.  `host_names` are the annotation names of
    the harness (the `window` annotation bounds the reduction)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    on_device = any(DEVICE_PLANE.match(p.name) for p in planes)
    wanted = set(host_names) | {WINDOW}
    host: List[Tuple[int, int, str]] = []
    devices: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_dev:
                if line.name != "XLA Ops":
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    ops.append((int(ev.start_ns), int(ev.end_ns),
                                op_kind(ev.name)))
                continue
            for ev in line.events:
                if ev.name in wanted:
                    host.append((int(ev.start_ns), int(ev.end_ns), ev.name))
                elif not on_device and "hlo_op" in _stats(ev):
                    devices.setdefault("cpu", []).append(
                        (int(ev.start_ns), int(ev.end_ns),
                         op_kind(ev.name)))
    windows = [(a, b) for a, b, n in host if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no '{WINDOW}' annotation")
    lo, hi = windows[0]
    spans = sorted((a, b, n) for a, b, n in host if n != WINDOW)
    starts = [s[0] for s in spans]

    def host_at(t: int) -> str:
        # innermost open annotation: the latest-starting one covering t
        i = bisect.bisect_right(starts, t)
        best = None
        for a, b, n in reversed(spans[max(0, i - 64):i]):
            if a <= t < b and (best is None or a > best[0]):
                best = (a, b, n)
        return best[2] if best else "no_span"

    op_ns: Dict[str, float] = {}
    busy = 0.0
    idle: Dict[str, float] = {}
    for ops in devices.values():
        inside = [(a, b, n) for a, b, n in ops if b > lo and a < hi]
        for a, b, n in inside:
            if n not in CONTAINERS:
                op_ns[n] = op_ns.get(n, 0.0) + (min(b, hi) - max(a, lo))
        merged = clip(union([(a, b) for a, b, _ in inside]), lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= min_gap_ns:
                name = host_at((a + b) // 2)
                idle[name] = idle.get(name, 0.0) + (b - a)
    n_dev = len(devices)
    return Reduced(window=(lo, hi), n_devices=n_dev,
                   busy_ns=busy / n_dev, op_ns=op_ns,
                   idle_by_host={k: v / n_dev for k, v in idle.items()})


def op_time_ns(red: Reduced, pattern: str) -> Optional[float]:
    """Device time of the operations whose kind matches `pattern` (a
    regular expression, matched whole); None where none does."""
    rx = re.compile(pattern)
    hit = [n for n in red.op_ns if rx.fullmatch(n)]
    return sum(red.op_ns[n] for n in hit) if hit else None


def breakdown(red: Reduced) -> Dict[str, List]:
    """The ten device operations that took most time and the ten host
    activities under which the device sat idle longest (seconds)."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in idle]}
