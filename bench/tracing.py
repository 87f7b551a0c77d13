"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time and idle gaps, time per program and per kernel,
and the host span each idle gap fell in.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
A TPU trace holds one plane per chip (``/device:TPU:<n>``) with the
lines ``XLA Modules`` (one event per program run) and ``XLA Ops`` (one
event per HLO instruction run, named by the instruction's text); the
host planes hold the benchmark's own ``TraceAnnotation`` spans. Both
are on one clock.

Kernels are found by where they come from: the compiled HLO of the
block program records, for each ``tpu_custom_call`` instruction, the
Python stack that built it; the package under ``kernels/`` on that
stack names the kernel (``kernel_instructions``). The trace's op events
carry the instruction names.
"""
from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside_spans"
CONTAINER_OPS = (" while(", " conditional(", " call(")


@dataclass
class Event:
    name: str
    start: float   # ns
    dur: float     # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class TraceEvents:
    """What the reduction needs from one trace: per chip, its op and
    program events; and the host spans by name."""
    ops: List[List[Event]] = field(default_factory=list)
    modules: List[List[Event]] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)


def load(path: str, span_names: Sequence[str]) -> TraceEvents:
    """Read an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = TraceEvents()
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [Event(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            out.ops.append(ops)
            out.modules.append(mods)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        out.spans.append(
                            Event(e.name, e.start_ns, e.duration_ns))
    out.spans.sort(key=lambda e: e.start)
    return out


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of ``busy`` (merged) within [lo, hi]."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def span_at(spans: Sequence[Event], starts: Sequence[float],
            t: float) -> str:
    """Name of the host span that holds ``t``; the spans are sorted by
    start and do not overlap (``starts`` lists their starts)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i].end >= t:
        return spans[i].name
    return OUTSIDE


def instr_name(op_text: str) -> str:
    """``%fusion.146 = f32[...] fusion(...)`` -> ``fusion.146``."""
    head = op_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_label(op_text: str) -> str:
    """Short label of an op for the breakdown: instruction name and the
    opcode (custom calls by their target)."""
    name = instr_name(op_text)
    rest = op_text.split(" = ", 1)[1] if " = " in op_text else ""
    if "tpu_custom_call" in rest:
        return f"{name} tpu_custom_call"
    m = re.search(r"\)?\}?\s([a-z][a-z0-9\-]*)\(", rest)
    return f"{name} {m.group(1)}" if m else name


def module_base(name: str) -> str:
    """``jit__lambda(5578243261064969515)`` -> ``jit__lambda``."""
    return name.split("(", 1)[0]


def kernel_instructions(hlo_text: str) -> Dict[str, str]:
    """Map each ``tpu_custom_call`` instruction of a compiled HLO module
    to the kernel package (``kernels/<name>/``) whose code built it."""
    tables: Dict[str, Dict[int, str]] = {}
    section = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames"):
            section = s
            tables[s] = {}
            continue
        if not s:
            section = None
            continue
        if section is not None:
            m = re.match(r"(\d+)\s+(.*)", s)
            if m:
                tables[section][int(m.group(1))] = m.group(2)
            else:
                section = None
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    locs = {}
    for k, v in tables.get("FileLocations", {}).items():
        m = re.search(r"file_name_id=(\d+)", v)
        if m:
            locs[k] = int(m.group(1))
    frames = {}
    for k, v in tables.get("StackFrames", {}).items():
        m = re.search(r"file_location_id=(\d+)\s+parent_frame_id=(\d+)", v)
        if m:
            frames[k] = (int(m.group(1)), int(m.group(2)))

    def kernel_of(frame_id: int) -> Optional[str]:
        seen = set()
        while frame_id in frames and frame_id not in seen:
            seen.add(frame_id)
            loc, parent = frames[frame_id]
            path = files.get(locs.get(loc, -1), "")
            m = re.search(r"/kernels/([A-Za-z0-9_]+)/", path)
            if m:
                return m.group(1)
            if parent == frame_id:
                break
            frame_id = parent
        return None

    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=", line)
        f = re.search(r"stack_frame_id=(\d+)", line)
        if m and f:
            k = kernel_of(int(f.group(1)))
            if k is not None:
                out[m.group(1)] = k
    return out


def reduce(ev: TraceEvents, *, kernels: Dict[str, str],
           block_module: str) -> Optional[dict]:
    """Device busy and idle over the traced window, time per kernel and
    in the block program, the heaviest ops and the idle time by host
    span. The window runs from the first host span's start to the last
    one's end. Returns None when the trace holds no device op."""
    if not ev.spans or not any(ev.ops):
        return None
    lo = min(s.start for s in ev.spans)
    hi = max(s.end for s in ev.spans)
    n_chips = len(ev.ops)
    busy_ns = 0.0
    idle_by_span: Dict[str, float] = collections.defaultdict(float)
    kernel_ns: Dict[str, float] = collections.defaultdict(float)
    kernel_calls: Dict[str, int] = collections.defaultdict(int)
    op_ns: Dict[str, float] = collections.defaultdict(float)
    block_ns, block_runs = 0.0, 0
    starts = [s.start for s in ev.spans]
    info: Dict[str, Tuple[Optional[str], Optional[str]]] = {}
    for ops, mods in zip(ev.ops, ev.modules):
        busy = merge(clip([(e.start, e.end) for e in ops], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        for a, b in gaps(busy, lo, hi):
            idle_by_span[span_at(ev.spans, starts, (a + b) / 2)] += b - a
        for e in ops:
            if e.end < lo or e.start > hi:
                continue
            if e.name not in info:
                container = any(c in e.name for c in CONTAINER_OPS)
                kern = kernels.get(instr_name(e.name)) \
                    if "tpu_custom_call" in e.name else None
                info[e.name] = (None if container else op_label(e.name),
                                kern)
            label, kern = info[e.name]
            if label is not None:
                op_ns[label] += e.dur
            if kern is not None:
                kernel_ns[kern] += e.dur
                kernel_calls[kern] += 1
        by_name: Dict[str, List[Event]] = collections.defaultdict(list)
        for e in mods:
            if lo <= e.start <= hi and module_base(e.name) == block_module:
                by_name[e.name].append(e)
        if by_name:
            main = max(by_name.values(), key=lambda es: sum(x.dur
                                                             for x in es))
            block_ns += sum(e.dur for e in main)
            block_runs += len(main)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_chips,
        "kernel_s": {k: v * 1e-9 / n_chips for k, v in kernel_ns.items()},
        "kernel_calls": {k: v // n_chips for k, v in kernel_calls.items()},
        "block_program_s": block_ns * 1e-9 / n_chips,
        "block_program_runs": block_runs // n_chips,
        "device_ops": [[k, v * 1e-9 / n_chips] for k, v in top_ops],
        "idle_gaps": [[k, v * 1e-9 / n_chips] for k, v in top_idle],
    }
