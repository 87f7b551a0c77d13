"""The program's own spans and stages in a profiler trace: what the
host was doing in each idle gap of the device, and the device time of
each stage of the round.

The program opens host spans named ``fl.*`` (``repro.core.telemetry``
``SPANS``: ``fl.block`` with its args, dispatch and copy,
``fl.eval`` with its upload, dispatch, copy and report) and wraps each
stage of its round step in a ``jax.named_scope``
(``repro.core.engine.STAGES``), which the compiled HLO keeps in each
instruction's ``op_name`` metadata. ``reduce_program`` reads both from
the same trace that ``bench/tracing.py`` reduces, and leaves that
reduction as it is:

- per span name: count, median duration and median self time (the
  duration less what its child spans cover);
- the device seconds of each stage in the block program's runs, the
  heaviest ops no stage holds, and the runs' own length (what their
  ops do not cover is the device's idle time between them);
- the device's idle time inside the harness's ``run_block`` and
  ``eval`` spans, each piece put down to the innermost program span
  that holds it, and the part no program span holds.

A program without the spans or the scopes (an older commit) gives
empty tables, not an error.

Run as a script from the root of a checkout, it runs one cell as the
harness does (set-up, then ``--seconds`` of blocks with the profiler
off, then ``--trace-seconds`` of blocks traced) and prints one JSON
line with the reduction, the updates/s of the untraced and the traced
stretch, each span's mean host time up to the traced stretch (from the
program's registry), and the cost of a span with the profiler off:

    python bench/program_trace.py --workload paper.single --seed 7 \\
        --seconds 20
"""
from __future__ import annotations

import bisect
import collections
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    import os
    import sys
    from pathlib import Path
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        _ROOT / "bench" / ".cache" / "jax")

from bench import tracing
from bench.tracing import Event, TraceEvents

PREFIX = "fl."
STAGES = ("draws", "select", "gather", "downlink", "train", "faults",
          "uplink", "server", "telemetry")
OTHER = "other"
NO_SPAN = "no_program_span"
IDLE_IN = ("run_block", "eval")


def load_program_spans(path: str) -> List[Event]:
    """The host events of an ``.xplane.pb`` file whose names start with
    ``fl.``, sorted by start."""
    from jax.profiler import ProfileData
    out = [Event(e.name, e.start_ns, e.duration_ns)
           for plane in ProfileData.from_file(path).planes
           if not plane.name.startswith(tracing.DEVICE_PREFIX)
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    out.sort(key=lambda e: (e.start, -e.dur))
    return out


_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def stage_instructions(hlo_text: str) -> Dict[str, str]:
    """Map each instruction of a compiled HLO module to the outermost
    stage named in its ``op_name`` (a path such as
    ``jit(round_block)/while/body/closed_call/gather/gather``: the last
    part is the primitive, so it names no stage). Instructions with no
    stage map to ``other``."""
    wanted = set(STAGES)
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        parts = op.group(1).split("/")[:-1] if op else []
        out[m.group(1)] = next((p for p in parts if p in wanted), OTHER)
    return out


def innermost(spans: Sequence[Event]) -> List[Tuple[float, float, int]]:
    """Disjoint pieces ``(start, end, i)`` of the time the spans cover,
    each under the innermost span ``spans[i]`` that holds it; the
    spans sorted by start, longest first among equal starts, nested. A
    span's self time is the length of its pieces."""
    out: List[Tuple[float, float, int]] = []
    stack: List[int] = []
    cur = float("-inf")

    def close_until(t):
        nonlocal cur
        while stack and spans[stack[-1]].end <= t:
            top = stack.pop()
            if spans[top].end > cur:
                out.append((cur, spans[top].end, top))
                cur = spans[top].end

    for i, s in enumerate(spans):
        close_until(s.start)
        if stack and s.start > cur:
            out.append((cur, s.start, stack[-1]))
        stack.append(i)
        cur = max(cur, s.start)
    close_until(float("inf"))
    return out


def _attribute(gaps: Sequence[Tuple[float, float]],
               pieces: Sequence[Tuple[float, float, str]],
               into: Dict[str, float]) -> None:
    """Add each gap's overlap with each named piece to ``into[name]``,
    and the rest to ``into[NO_SPAN]``; both lists sorted, disjoint."""
    starts = [p[0] for p in pieces]
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][0] < b:
            lo, hi = max(a, pieces[i][0]), min(b, pieces[i][1])
            if hi > lo:
                into[pieces[i][2]] += hi - lo
                covered += hi - lo
            i += 1
        into[NO_SPAN] += (b - a) - covered


def reduce_program(ev: TraceEvents, program_spans: Sequence[Event], *,
                   stage_of: Dict[str, str], block_module: str) -> dict:
    """The program's spans and stages in one trace (module doc).
    ``ev`` is ``bench.tracing.load``'s reading of the trace with the
    harness's spans, ``stage_of`` maps the block program's instructions
    to stages (``stage_instructions``). Seconds are per chip; the
    device tables are empty where the trace has no device ops."""
    pieces = innermost(program_spans)
    self_ns = [0.0] * len(program_spans)
    for a, b, i in pieces:
        self_ns[i] += b - a
    durs: Dict[str, List[float]] = collections.defaultdict(list)
    selfs: Dict[str, List[float]] = collections.defaultdict(list)
    for s, own in zip(program_spans, self_ns):
        durs[s.name].append(s.dur)
        selfs[s.name].append(own)
    named = [(a, b, program_spans[i].name) for a, b, i in pieces]
    table = {name: {"count": len(d),
                    "median_ms": statistics.median(d) * 1e-6,
                    "median_self_ms": statistics.median(selfs[name])
                    * 1e-6}
             for name, d in sorted(durs.items())}
    n_chips = max(len(ev.ops), 1)
    stage_ns: Dict[str, float] = collections.defaultdict(float)
    unstaged: Dict[str, float] = collections.defaultdict(float)
    idle_ns: Dict[str, float] = collections.defaultdict(float)
    runs, module_ns = 0, 0.0
    holders = [s for s in ev.spans if s.name in IDLE_IN]
    # the block program's runs in the window ``tracing.reduce`` reads
    lo = min((s.start for s in ev.spans), default=float("-inf"))
    hi = max((s.end for s in ev.spans), default=float("inf"))
    for ops, mods in zip(ev.ops, ev.modules):
        by_name: Dict[str, List[Event]] = collections.defaultdict(list)
        for e in mods:
            if lo <= e.start <= hi \
                    and tracing.module_base(e.name) == block_module:
                by_name[e.name].append(e)
        main = max(by_name.values(), key=lambda es: sum(x.dur for x in es),
                   default=[])
        runs += len(main)
        module_ns += sum(e.dur for e in main)
        spans_of_main = sorted((e.start, e.end) for e in main)
        starts = [a for a, _ in spans_of_main]
        for e in ops:
            if any(c in e.name for c in tracing.CONTAINER_OPS):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.start < spans_of_main[i][1]:
                stage = stage_of.get(tracing.instr_name(e.name), OTHER)
                stage_ns[stage] += e.dur
                if stage == OTHER:
                    unstaged[tracing.op_label(e.name)] += e.dur
        busy = tracing.merge([(e.start, e.end) for e in ops])
        b_starts = [a for a, _ in busy]
        b_ends = [b for _, b in busy]
        for h in holders:
            near = busy[bisect.bisect_right(b_ends, h.start):
                        bisect.bisect_left(b_starts, h.end)]
            _attribute(tracing.gaps(tracing.clip(near, h.start, h.end),
                                    h.start, h.end), named, idle_ns)
    return {
        "spans": table,
        "block_runs": runs // n_chips,
        "module_s": module_ns * 1e-9 / n_chips,
        "stage_s": {k: v * 1e-9 / n_chips
                    for k, v in sorted(stage_ns.items())},
        "unstaged_ops": [[k, v * 1e-9 / n_chips] for k, v in sorted(
            unstaged.items(), key=lambda kv: -kv[1])[:5]],
        "idle_in_spans_s": sum(idle_ns.values()) * 1e-9 / n_chips,
        "idle_by_program_span": {
            k: v * 1e-9 / n_chips
            for k, v in sorted(idle_ns.items(), key=lambda kv: -kv[1])},
    }


# -- run as a script ----------------------------------------------------------

def span_off_cost_us(n: int = 100_000) -> Optional[float]:
    """Host microseconds of one program span's enter and exit with the
    profiler off; None where the program has no spans."""
    import time
    from repro.core import telemetry
    span = getattr(telemetry, "Span", None)
    if span is None:
        return None
    t0 = time.perf_counter()
    for _ in range(n):
        with span("fl.block.args"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def _span_means_ms() -> Optional[Dict[str, float]]:
    """Mean host ms of each span the program's registry has booked so
    far; None where the program keeps no span clock."""
    from repro.core.telemetry import REGISTRY
    stats = getattr(REGISTRY, "span_stats", None)
    if stats is None:
        return None
    return {k: v["seconds"] / v["count"] * 1e3
            for k, v in sorted(stats().items()) if v["count"]}


def _registry_bytes() -> Dict[str, Tuple[int, int, int]]:
    """(calls, h2d, d2h) per program kind, where the program counts
    bytes."""
    from repro.core.telemetry import REGISTRY
    out: Dict[str, Tuple[int, int, int]] = {}
    for st in REGISTRY.stats():
        c, h, d = out.get(st["kind"], (0, 0, 0))
        out[st["kind"]] = (c + st["calls"], h + st.get("h2d_bytes", 0),
                           d + st.get("d2h_bytes", 0))
    return out


def run(name: str, seed: int, seconds: float, trace_seconds: float
        ) -> dict:
    """One cell's untraced and traced stretches and their reduction."""
    import glob
    import json
    import shutil
    import time

    import jax
    import numpy as np
    from bench import harness
    from repro.utils.compile_cache import enable_compile_cache

    spec = harness.cell_spec(name)
    device = harness.device_info(spec["cell"]["chips"], True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    run_ = harness.CellRun(spec, seed, tracer=jax.profiler.TraceAnnotation)
    k, C = run_.k, run_.C
    t = 0
    for _ in range(harness.SETUP_BLOCKS):
        run_.block(t)
        t += k

    def stretch(duration):
        nonlocal t
        times = []
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < duration:
            a = time.perf_counter()
            run_.block(t)
            times.append(time.perf_counter() - a)
            t += k
        return times

    plain = stretch(seconds)
    untraced_spans = _span_means_ms()
    before = _registry_bytes()
    trace_dir = harness.TRACE_DIR / f"program_{name}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    traced = stretch(trace_seconds)
    jax.profiler.stop_trace()
    after = _registry_bytes()
    hlo = run_.block_hlo(t)
    block_module = hlo.split(",", 1)[0].replace("HloModule", "").strip()
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    ev = tracing.load(path, harness.SPANS)
    red = tracing.reduce(ev, kernels=tracing.kernel_instructions(hlo),
                         block_module=block_module)
    prog = reduce_program(ev, load_program_spans(path),
                          stage_of=stage_instructions(hlo),
                          block_module=block_module)
    shutil.rmtree(trace_dir, ignore_errors=True)
    rounds = max(prog["block_runs"], 1) * k
    n = len(traced)
    per = {kind: {"h2d_bytes": (after[kind][1] - before.get(
                      kind, (0, 0, 0))[1]) / n,
                  "d2h_bytes": (after[kind][2] - before.get(
                      kind, (0, 0, 0))[2]) / n}
           for kind in after}
    out = {
        "device": device, "block_module": block_module,
        "blocks": {"untraced": len(plain), "traced": n},
        "updates_per_s": {"untraced": len(plain) * k * C / sum(plain),
                          "traced": n * k * C / sum(traced)},
        "block_p95_ms": float(np.percentile(plain, 95)) * 1e3,
        "round_device_ms": None if red is None or not red[
            "block_program_runs"] else red["block_program_s"]
        / (red["block_program_runs"] * k) * 1e3,
        "busy_s": None if red is None else red["busy_s"],
        "window_s": None if red is None else red["window_s"],
        "idle_gaps": None if red is None else red["idle_gaps"],
        "module_ms_per_round": prog["module_s"] / rounds * 1e3,
        "stage_ms_per_round": {s: v / rounds * 1e3
                               for s, v in prog["stage_s"].items()},
        "unstaged_ops_ms_per_round": [[op, v / rounds * 1e3] for op, v
                                      in prog["unstaged_ops"]],
        "untraced_span_mean_ms": untraced_spans,
        "spans": prog["spans"],
        "idle_in_spans_s": prog["idle_in_spans_s"],
        "idle_by_program_span": prog["idle_by_program_span"],
        "bytes_per_block_by_kind": per,
    }
    spans_per_block = sum(v["count"] for v in prog["spans"].values()) / n
    cost = span_off_cost_us()
    out["span_off_cost"] = None if cost is None else {
        "us_per_span": cost, "spans_per_block": spans_per_block,
        "share_of_block_p95": cost * 1e-3 * spans_per_block
        / out["block_p95_ms"]}
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace-seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    run(args.workload, args.seed, args.seconds, args.trace_seconds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
