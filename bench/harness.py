"""The benchmark harness: one run of one cell.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the program's ``FLConfig`` fields, the
model, the generators of its data and client networks, and its plain
reference) and a traffic mix (``bench/traffic/<traffic>.json``: how the
job is driven, ``block_rounds`` rounds to a block). The configuration's
model kind names its module, ``bench/models/<kind>.py``, which makes the
data and the weights, builds the program and the reference (see
``bench/models``); the cell's comparison limits are in
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell or a model, so
a cell, a configuration, a model kind or a metric is added by adding
files.

A run:

1. set-up: the model's module makes the data, the client networks and
   the weights from the seed and builds the program's
   ``FederatedServer``; the harness drives it through its first three
   blocks: the first compiles, and all three are what the comparison
   reads;
2. the window: blocks, each ``block_rounds`` rounds (the server's
   ``eval_every``), the flush of its logs and the fairness eval after
   it, as ``FederatedServer.run`` runs them, until ``--seconds`` have
   passed. With
   ``--trace 1`` the profiler then records a few seconds more of
   blocks, which the per-layer metrics read;
3. the comparison: after the window, with the program's state freed,
   the plain reference (``bench/reference``) replays the first three
   blocks from the same seed, and the numbers of ``compare.py`` are held
   to the cell's limits.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from bench import models

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SPANS = ("run_block", "flush", "eval")
TRACE_SECONDS = 3.0
SETUP_BLOCKS = 3
TRACE_DIR = BENCH_DIR / ".traces"


class NoChip(RuntimeError):
    """JAX found no accelerator, too few chips, or a chip the peak table
    does not know."""


def process_start() -> float:
    """Wall-clock time at which this process started (``time.time()``
    scale), from ``/proc``; the harness's import time where that is not
    readable."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_IMPORTED = time.time()


# -- the cell's files ----------------------------------------------------------

def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def cell_spec(name: str) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[cell["config"]]
    reported = {m["name"] for m in bench["end_to_end"]
                if name in m.get("workloads", [name])}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"cell": cell,
            "config": load_json(ROOT / cfg_entry["file"]),
            "traffic": load_json(BENCH_DIR / "traffic"
                                 / f"{cell['traffic']}.json"),
            "limits": load_json(BENCH_DIR / "limits" / f"{name}.json"),
            "end_to_end": [m for m in bench["end_to_end"]
                           if m["name"] in reported],
            "per_layer": per_layer}


def peaks_for(kind: str) -> dict:
    peaks = load_json(BENCH_DIR / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json "
                     f"(have {sorted(peaks)})")
    return peaks[kind]


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip:
        if info["platform"] != "tpu":
            raise NoChip(f"no TPU: JAX found {info}")
        if info["count"] < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{info['count']}")
        peaks_for(info["kind"])
    return info


# -- one run -------------------------------------------------------------------

class CellRun:
    """The program under test, built for one cell and one seed, and the
    block loop the window drives."""

    def __init__(self, spec: dict, seed: int, tracer=None):
        from bench.traffic import networks

        cfg = spec["config"]
        self.model, self.fl = cfg["model"], cfg["fl"]
        self.module = models.load(self.model["kind"])
        self.k = spec["traffic"]["block_rounds"]
        self.C = self.fl["clients_per_round"]
        self.seed = seed % 2 ** 31
        self.tracer = tracer
        self.eval_s: List[float] = []
        built = self.module.build(cfg, self.seed, np.random.default_rng(seed),
                                  self.k, networks.generate)
        self.srv = built.pop("srv")
        # the rest is for the reference: params0, and what the module
        # keeps for it, as attributes of the run too
        self.built = built
        vars(self).update(built)

    def _span(self, name):
        import contextlib
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer(name)

    def block(self, t: int) -> np.ndarray:
        """``FederatedServer.run``'s loop body: one block of rounds from
        ``t``, the flush of its logs and the eval. Returns the logged
        losses."""
        from repro.core.server import RoundLog
        srv = self.srv
        with self._span("run_block"):
            srv._state, logs = srv.engine.run_block(srv._state, t, self.k)
        with self._span("flush"):
            for i, loss in enumerate(logs["loss"]):
                srv.history.append(RoundLog(t + i, float(loss)))
        a = time.perf_counter()
        with self._span("eval"):
            srv.history[-1].report = srv.evaluate()
        self.eval_s.append(time.perf_counter() - a)
        return np.asarray(logs["loss"])

    def snapshot(self, ef: bool) -> dict:
        """What the comparison reads after a set-up block: parameters,
        and (after the last) error-feedback memory (N, D), on the host."""
        st = self.srv._state
        return {"p": {k: np.asarray(v) for k, v in st.params.items()},
                "ef": np.asarray(st.ef_mem) if ef else None}

    def block_hlo(self, t: int) -> str:
        """Compiled HLO of the block program (from the compile cache)."""
        import jax.numpy as jnp
        ts = jnp.arange(t, t + self.k, dtype=jnp.int32)
        eng = self.srv.engine
        return eng._block._fn.lower(eng.ctx, self.srv._state,
                                    ts).compile().as_text()

    def free(self) -> None:
        if hasattr(self, "srv"):
            del self.srv


def reference(run: CellRun, **kw):
    """The plain reference of the run's configuration, data and seed;
    ``kw`` picks its matrix-product precision or plants a fault."""
    return run.module.reference(run.built, run.fl, run.model, **kw)


class CompileCounter:
    """Counts XLA compilations (and compile-cache loads) as JAX reports
    them while it is open, beside the program registry's own counts of
    compiled programs and of program-cache misses."""

    def __enter__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listener(name, duration, **kw):
            if name == event:
                self.n += 1

        def cache(name, **kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        self.hits = self.misses = 0
        self._listeners = (listener, cache)
        jax.monitoring.register_event_duration_secs_listener(listener)
        jax.monitoring.register_event_listener(cache)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listeners[0])
        jax.monitoring.unregister_event_listener(self._listeners[1])

    @staticmethod
    def registry() -> tuple:
        """(compiles, misses) the program registry has counted."""
        from repro.core.telemetry import REGISTRY
        st = REGISTRY.stats()
        return (sum(s["compiles"] for s in st),
                sum(s["misses"] for s in st))


def load_metric(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, cache: bool = True, log=None,
             spec: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line as a dict. Tests
    pass ``require_chip=False`` (and no compile cache) to run it on the
    CPU, and may pass a smaller ``spec`` than ``cell_spec(name)``."""
    with CompileCounter() as counter:
        return _run_cell(name, seed, seconds, trace, counter,
                         require_chip=require_chip, cache=cache,
                         log=log, spec=spec)


def _run_cell(name, seed, seconds, trace, counter, *, require_chip, cache,
              log, spec):
    t_start = process_start()
    log = log or (lambda *a: print(*a, flush=True))
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    from bench import compare

    spec = spec or cell_spec(name)
    device = device_info(spec["cell"]["chips"], require_chip)
    if cache:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        enable_compile_cache()
    tracer = jax.profiler.TraceAnnotation if trace else None

    t_dev = time.time()
    run = CellRun(spec, seed, tracer=tracer)
    t_built = time.time()
    k, C = run.k, run.C
    setup_losses, snaps, t_blocks = [], {}, []
    for b in range(SETUP_BLOCKS):
        setup_losses.append(run.block(b * k))
        t_blocks.append(time.time())
        if b in (0, SETUP_BLOCKS - 1):
            snaps[b + 1] = run.snapshot(ef=b > 0)
    run.eval_s.clear()
    setup_s = time.time() - t_start
    log(json.dumps({"setup": {
        "seconds": setup_s, "to_device": t_dev - t_start,
        "build": t_built - t_dev, "first_block": t_blocks[0] - t_built,
        "next_blocks": t_blocks[-1] - t_blocks[0],
        "compiles": counter.n, "cache_hits": counter.hits,
        "cache_misses": counter.misses}}))

    state = {"t": SETUP_BLOCKS * k}

    def blocks_for(duration: float):
        """Blocks until ``duration`` seconds have passed: their host
        seconds, how many logged only finite losses, and the span."""
        times, ok = [], 0
        w0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            losses = run.block(state["t"])
            b_ = time.perf_counter()
            times.append(b_ - a)
            ok += int(np.isfinite(losses).all())
            state["t"] += k
            if b_ - w0 >= duration:
                return times, ok, b_ - w0

    # the window
    c0, (r0, m0) = counter.n, counter.registry()
    times, losses_ok, window_s = blocks_for(seconds)
    n_blocks = len(times)
    eval_s = list(run.eval_s)
    r1, m1 = counter.registry()
    thirds = [c for c in np.array_split(np.asarray(times), 3) if len(c)]
    log(json.dumps({"window": {
        "blocks": n_blocks, "seconds": window_s,
        "updates_per_s_by_third": [len(c) * k * C / float(c.sum())
                                   for c in thirds],
        "xla_compiles": counter.n - c0, "registry_compiles": r1 - r0,
        "registry_misses": m1 - m0}}))
    if trace:
        # a traced stretch after the window: the profiler slows the host
        trace_dir = TRACE_DIR / name
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        trace_blocks = len(blocks_for(min(TRACE_SECONDS, seconds))[0])
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    device["memory_peak_bytes"] = peak

    metrics: Dict[str, dict] = {}
    breakdown = None
    updates = n_blocks * k * C
    if not trace:
        values = {"client_updates_per_s": updates / window_s,
                  "block_p95_ms": float(np.percentile(times, 95)) * 1e3,
                  "peak_hbm_mib": peak / 2 ** 20,
                  "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import tracing as tr
        hlo = run.block_hlo(state["t"])
        kernels = tr.kernel_instructions(hlo)
        block_module = hlo.split(",", 1)[0].replace("HloModule", "").strip()
        red = None
        files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if files:
            ev = tr.load(files[-1], SPANS)
            red = tr.reduce(ev, kernels=kernels, block_module=block_module)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = SimpleNamespace(
            config=spec["config"], C=C, k=k, trace=red, eval_s=eval_s,
            peaks=peaks_for(device["kind"]) if require_chip else None)
        for m in spec["per_layer"]:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        log(json.dumps({"trace": {"blocks": trace_blocks,
                                  "kernels": sorted(set(kernels.values())),
                                  "block_module": block_module}}))

    # the comparison, after the program's state is freed
    prog = compare.program_readings(setup_losses, snaps, SETUP_BLOCKS)
    run.free()
    ref = compare.reference_blocks(reference(run), run.params0, k,
                                   SETUP_BLOCKS)
    p0 = compare.as_float64(run.params0)
    numbers = compare.numbers(prog, ref, p0, k, SETUP_BLOCKS)
    log(json.dumps({"compared": numbers}))
    checks = compare.hold(numbers, spec["limits"])
    correct = all(c["ok"] for c in checks.values())

    out = {"correct": correct, "attempted": n_blocks,
           "failed": n_blocks - losses_ok, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n_: {"value": c["value"] if math.isfinite(c["value"])
                          else None, "limit": c["limit"]}
                     for n_, c in checks.items()}
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="One run of one benchmark "
                                 "cell (see PERF.md).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out, default=_jsonable), flush=True)
    for n_, c in out["checks"].items():
        print(f"check {n_}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


def _jsonable(x):
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    raise TypeError(type(x))


if __name__ == "__main__":
    sys.exit(main())
