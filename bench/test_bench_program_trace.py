"""The program's spans and stages as ``bench/program_trace.py`` reads
them: the spans in a real CPU profiler trace, the stage map of the
CPU-compiled block program of each cell, the reduction on a hand-made
trace, and the readers of the span metrics."""
import glob
import re

import jax
import numpy as np
import pytest

from bench import harness, program_trace, tracing
from bench.program_trace import NO_SPAN, OTHER
from bench.test_bench_rehearsal import SEED, run_tiny, tiny_spec
from bench.tracing import Event, TraceEvents

MS = 1e6  # ns
BLOCK = ("fl.block.args", "fl.block.dispatch", "fl.block.copy")
EVAL = ("fl.eval.upload", "fl.eval.dispatch", "fl.eval.copy",
        "fl.eval.report")


def _tiny_server():
    from repro.core.server import FederatedServer, FLConfig
    from repro.data.synthetic import generate_synthetic
    data = generate_synthetic(np.random.default_rng(0), n_clients=8,
                              alpha=0.5, beta=0.5)
    cfg = FLConfig(clients_per_round=4, local_steps=2, batch_size=4,
                   n_rounds=4, eval_every=2)
    return FederatedServer(cfg, data)


def test_program_spans_in_a_profiler_trace(tmp_path):
    srv = _tiny_server()
    # compile outside the trace
    srv._state, _ = srv.engine.run_block(srv._state, 0, 2)
    srv.evaluate()
    srv._state, _ = srv.engine.run_single(srv._state, 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("run_block"):
            srv._state, _ = srv.engine.run_block(srv._state, 2, 2)
        with jax.profiler.TraceAnnotation("eval"):
            srv.evaluate()
        srv._state, _ = srv.engine.run_single(srv._state, 4)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[-1]
    spans = program_trace.load_program_spans(path)
    names = [s.name for s in spans]
    assert sorted(names) == sorted(("fl.block", "fl.eval",
                                    "fl.round.dispatch") + BLOCK + EVAL)
    by = {s.name: s for s in spans}
    for parent, children in (("fl.block", BLOCK), ("fl.eval", EVAL)):
        p = by[parent]
        for c in children:
            assert p.start <= by[c].start and by[c].end <= p.end, c
        ends = [by[c].end for c in children]
        assert ends == sorted(ends)     # in the documented order
    # the benchmark's own reading keeps only its spans, so the metrics
    # and the idle gaps it had read the same
    ev = tracing.load(path, harness.SPANS)
    assert sorted(s.name for s in ev.spans) == ["eval", "run_block"]
    red = program_trace.reduce_program(ev, spans, stage_of={},
                                       block_module="jit_round_block")
    assert {n: t["count"] for n, t in red["spans"].items()} == {
        n: 1 for n in names}
    assert red["spans"]["fl.block"]["median_self_ms"] >= 0.0


def _fusions(hlo: str) -> dict:
    """Fusion instructions of a compiled module and their op_name."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s", line)
        if m and " fusion(" in line and "kind=k" in line:
            op = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


@pytest.mark.parametrize("name", ["paper.single", "full.single"])
def test_stage_instructions_cover_the_block_program(name):
    run = harness.CellRun(tiny_spec(name), SEED)
    hlo = run.block_hlo(0)
    stages = program_trace.stage_instructions(hlo)
    fusions = _fusions(hlo)
    in_step = [f for f, op in fusions.items()
               if "/while/body/closed_call/" in op]
    staged = [f for f in in_step if stages[f] != OTHER]
    assert len(staged) >= 0.95 * len(in_step) > 0
    # what no stage holds is the scan's own loop control (its counter,
    # the writes of each round's logs, the condition) or fusions the
    # CPU backend leaves without metadata
    for f, op in fusions.items():
        if stages[f] == OTHER:
            assert "/closed_call/" not in op, (f, op)
    assert {"draws", "select", "gather", "train", "uplink",
            "server"} <= set(stages.values())
    if name == "full.single":
        assert "faults" in set(stages[f] for f in in_step)


def _trace():
    """One chip. Harness spans: run_block 0-5 ms, eval 6-10 ms. Device:
    the block program 1-4 ms (a while op, a gather and a train fusion
    and a copy no stage holds), the eval program 7-8 ms, whose own
    ``fusion.1`` shares a name with the block program's."""
    ops = [
        Event("%while.1 = (f32[]) while(f32[] %t), body=%b", 1 * MS, 3 * MS),
        Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              1 * MS, 1 * MS),
        Event("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              2 * MS, 1.5 * MS),
        Event("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", 3.5 * MS,
              0.1 * MS),
        Event("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %q), kind=kLoop",
              7 * MS, 1 * MS),
    ]
    mods = [Event("jit_round_block(123)", 1 * MS, 3 * MS),
            Event("jit_mlp_accuracy(9)", 7 * MS, 1 * MS)]
    spans = [Event("run_block", 0, 5 * MS), Event("eval", 6 * MS, 4 * MS)]
    return TraceEvents(ops=[ops], modules=[mods], spans=spans)


def _program_spans():
    ms = [("fl.block", 0.2, 4.8), ("fl.block.args", 0.2, 0.5),
          ("fl.block.dispatch", 0.5, 0.9), ("fl.block.copy", 0.9, 4.7),
          ("fl.eval", 6.1, 9.9),
          ("fl.eval.upload", 6.1, 6.8), ("fl.eval.dispatch", 6.8, 7.0),
          ("fl.eval.copy", 7.0, 8.5), ("fl.eval.report", 8.5, 9.5)]
    out = [Event(n, a * MS, (b - a) * MS) for n, a, b in ms]
    return sorted(out, key=lambda e: (e.start, -e.dur))


def test_reduce_program_self_time_stages_and_idle():
    red = program_trace.reduce_program(
        _trace(), _program_spans(),
        stage_of={"fusion.1": "gather", "fusion.2": "train"},
        block_module="jit_round_block")
    sp = red["spans"]
    assert sp["fl.block"]["median_ms"] == pytest.approx(4.6)
    # 4.6 ms less the children's 0.3 + 0.4 + 3.8
    assert sp["fl.block"]["median_self_ms"] == pytest.approx(0.1)
    assert sp["fl.eval"]["median_self_ms"] == pytest.approx(0.4)
    assert sp["fl.block.copy"]["median_self_ms"] == pytest.approx(3.8)
    # the eval program's fusion.1 and the while op are left out
    assert red["block_runs"] == 1
    assert red["stage_s"] == {"gather": pytest.approx(0.001),
                              "train": pytest.approx(0.0015),
                              OTHER: pytest.approx(0.0001)}
    assert red["unstaged_ops"] == [["copy.3 copy", pytest.approx(0.0001)]]
    assert red["module_s"] == pytest.approx(0.003)
    idle = {k: v * 1e3 for k, v in red["idle_by_program_span"].items()}
    # idle in run_block: 0-1 and 4-5 ms; in eval: 6-7 and 8-10 ms
    assert red["idle_in_spans_s"] == pytest.approx(0.005)
    assert idle == {
        NO_SPAN: pytest.approx(0.6), "fl.block.args": pytest.approx(0.3),
        "fl.block.dispatch": pytest.approx(0.4),
        "fl.block.copy": pytest.approx(0.8),
        "fl.block": pytest.approx(0.1),
        "fl.eval.upload": pytest.approx(0.7),
        "fl.eval.dispatch": pytest.approx(0.2),
        "fl.eval.copy": pytest.approx(0.5),
        "fl.eval.report": pytest.approx(1.0),
        "fl.eval": pytest.approx(0.4)}


def test_reduce_program_without_program_spans_or_device():
    # an older program: no spans, no stages; a CPU trace: no device
    red = program_trace.reduce_program(_trace(), [], stage_of={},
                                       block_module="jit__lambda")
    assert red["spans"] == {} and red["block_runs"] == 0
    assert red["idle_by_program_span"] == {NO_SPAN: pytest.approx(0.005)}
    cpu = TraceEvents(ops=[], modules=[], spans=_trace().spans)
    red = program_trace.reduce_program(cpu, _program_spans(), stage_of={},
                                       block_module="jit_round_block")
    assert red["stage_s"] == {} and red["idle_by_program_span"] == {}
    assert red["spans"]["fl.eval.upload"]["count"] == 1


def test_innermost_pieces():
    spans = _program_spans()[:4]
    pieces = program_trace.innermost(spans)
    assert [(spans[i].name, round((b - a) / MS, 6))
            for a, b, i in pieces] == [
        ("fl.block.args", 0.3), ("fl.block.dispatch", 0.4),
        ("fl.block.copy", 3.8), ("fl.block", 0.1)]


def test_span_readers(monkeypatch):
    from repro.core import telemetry
    reg = telemetry.ProgramRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", reg)
    for name in ("block_dispatch_ms", "block_copy_ms", "eval_upload_ms"):
        assert harness.load_metric(name).read(None) is None
    reg.record_span("fl.block.dispatch", 0.002)
    reg.record_span("fl.block.dispatch", 0.004)
    reg.record_span("fl.block.copy", 0.0005)
    reg.record_span("fl.eval.upload", 0.003)
    read = {n: harness.load_metric(n).read(None)
            for n in ("block_dispatch_ms", "block_copy_ms",
                      "eval_upload_ms")}
    assert read == {"block_dispatch_ms": pytest.approx(3.0),
                    "block_copy_ms": pytest.approx(0.5),
                    "eval_upload_ms": pytest.approx(3.0)}
    # a program whose registry keeps no span clock
    monkeypatch.setattr(telemetry, "REGISTRY", object())
    assert harness.load_metric("block_copy_ms").read(None) is None


@pytest.mark.parametrize("name", ["paper.single", "full.single"])
def test_traced_run_reports_span_metrics(name):
    out, _ = run_tiny(name, trace=True)
    for m in ("block_dispatch_ms", "block_copy_ms", "eval_upload_ms"):
        assert out["metrics"][m]["value"] > 0.0
        assert out["metrics"][m]["unit"] == "ms"
