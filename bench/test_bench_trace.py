"""The trace reduction on a small hand-made event list, the kernel map
from compiled HLO text, and the FLOP and byte counts against shapes
worked out by hand."""
import pytest

from bench import flops, tracing
from bench.tracing import Event, TraceEvents

MS = 1e6  # ns


def _trace():
    """One chip; window 0-10 ms from the host spans. Device ops: the
    block program 1-4 ms (a while op over two fusions and one kernel),
    the eval program 7-8 ms."""
    ops = [
        Event("%while.1 = (f32[]) while(f32[] %t), body=%b", 1 * MS, 3 * MS),
        Event("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              1 * MS, 1 * MS),
        Event('%closed_call.13 = (f32[1,36,256]) custom-call(f32[1] %x), '
              'custom_call_target="tpu_custom_call"', 2 * MS, 0.5 * MS),
        Event("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              3 * MS, 1 * MS),
        Event("%dot.2 = f32[4]{0} dot(f32[4] %a, f32[4] %b)", 7 * MS, MS),
    ]
    mods = [Event("jit__lambda(123)", 1 * MS, 3 * MS),
            Event("jit_mlp_accuracy(9)", 7 * MS, 1 * MS)]
    spans = [Event("run_block", 0, 5 * MS), Event("flush", 5 * MS, 1 * MS),
             Event("eval", 6 * MS, 4 * MS)]
    return TraceEvents(ops=[ops], modules=[mods], spans=spans)


def test_reduce_busy_idle_and_spans():
    red = tracing.reduce(_trace(), kernels={"closed_call.13": "uplink_fused"},
                         block_module="jit__lambda")
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.004)       # 1-4 ms and 7-8 ms
    idle = dict(red["idle_gaps"])
    # gaps: 0-1 (run_block), 4-7 split at 5.5 -> midpoint in flush,
    # 8-10 (eval)
    assert idle["run_block"] == pytest.approx(0.001)
    assert idle["flush"] == pytest.approx(0.003)
    assert idle["eval"] == pytest.approx(0.002)
    assert sum(idle.values()) == pytest.approx(0.006)


def test_reduce_kernels_modules_and_top_ops():
    red = tracing.reduce(_trace(), kernels={"closed_call.13": "uplink_fused"},
                         block_module="jit__lambda")
    assert red["kernel_s"] == {"uplink_fused": pytest.approx(0.0005)}
    assert red["kernel_calls"] == {"uplink_fused": 1}
    assert red["block_program_s"] == pytest.approx(0.003)
    assert red["block_program_runs"] == 1
    ops = dict(red["device_ops"])
    assert ops["fusion.7 fusion"] == pytest.approx(0.002)
    assert ops["closed_call.13 tpu_custom_call"] == pytest.approx(0.0005)
    assert not any(name.startswith("while.1") for name in ops)


def test_reduce_without_device_ops_is_none():
    ev = TraceEvents(ops=[[]], modules=[[]], spans=[Event("eval", 0, MS)])
    assert tracing.reduce(ev, kernels={}, block_module="m") is None


HLO = """HloModule jit__lambda, is_scheduled=true

FileNames
1 "/x/src/repro/core/engine.py"
2 "/x/src/repro/kernels/robust_agg/ops.py"
3 "/x/src/repro/kernels/fec_recover/fec_recover.py"

FunctionNames
1 "step"
2 "robust_uplink_round"
3 "fec_recover_call"

FileLocations
1 {file_name_id=1 function_name_id=1 line=1 end_line=1 column=0 end_column=1}
2 {file_name_id=2 function_name_id=2 line=2 end_line=2 column=0 end_column=1}
3 {file_name_id=3 function_name_id=3 line=3 end_line=3 column=0 end_column=1}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}

ENTRY %main {
  %closed_call.18 = f32[1] custom-call(f32[1] %a), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call" stack_frame_id=2}
  %fec_recover_call.14 = f32[1] custom-call(f32[1] %a), custom_call_target="tpu_custom_call", metadata={op_name="pallas_call" stack_frame_id=3}
  %fusion.2 = f32[1] fusion(f32[1] %a), metadata={op_name="x" stack_frame_id=1}
}
"""


def test_kernel_instructions_by_source_package():
    assert tracing.kernel_instructions(HLO) == {
        "closed_call.18": "robust_agg", "fec_recover_call.14": "fec_recover"}


def test_merge_and_gaps():
    busy = tracing.merge([(3, 4), (0, 1), (0.5, 2)])
    assert busy == [(0, 2), (3, 4)]
    assert tracing.gaps(busy, -1, 5) == [(-1, 0), (2, 3), (4, 5)]


def test_mlp_params_and_training_flops():
    d = flops.mlp_params(60, 128, 10)
    assert d == 60 * 128 + 128 + 128 * 10 + 10 == 9098
    # 20 steps x 32 samples x 6 D
    assert flops.train_flops_per_update(d, 20, 32) == 6 * 9098 * 640


def test_uplink_fused_bytes():
    # paper cell: C = 10, P = 36, F = 256, error feedback, ssq
    f, b = flops.uplink_fused(10, 36, 256, ef=True, want_ssq=True)
    n = 10 * 36 * 256
    assert b == 4 * (3 * n + 10 * 36 + 10 + 1 + 36 * 256 + 10 * 36)
    assert f == n * 8


def test_robust_agg_bytes():
    f, b = flops.robust_agg(10, 36, 256, ef=True, trim_k=1)
    n = 10 * 36 * 256
    assert b == 4 * (3 * n + 10 * 36 + 10 * 4 + 3 + 36 * 256)
    assert f == n * (3 + 3 + 6)


def test_round_readers_take_device_time_from_the_trace():
    from types import SimpleNamespace
    from bench import harness
    cfg = {"model": {"kind": "mlp", "d_in": 60, "d_hidden": 128,
                     "n_classes": 10},
           "fl": {"local_steps": 20, "batch_size": 32}}
    # 4 runs of a 10-round block program, 2 ms of device time in all
    trace = {"block_program_runs": 4, "block_program_s": 2e-3}
    ctx = SimpleNamespace(config=cfg, C=10, k=10, trace=trace,
                          peaks={"bf16_flops_per_s": 197e12})
    mfu = harness.load_metric("round_mfu").read(ctx)
    assert mfu == pytest.approx(
        100 * 6 * 9098 * 640 * 4 * 10 * 10 / 2e-3 / 197e12)
    assert harness.load_metric("round_device_ms").read(ctx) == \
        pytest.approx(2e-3 / 40 * 1e3)
    for t in (None, {"block_program_runs": 0, "block_program_s": 0.0}):
        ctx.trace = t
        assert harness.load_metric("round_mfu").read(ctx) is None
