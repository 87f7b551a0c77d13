"""Compile the engine-path Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler that ships with jaxlib compiles
for a topology that is only described, and refuses what the chip's
compiler would refuse — blocks that break Mosaic's (8, 128) tiling
rule, primitives Mosaic cannot lower, kernels that overflow VMEM.
Interpret mode (every other kernel test) sees none of that.

Each kernel compiles with ``interpret=False``, single and
scenario-batched, at the paper's size (C = 10 clients, P = 36 packets:
the 60-128-10 MLP, D = 9,098) and at C = 64, P = 4096 (D_up = 2^20).
The block sizes come from the kernels' own TPU rows: the tests report
a TPU backend to them through ``monkeypatch``. The topology is
described inside a fixture, never while a module is imported, so every
test worker collects the same tests and only the one that runs this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fec_recover.fec_recover import fec_recover_call
from repro.kernels.netsim_mask.netsim_mask import netsim_mask_call
from repro.kernels.robust_agg.robust_agg import (robust_agg_batched_call,
                                                robust_agg_call)
from repro.kernels.uplink_fused.uplink_fused import (
    uplink_fused_batched_call, uplink_fused_call)

F = 256
SIZES = [(10, 36), (64, 4096)]
S = 4           # scenarios in the batched variants
GROUP = 8       # FEC group (RecoveryConfig default)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_rows(monkeypatch):
    """The kernels pick their TPU block sizes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _assert_compiles(fn, one_chip, shapes, lead=()):
    """Compile ``fn`` for the described chip at ``shapes`` (each
    ``(shape, dtype)``, with ``lead`` prepended) and find the kernel."""
    args = [jax.ShapeDtypeStruct(lead + s, dt, sharding=one_chip)
            for s, dt in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


f32, i32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("C,P", SIZES)
@pytest.mark.parametrize("batched", [False, True])
def test_uplink_fused_compiles(one_chip, tpu_rows, C, P, batched):
    call = uplink_fused_batched_call if batched else uplink_fused_call

    def fn(x, m, q, den, ef):
        return call(x, m, q, den, ef=ef, want_ssq=True, per_coord=False,
                    interpret=False)

    _assert_compiles(fn, one_chip, [((C, P, F), f32), ((C, P), f32),
                                    ((C,), f32), ((), f32),
                                    ((C, P, F), f32)],
                     lead=(S,) if batched else ())


@pytest.mark.parametrize("C,P", SIZES)
@pytest.mark.parametrize("batched", [False, True])
def test_robust_agg_compiles(one_chip, tpu_rows, C, P, batched):
    call = robust_agg_batched_call if batched else robust_agg_call

    def fn(x, m, q, den, scr, trg, ef, g, wpos):
        return call(x, m, q, den, scr, trg, ef=ef, g=g, w_pos=wpos,
                    trim_k=1, per_coord=False, interpret=False)

    _assert_compiles(fn, one_chip, [((C, P, F), f32), ((C, P), f32),
                                    ((C,), f32), ((), f32), ((), f32),
                                    ((), f32), ((C, P, F), f32),
                                    ((C,), f32), ((C,), f32)],
                     lead=(S,) if batched else ())


@pytest.mark.parametrize("C,P", SIZES)
@pytest.mark.parametrize("batched", [False, True])
def test_netsim_mask_compiles(one_chip, tpu_rows, C, P, batched):
    def fn(*a):
        return netsim_mask_call(*a, interpret=False)

    # the batched variant is pallas_call's own vmap rule (ops.py)
    _assert_compiles(jax.vmap(fn) if batched else fn, one_chip,
                     [((C, P), f32), ((C, P), f32), ((C,), i32),
                      ((C,), f32), ((C,), f32), ((C,), f32), ((C,), f32)],
                     lead=(S,) if batched else ())


@pytest.mark.parametrize("C,P", SIZES)
@pytest.mark.parametrize("batched", [False, True])
def test_fec_recover_compiles(one_chip, tpu_rows, C, P, batched):
    gn = -(-P // GROUP)

    def fn(m, par):
        return fec_recover_call(m, par, group=GROUP, interpret=False)

    _assert_compiles(jax.vmap(fn) if batched else fn, one_chip,
                     [((C, gn * GROUP), f32), ((C, gn), f32)],
                     lead=(S,) if batched else ())


def _bench_tracing():
    """``bench/tracing.py`` by path: the benchmark's kernel map."""
    import importlib.util
    import sys
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "bench" / "tracing.py"
    spec = importlib.util.spec_from_file_location("bench_tracing", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def _kernel_case(kernel, C, P):
    """(fn, shapes) of one engine kernel's call at (C, P)."""
    gn = -(-P // GROUP)
    return {
        "uplink_fused": (
            lambda x, m, q, den, ef: uplink_fused_call(
                x, m, q, den, ef=ef, want_ssq=True, per_coord=False,
                interpret=False),
            [((C, P, F), f32), ((C, P), f32), ((C,), f32), ((), f32),
             ((C, P, F), f32)]),
        "robust_agg": (
            lambda x, m, q, den, scr, trg, ef, g, wpos: robust_agg_call(
                x, m, q, den, scr, trg, ef=ef, g=g, w_pos=wpos, trim_k=1,
                per_coord=False, interpret=False),
            [((C, P, F), f32), ((C, P), f32), ((C,), f32), ((), f32),
             ((), f32), ((), f32), ((C, P, F), f32), ((C,), f32),
             ((C,), f32)]),
        "netsim_mask": (
            lambda *a: netsim_mask_call(*a, interpret=False),
            [((C, P), f32), ((C, P), f32), ((C,), i32), ((C,), f32),
             ((C,), f32), ((C,), f32), ((C,), f32)]),
        "fec_recover": (
            lambda m, par: fec_recover_call(m, par, group=GROUP,
                                            interpret=False),
            [((C, gn * GROUP), f32), ((C, gn), f32)]),
    }[kernel]


@pytest.mark.parametrize("kernel", ["uplink_fused", "robust_agg",
                                    "netsim_mask", "fec_recover"])
def test_kernel_custom_calls_carry_their_names(one_chip, tpu_rows,
                                               kernel):
    """Each kernel's ``pallas_call`` ``name=`` names its custom call in
    the compiled HLO (and so its op events in a profiler trace), and
    the benchmark's stack-frame map agrees."""
    fn, shapes = _kernel_case(kernel, *SIZES[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.split("=", 1)[0].strip().lstrip("%")
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, hlo[:500]
    assert all(c.split(".")[0] == kernel for c in calls), calls
    by_frames = _bench_tracing().kernel_instructions(hlo)
    assert {c: by_frames.get(c) for c in calls} == {c: kernel
                                                    for c in calls}
