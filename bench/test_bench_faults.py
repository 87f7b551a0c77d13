"""A run whose timed path is broken underneath reads ``correct`` false.

Each fault is planted in the program itself, before its round program
is built, and the harness then drives a whole run of the cell at its
own size (on the CPU, with the chip check skipped):

* ``unchanged``: the round step returns the state it was given;
* ``half_cohort``: half of the round's batch of client updates is left
  out of the server's aggregate, the mean taken over the rest;
* ``flip_upload``: the first cohort slot's upload is negated where the
  client produces it.

One chip runs each cell, so there is no exchange between chips to leave
out.
"""
import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import engine
from repro.kernels.robust_agg import ops as robust_ops
from repro.kernels.uplink_fused import ops as uplink_ops

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]]
FAULTS = ["unchanged", "half_cohort", "flip_upload"]


def plant(monkeypatch, fault: str) -> None:
    # fresh program caches: the round program is rebuilt with the fault
    monkeypatch.setattr(engine, "_STEP_CACHE", {})
    if fault == "unchanged":
        real = engine.make_round_step

        def make(cfg, cohort):
            step = real(cfg, cohort)

            def frozen(ctx, state, t):
                return state, step(ctx, state, t)[1]
            return frozen

        monkeypatch.setattr(engine, "make_round_step", make)
    elif fault == "half_cohort":
        for mod, name in ((uplink_ops, "uplink_round"),
                          (robust_ops, "robust_uplink_round")):
            real_round = getattr(mod, name)

            def halved(xp, mask, w, *a, real_round=real_round, **kw):
                keep = jnp.arange(w.shape[0]) < w.shape[0] // 2
                return real_round(xp, mask, w * keep, *a, **kw)

            monkeypatch.setattr(mod, name, halved)
    elif fault == "flip_upload":
        real = engine.flatten_clients
        monkeypatch.setattr(
            engine, "flatten_clients",
            lambda tree, n: real(tree, n).at[0].multiply(-1.0))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    plant(monkeypatch, fault)
    out = harness.run_cell(name, 2 ** 31 + 9, 0.0, False,
                           require_chip=False, cache=False,
                           log=lambda *a: None)
    assert out["correct"] is False, out["checks"]
