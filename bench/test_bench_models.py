"""A configuration's client model comes from its model kind's module,
``bench/models/<kind>.py``: the MLP's module makes the same data,
client speeds, weights and reference as the calls it was moved from, a
kind is added by a file alone, and an unknown kind is refused at
set-up."""
import shutil

import jax
import numpy as np
import pytest

from bench import compare, harness, models
from bench.reference import fl_round
from bench.test_bench_rehearsal import CELLS, SEED, run_tiny, tiny_spec
from bench.traffic import networks, synthetic


@pytest.mark.parametrize("name", CELLS)
def test_mlp_module_builds_what_the_harness_built(name):
    spec = tiny_spec(name)
    cfg = spec["config"]
    model, fl, d, k = (cfg["model"], cfg["fl"], cfg["data"],
                       spec["traffic"]["block_rounds"])
    seed = SEED % 2 ** 31
    built = models.load("mlp").build(cfg, seed, np.random.default_rng(SEED),
                                     k, networks.generate)
    srv = built.pop("srv")

    # the calls the harness made before the model module
    rng = np.random.default_rng(SEED)
    tx, ty, sx, sy = synthetic.generate(
        rng, d["n_clients"], d["alpha"], d["beta"],
        max_samples=d["max_samples"], test_frac=d["test_frac"],
        sizes=d.get("sizes", "drawn"))
    speed, loss = networks.generate(rng, d["n_clients"], cfg["networks"])
    train = synthetic.padded_train(tx, ty)
    params = fl_round.init_params(jax.random.PRNGKey(seed)[None], model)

    for ours, theirs in zip(built["train"], train):
        np.testing.assert_array_equal(ours, theirs)
    for ours, theirs in zip((srv.data.train_x, srv.data.train_y,
                             srv.data.test_x, srv.data.test_y),
                            (tx, ty, sx, sy)):
        assert len(ours) == len(theirs)
        for a, b in zip(ours, theirs):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(built["upload_mbps"], speed)
    np.testing.assert_array_equal(srv.nets.upload_mbps, speed)
    np.testing.assert_array_equal(srv.nets.packet_loss, loss)
    assert set(built["params0"]) == set(params)
    for n, v in params.items():
        np.testing.assert_array_equal(built["params0"][n], np.asarray(v[0]))
        np.testing.assert_array_equal(np.asarray(srv.params[n]),
                                      np.asarray(v[0]))
    del srv

    scenario = {"seed": seed, "loss_rate": fl["tra"]["loss_rate"],
                "recovery_policy": fl.get("recovery", {}).get(
                    "policy", "one_shot")}
    n_blocks = harness.SETUP_BLOCKS
    ours = compare.reference_blocks(
        models.load("mlp").reference(built, fl, model),
        built["params0"], k, n_blocks)
    theirs = compare.reference_blocks(
        fl_round.Reference(fl, model, [scenario], train, speed),
        built["params0"], k, n_blocks)
    assert set(ours) == set(theirs)
    for key in ("losses", "e3"):
        np.testing.assert_array_equal(ours[key], theirs[key])
    for key in ("p1", "p3"):
        for n in theirs[key]:
            np.testing.assert_array_equal(ours[key][n], theirs[key][n])


@pytest.mark.parametrize("name", CELLS)
def test_a_model_kind_is_added_by_a_file(tmp_path, monkeypatch, name):
    mlp_out, _ = run_tiny(name)
    shutil.copy(models.DIR / "mlp.py", tmp_path / "copied.py")
    monkeypatch.setattr(models, "DIR", tmp_path)
    spec = tiny_spec(name)
    spec["config"]["model"]["kind"] = "copied"
    out = harness.run_cell(name, SEED, 0.0, False,
                           require_chip=False, cache=False,
                           log=lambda *a: None, spec=spec)
    assert out["correct"] is True
    assert out["checks"] == mlp_out["checks"]


def test_unknown_model_kind_is_refused_at_set_up():
    spec = tiny_spec("paper.single")
    spec["config"]["model"]["kind"] = "imaginary"
    with pytest.raises(ValueError, match=r"bench/models/imaginary\.py"):
        harness.CellRun(spec, SEED)
