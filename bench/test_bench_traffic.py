"""The benchmark's frozen copies of the program's data and network
generators give the program's arrays, every configuration names a
network generator there is, and every traffic file gives a block
length."""
import numpy as np
import pytest

from bench import harness, models
from bench.traffic import networks, synthetic
from repro.data.synthetic import generate_synthetic, stage_on_device
from repro.network.trace import sample_networks

SEED = 2 ** 31 + 77


def test_synthetic_copy_matches_program():
    args = dict(n_clients=100, alpha=1.0, beta=1.0)
    ours = synthetic.generate(np.random.default_rng(SEED), **args)
    theirs = generate_synthetic(np.random.default_rng(SEED), **args)
    for mine, prog in zip(ours, (theirs.train_x, theirs.train_y,
                                 theirs.test_x, theirs.test_y)):
        assert len(mine) == len(prog)
        for a, b in zip(mine, prog):
            np.testing.assert_array_equal(a, b)


def test_padded_train_matches_program_staging():
    data = generate_synthetic(np.random.default_rng(SEED), n_clients=20)
    X, Y, counts = synthetic.padded_train(data.train_x, data.train_y)
    dd = stage_on_device(data)
    np.testing.assert_array_equal(X, np.asarray(dd.train_x))
    np.testing.assert_array_equal(Y, np.asarray(dd.train_y))
    np.testing.assert_array_equal(counts, np.asarray(dd.counts))


def test_networks_copy_matches_program():
    rng_a, rng_b = np.random.default_rng(SEED), np.random.default_rng(SEED)
    speed, loss = networks.sample(rng_a, 100)
    nets = sample_networks(rng_b, 100)
    np.testing.assert_array_equal(speed, nets.upload_mbps)
    np.testing.assert_array_equal(loss, nets.packet_loss)


def test_linspace_networks_are_the_recipe_networks():
    rng = np.random.default_rng(SEED)
    speed, loss = networks.generate(rng, 20, {
        "generator": "linspace", "low_mbps": 0.5, "high_mbps": 20.0,
        "packet_loss": 0.05})
    np.testing.assert_array_equal(speed, np.linspace(0.5, 20.0, 20))
    np.testing.assert_array_equal(loss, np.full(20, 0.05))
    # it draws nothing: the generator's state is untouched
    assert rng.random() == np.random.default_rng(SEED).random()


def test_unknown_network_generator_is_refused():
    with pytest.raises(ValueError):
        networks.generate(np.random.default_rng(0), 4,
                          {"generator": "imaginary"})


BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name):
    spec = harness.cell_spec(name)
    assert spec["traffic"]["block_rounds"] >= 1
    assert "eval_every" not in spec["config"]["fl"]
    n = spec["config"]["data"]["n_clients"]
    speed, loss = networks.generate(np.random.default_rng(SEED), n,
                                    spec["config"]["networks"])
    assert speed.shape == loss.shape == (n,)
    model = spec["config"]["model"]
    leaves = models.load(model["kind"]).leaves(model)
    assert set(spec["limits"]) <= {"loss_gap", "update_gap", "change_gap",
                                   "ef_gap", "update_leaf_gap",
                                   "change_leaf_gap"} | {
        f"{n}.{leaf}" for n in ("update_gap", "change_gap")
        for leaf in leaves}


@pytest.mark.parametrize("n_clients", [20, 100])
def test_quantile_sizes_are_one_set_in_seeded_orders(n_clients):
    sizes = []
    for seed in (SEED, SEED + 1):
        tx, _, sx, _ = synthetic.generate(
            np.random.default_rng(seed), n_clients, 0.5, 0.5,
            sizes="quantiles")
        sizes.append([len(a) + len(b) for a, b in zip(tx, sx)])
    assert sorted(sizes[0]) == sorted(sizes[1])
    assert sizes[0] != sizes[1]
    assert sorted(sizes[0]) == sorted(synthetic.size_quantiles(n_clients,
                                                               1000))
    assert max(sizes[0]) == 1000
