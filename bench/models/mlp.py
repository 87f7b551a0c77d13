"""The LT-FL paper's client model: the d_in-d_hidden-n_classes MLP
(ReLU, softmax cross entropy) on the Synthetic(alpha, beta) data of
``bench/traffic/synthetic.py``, every parameter trained and uploaded."""
from __future__ import annotations

import numpy as np

from bench import flops
from bench.models import fl_config, load_reference
from bench.traffic import synthetic


def build(cfg: dict, seed: int, rng, block_rounds: int, networks) -> dict:
    import jax
    from repro.core.server import FederatedServer
    from repro.data.synthetic import FederatedDataset
    from repro.network.trace import ClientNetworks

    fl_round = load_reference(cfg["reference"])
    model, d = cfg["model"], cfg["data"]
    tx, ty, sx, sy = synthetic.generate(
        rng, d["n_clients"], d["alpha"], d["beta"],
        max_samples=d["max_samples"], test_frac=d["test_frac"],
        sizes=d.get("sizes", "drawn"))
    speed, loss = networks(rng, d["n_clients"], cfg["networks"])
    train = synthetic.padded_train(tx, ty)
    params = fl_round.init_params(jax.random.PRNGKey(seed)[None], model)
    params0 = {k: np.asarray(v[0]) for k, v in params.items()}
    srv = FederatedServer(fl_config(cfg["fl"], seed, block_rounds),
                          FederatedDataset(tx, ty, sx, sy),
                          ClientNetworks(speed, loss))
    srv._state = srv.engine.init_state({k: v[0] for k, v in params.items()})
    return {"srv": srv, "params0": params0, "train": train,
            "upload_mbps": speed, "seed": seed,
            "reference_file": cfg["reference"]}


def reference(built: dict, fl: dict, model: dict, **kw):
    fl_round = load_reference(built["reference_file"])
    scenario = {"seed": built["seed"], "loss_rate": fl["tra"]["loss_rate"],
                "recovery_policy": fl.get("recovery", {}).get(
                    "policy", "one_shot")}
    return fl_round.Reference(fl, model, [scenario], built["train"],
                              built["upload_mbps"], **kw)


def train_flops_per_update(model: dict, fl: dict) -> float:
    d = flops.mlp_params(model["d_in"], model["d_hidden"],
                         model["n_classes"])
    return flops.train_flops_per_update(d, fl["local_steps"],
                                        fl["batch_size"])


def leaves(model: dict) -> list:
    return list(model["param_order"])
