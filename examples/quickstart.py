"""Quickstart: loss-tolerant federated learning in ~40 lines.

Trains the paper's MLP on Synthetic(1,1) three ways and prints the
fairness comparison:
  1. threshold-based selection (70% eligible ratio)  — the baseline the
     paper criticises,
  2. TRA with 10% packet loss                         — the paper's fix,
  3. ideal lossless full participation                — the upper bound.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.server import FederatedServer, FLConfig
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.network.trace import sample_networks
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(0)
data = generate_synthetic(rng, n_clients=30, alpha=1.0, beta=1.0)
nets = sample_networks(rng, data.n_clients)
ROUNDS = 50


def run(label, **kw):
    cfg = FLConfig(algo="qfedavg", n_rounds=ROUNDS, clients_per_round=10,
                   local_steps=10, eval_every=10 ** 6, **kw)
    server = FederatedServer(cfg, data, nets)
    server.run()
    rep = server.evaluate()
    print(f"{label:28s} acc={rep.average*100:5.1f}%  "
          f"worst10%={rep.worst10*100:5.1f}%  var={rep.variance:6.0f}")
    return rep


print(f"cohort: {data.n_clients} clients, "
      f"{(nets.upload_mbps < 2).sum()} below the 2 Mbps threshold\n")
biased = run("threshold (70% eligible)", selection="ratio",
             eligible_ratio=0.7, tra=TRAConfig(enabled=False))
tra = run("TRA, 10% packet loss", selection="all",
          tra=TRAConfig(enabled=True, loss_rate=0.1))
ideal = run("ideal lossless", selection="all", tra=TRAConfig(enabled=False))

assert tra.worst10 >= biased.worst10, "TRA should lift the worst clients"
print("\nTRA recovers most of the fairness the threshold threw away.")
