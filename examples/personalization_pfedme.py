"""Paper §5 'Personalization': TRA-pFedMe vs biased pFedMe (Fig. 9).

pFedMe trains personalized models theta_i around a global model w via
Moreau envelopes. Threshold selection degrades the GLOBAL model badly
while personalized accuracy is resilient; TRA recovers the global model
at a ~1% personalized cost (the paper's headline: up to +20% global).

Run:  PYTHONPATH=src python examples/personalization_pfedme.py
"""
import numpy as np

from repro.core.server import FederatedServer, FLConfig
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.network.trace import sample_networks
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

rng = np.random.default_rng(1)
data = generate_synthetic(rng, n_clients=30, alpha=0.5, beta=0.5)
nets = sample_networks(rng, data.n_clients)


def run(label, **kw):
    cfg = FLConfig(algo="pfedme", n_rounds=40, clients_per_round=10,
                   local_steps=10, eval_every=10 ** 6, **kw)
    s = FederatedServer(cfg, data, nets)
    s.run()
    g = s.evaluate()
    p = s.evaluate_personalized()
    print(f"{label:26s} global={g.average*100:5.1f}%  "
          f"personalized={p.average*100:5.1f}%")
    return g, p


gb, pb = run("pFedMe, biased 70%", selection="ratio", eligible_ratio=0.7,
             tra=TRAConfig(enabled=False))
gt, pt = run("TRA-pFedMe, 10% loss", selection="all",
             tra=TRAConfig(enabled=True, loss_rate=0.1))
print(f"\nglobal model gain from TRA: "
      f"{(gt.average-gb.average)*100:+.1f}pp "
      f"(personalized cost: {(pt.average-pb.average)*100:+.1f}pp)")
