"""END-TO-END DRIVER: federated pre-training of a ~100M-param transformer
for a few hundred steps with the TRA protocol in the loop.

A 4-client cohort collaboratively trains a reduced StableLM on a synthetic
token stream; client 0 and 1 are 'insufficient' (20% packet loss on every
upload), aggregation uses the per-coordinate debias. Loss must decrease
and stay finite through packet loss — the paper's core claim at the
systems level.

Run:  PYTHONPATH=src python examples/fl_pretrain_e2e.py [--steps 200]
(On the production mesh the same step function shards clients over the
'data' axis; see src/repro/launch/fl_train.py and the dry-run.)
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig, get_config
from repro.core.tra import TRAConfig
from repro.launch.fl_train import make_fl_train_step
from repro.models import transformer as T
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--clients", type=int, default=4)
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--seq", type=int, default=128)
args = ap.parse_args()

# ~100M params: widen the reduced config
import dataclasses
cfg = dataclasses.replace(
    get_config("stablelm-3b").reduced(),
    n_layers=4, d_model=512, n_heads=8, n_kv_heads=8, head_dim=64,
    d_ff=2048, vocab=32_000)
n_params = cfg.n_params()
print(f"model: {n_params/1e6:.1f}M params, cohort={args.clients} clients")

tcfg = TrainConfig(lr=3e-4)
tra = TRAConfig(loss_rate=0.2, debias="per_coord_count")
C = args.clients
params = T.init_params(cfg, jax.random.PRNGKey(0))
step, opt = make_fl_train_step(cfg, tcfg, tra, C)
opt_state = opt.init(params)
step = jax.jit(step)
sufficient = jnp.asarray([0.0, 0.0] + [1.0] * (C - 2))

# synthetic "language": per-client Markov streams with distinct stats —
# heterogeneous data so federation actually matters
rng = np.random.default_rng(0)
trans = rng.dirichlet(np.full(64, 0.1), size=(C, 64))   # per-client bigram
cum = np.cumsum(trans, axis=-1)                          # (C, 64, 64)
start = time.time()
losses = []
for i in range(args.steps):
    toks = np.zeros((C, args.batch, args.seq + 1), np.int64)
    t = rng.integers(0, 64, (C, args.batch))
    u = rng.random((args.seq + 1, C, args.batch))
    cidx = np.arange(C)[:, None]
    for s in range(args.seq + 1):
        toks[..., s] = t
        # vectorized categorical draw from each client's bigram row
        t = (cum[cidx, t] < u[s][..., None]).sum(-1)
    batch = {"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
             "labels": jnp.asarray(toks[..., 1:], jnp.int32)}
    params, opt_state, m = step(params, opt_state, batch, sufficient,
                                jax.random.PRNGKey(i))
    losses.append(float(m["loss"]))
    if i % 20 == 0 or i == args.steps - 1:
        print(f"step {i:4d} loss={losses[-1]:7.4f} "
              f"({time.time()-start:6.1f}s)", flush=True)

assert np.isfinite(losses).all(), "NaN in federated training"
assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.9, \
    "loss failed to decrease"
print(f"\nOK: {losses[0]:.3f} -> {np.mean(losses[-10:]):.3f} "
      f"with 20% packet loss on half the cohort")
