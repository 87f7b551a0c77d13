"""Synthetic(alpha, beta) federated data: the q-FedAvg / FedProx recipe
(arXiv:1905.10497 §5) that the LT-FL paper uses for its tables.

Per device k:
    u_k ~ N(0, alpha);  W_k[i,j] ~ N(u_k, 1),  b_k[i] ~ N(u_k, 1)
    B_k ~ N(0, beta);   v_k[j] ~ N(B_k, 1)
    Sigma = diag(j^-1.2);  x ~ N(v_k, Sigma)
    y = argmax(W_k x + b_k)
    n_k ~ LogNormal(4, 2) + 50, clipped to [50, max_samples]

A frozen copy of the program's generator: the same draws, in the same
order, from the same ``numpy`` generator. With ``sizes="quantiles"``
the client sizes are not drawn: every seed gets the same set, the
quantiles (k + 0.5) / N of the size law, in an order drawn from the
seed, so that the padded shapes, and with them the work and the memory
of a run, do not change with the seed.
"""
from __future__ import annotations

import statistics

import numpy as np

D_FEAT = 60
N_CLASSES = 10


def size_quantiles(n_clients: int, max_samples: int) -> np.ndarray:
    """The client sizes at the quantiles (k + 0.5) / N of the size law."""
    z = np.array([statistics.NormalDist().inv_cdf((k + 0.5) / n_clients)
                  for k in range(n_clients)])
    return (np.exp(4.0 + 2.0 * z).astype(int) + 50).clip(50, max_samples)


def generate(rng: np.random.Generator, n_clients: int, alpha: float,
             beta: float, max_samples: int = 1000, test_frac: float = 0.2,
             sizes: str = "drawn"):
    """Per-client lists ``(train_x, train_y, test_x, test_y)``; ``sizes``
    is ``"drawn"`` (the program's generator) or ``"quantiles"``."""
    diag = np.array([(j + 1) ** -1.2 for j in range(D_FEAT)])
    if sizes == "quantiles":
        n_k = rng.permutation(size_quantiles(n_clients, max_samples))
    elif sizes == "drawn":
        n_k = (rng.lognormal(4.0, 2.0, n_clients).astype(int)
               + 50).clip(50, max_samples)
    else:
        raise ValueError(f"unknown client sizes {sizes!r}")
    tx, ty, sx, sy = [], [], [], []
    for k in range(n_clients):
        u = rng.normal(0, np.sqrt(alpha))
        W = rng.normal(u, 1, (N_CLASSES, D_FEAT))
        b = rng.normal(u, 1, N_CLASSES)
        Bk = rng.normal(0, np.sqrt(beta))
        v = rng.normal(Bk, 1, D_FEAT)
        x = rng.normal(v, np.sqrt(diag), (n_k[k], D_FEAT)).astype(
            np.float32)
        y = np.argmax(x @ W.T + b, axis=1).astype(np.int32)
        n_test = max(1, int(test_frac * n_k[k]))
        tx.append(x[n_test:])
        ty.append(y[n_test:])
        sx.append(x[:n_test])
        sy.append(y[:n_test])
    return tx, ty, sx, sy


def padded_train(train_x, train_y):
    """Every client's train set zero-padded to the longest:
    ``(x (N, M, D_FEAT) f32, y (N, M) i32, counts (N,) i32)``."""
    counts = np.array([len(x) for x in train_x], np.int32)
    N, M = len(train_x), int(counts.max())
    X = np.zeros((N, M, D_FEAT), np.float32)
    Y = np.zeros((N, M), np.int32)
    for k in range(N):
        X[k, :counts[k]] = train_x[k]
        Y[k, :counts[k]] = train_y[k]
    return X, Y, counts
