"""Client networks: per client an upload speed (Mbps) and a packet loss
rate, made by the generator a configuration's ``networks`` block names.

``fcc_mobile``: FCC-calibrated mobile networks (LT-FL paper §3.1,
Fig. 2). Calibration points from the FCC "Measuring Broadband America" 2019
mobile trace: 90% of users lose < 10% of packets, 76% upload faster
than 2 Mbps, 51% faster than 8 Mbps. Upload speed ~ LogNormal(mu,
sigma) fitted to the two speed quantiles; packet loss ~ Exponential
truncated to [0, 1].

A frozen copy of the program's ``sample_networks``.

``linspace``: speeds evenly spaced from ``low_mbps`` to ``high_mbps``
and one ``packet_loss`` for every client, as the program's documented
recipes build them by hand. Draws nothing.
"""
from __future__ import annotations

import numpy as np

SPEED_MU = 2.1305
SPEED_SIGMA = 2.0351
LOSS_LAMBDA = 23.0259


def sample(rng: np.random.Generator, n_clients: int):
    """``(upload_mbps (N,), packet_loss (N,))``."""
    speed = rng.lognormal(SPEED_MU, SPEED_SIGMA, n_clients)
    loss = np.minimum(rng.exponential(1.0 / LOSS_LAMBDA, n_clients), 1.0)
    return speed, loss


def generate(rng: np.random.Generator, n_clients: int, spec: dict):
    """``(upload_mbps (N,), packet_loss (N,))`` by ``spec["generator"]``."""
    kind = spec["generator"]
    if kind == "fcc_mobile":
        return sample(rng, n_clients)
    if kind == "linspace":
        return (np.linspace(spec["low_mbps"], spec["high_mbps"], n_clients),
                np.full(n_clients, spec["packet_loss"]))
    raise ValueError(f"unknown network generator {kind!r}")
