"""The comparison that decides ``correct``: the program's first three
blocks against the plain reference's, from the same seed and weights.

The numbers:

``loss_gap``
    per block, the relative gap between the program's and the
    reference's mean logged train loss (each client's loss at the
    round's start, averaged over the block's rounds and the cohort);
``update_gap``
    the first block's parameter change: the gap between the program's
    norm and the reference's, over the reference's norm;
``change_gap``
    the same for the change after the three blocks;
``ef_gap``
    the same for the error-feedback memory after the three blocks (all
    clients' rows: what each client's last upload lost);
``update_leaf_gap``, ``change_leaf_gap``
    the first two by the worst leaf instead of the whole parameter
    vector: the gap of a leaf's norms over the reference's norm of that
    leaf or of the median leaf, whichever is larger. Leaves that the
    reference moves by less than a thousandth of the median leaf in the
    first block are left out (round-off alone moves them);
``update_gap.<leaf>``, ``change_gap.<leaf>``
    the first two for one leaf alone, over the reference's norm of that
    leaf's change.

Which of them a cell holds to a limit is in its ``bench/limits`` file,
with the readings the limit was set from; PERF.md says why. A number
that is not finite fails its limit.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

LEAF_FLOOR = 1e-3


def _host(params) -> Dict[str, np.ndarray]:
    """One scenario's parameters of a stacked reference state."""
    return {k: np.asarray(v[0], np.float64) for k, v in params.items()}


def program_readings(losses, snaps: dict, n_blocks: int) -> dict:
    """The program's readings from its first blocks: the logged losses
    (n_blocks * k,), the parameters after the first and the last block,
    and the error-feedback memory after the last."""
    return {"losses": np.concatenate(losses),
            "p1": snaps[1]["p"], "p3": snaps[n_blocks]["p"],
            "e3": snaps[n_blocks]["ef"]}


def reference_blocks(ref, params0: dict, k: int, n_blocks: int) -> dict:
    """Run the reference through ``n_blocks`` blocks of ``k`` rounds
    from the weights ``params0``."""
    import jax.numpy as jnp
    st = ref.init_states([{n: jnp.asarray(v) for n, v in params0.items()}])
    losses, out = [], {}
    for b in range(n_blocks):
        st, lo = ref.run(st, b * k, k)
        losses.append(np.asarray(lo)[0])
        if b == 0:
            out["p1"] = _host(ref.params(st))
    out["p3"] = _host(ref.params(st))
    out["e3"] = np.asarray(ref.ef(st)[0], np.float64)
    out["losses"] = np.concatenate(losses)
    return out


def _gap(a: float, b: float, scale: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / scale


def _vec_gap(p1: dict, r1: dict, p0: dict) -> float:
    """Norm gap of the whole parameter change."""
    dp = np.concatenate([(np.asarray(p1[n], np.float64) - p0[n]).ravel()
                         for n in sorted(p0)])
    dr = np.concatenate([(np.asarray(r1[n], np.float64) - p0[n]).ravel()
                         for n in sorted(p0)])
    nr = float(np.linalg.norm(dr))
    return _gap(float(np.linalg.norm(dp)), nr, nr) if nr else 0.0


def numbers(prog: dict, ref: dict, p0: dict, k: int, n_blocks: int) -> dict:
    """The numbers from the program's and the reference's losses
    (n_blocks * k,), parameters and error-feedback memory after the
    first and last block; ``p0`` the initial weights in float64."""
    out = dict.fromkeys(("loss_gap", "update_gap", "change_gap", "ef_gap",
                         "update_leaf_gap", "change_leaf_gap"), 0.0)

    def worst(name, v):
        out[name] = max(out[name], v)

    for b in range(n_blocks):
        lp = float(np.mean(prog["losses"][b * k:(b + 1) * k]))
        lr = float(np.mean(ref["losses"][b * k:(b + 1) * k]))
        worst("loss_gap", _gap(lp, lr, abs(lr)))
    out["update_gap"] = _vec_gap(prog["p1"], ref["p1"], p0)
    out["change_gap"] = _vec_gap(prog["p3"], ref["p3"], p0)
    er = float(np.linalg.norm(ref["e3"]))
    if er:
        out["ef_gap"] = _gap(float(np.linalg.norm(
            np.asarray(prog["e3"], np.float64))), er, er)
    d1 = {n: float(np.linalg.norm(ref["p1"][n] - p0[n])) for n in p0}
    med = float(np.median(list(d1.values())))
    moved = [n for n in p0 if d1[n] >= LEAF_FLOOR * med]
    for key, name in (("p1", "update_leaf_gap"), ("p3", "change_leaf_gap")):
        for n in moved:
            nr = float(np.linalg.norm(ref[key][n] - p0[n]))
            np_ = float(np.linalg.norm(
                np.asarray(prog[key][n], np.float64) - p0[n]))
            worst(name, _gap(np_, nr, max(nr, med)))
    for key, name in (("p1", "update_gap"), ("p3", "change_gap")):
        for n in sorted(p0):
            nr = float(np.linalg.norm(ref[key][n] - p0[n]))
            np_ = float(np.linalg.norm(
                np.asarray(prog[key][n], np.float64) - p0[n]))
            out[f"{name}.{n}"] = _gap(np_, nr, nr) if nr else 0.0
    return out


def as_float64(params: dict) -> dict:
    return {n: np.asarray(v, np.float64) for n, v in params.items()}


def hold(values: dict, limits: dict) -> dict:
    """Each number beside its limit; ``ok`` when finite and within."""
    out = {}
    for name, lim in ((n, limits[n]["limit"]) for n in limits):
        v = values[name]
        out[name] = {"value": v, "limit": lim,
                     "ok": bool(math.isfinite(v) and v <= lim)}
    return out
