"""Client models: one module per model kind, ``bench/models/<kind>.py``,
found by the ``kind`` of a configuration's ``model`` block. A model kind
is added by adding its module (and its reference) as files.

A module provides:

``build(cfg, seed, rng, block_rounds, networks)``
    makes the cell's data from the generator ``rng`` and builds the
    program's ``FederatedServer`` for the configuration ``cfg``, its
    state initialised from weights made from ``seed``; ``networks`` is
    ``bench/traffic/networks.generate``, called on ``rng`` after the
    data. Returns a dict: the server (``srv``), the host copy of the
    compared leaves (``params0``), and whatever ``reference`` needs.
``reference(built, fl, model, **kw)``
    the plain reference of the configuration's rounds over the data of
    ``built`` (``build``'s dict without the server), loaded from the
    file the configuration's ``reference`` key names: an object with
    ``init_states``, ``run``, ``params`` and ``ef`` (see
    ``compare.reference_blocks``). ``kw`` passes ``dot_dtype`` (the
    control's matrix-product precision) and ``fault`` (one of the
    reference file's ``FAULTS``).
``train_flops_per_update(model, fl)``
    model FLOPs of one client update (``round_mfu`` divides by it).
``leaves(model)``
    the names of the compared leaves: the suffixes ``<number>.<leaf>``
    that ``bench/limits/<cell>.json`` may use.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import re
from pathlib import Path

DIR = Path(__file__).resolve().parent
ROOT = DIR.parents[1]


def load(kind: str):
    """The module of a model kind, from ``DIR / f"{kind}.py"``."""
    path = DIR / f"{kind}.py"
    if not re.fullmatch(r"[A-Za-z0-9_]+", kind) or not path.is_file():
        raise ValueError(f"model kind {kind!r}: no model module {path}")
    spec = importlib.util.spec_from_file_location(f"bench_model_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(path: str):
    """The reference module a configuration's ``reference`` key names:
    a ``.py`` file of the checkout, by its path from the root, imported
    as the package module it is (so its compiled programs are shared)."""
    p = Path(path)
    if p.suffix != ".py" or not (ROOT / p).is_file():
        raise ValueError(f"no reference file {path!r} in {ROOT}")
    return importlib.import_module(".".join(p.with_suffix("").parts))


def fl_config(fl: dict, seed: int, block_rounds: int):
    """The program's ``FLConfig`` from a configuration's ``fl`` block;
    nested blocks become the dataclass that field holds by default."""
    from repro.core.server import FLConfig
    base = FLConfig()
    kw = {}
    for k, v in fl.items():
        default = getattr(base, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(default):
            kw[k] = type(default)(**v)
        else:
            kw[k] = v
    return FLConfig(**kw, seed=seed, n_rounds=10 ** 9,
                    eval_every=block_rounds)
