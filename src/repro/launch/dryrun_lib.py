"""Dry-run core: lower + compile every (arch x shape x mesh) combination,
extract memory/cost/collective statistics for the roofline analysis.

This module performs NO env mutation — ``dryrun.py`` sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 in its first two
lines and then calls into here. Tests import this module directly with
small meshes.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (INPUT_SHAPES, InputShape, ModelConfig,
                                TrainConfig, get_config)
from repro.launch import costmodel
from repro.launch import input_specs as ispec
from repro.launch import sharding as shard_rules
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import mesh_axis_sizes
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import decode as decode_mod
from repro.models import transformer as tf
from repro.utils.shardctx import use_rules

# --- TPU v5e hardware constants (roofline) ---------------------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

# ---------------------------------------------------------------------------
def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """Useful-work estimate: 6 N_active D for train, 2 N_active tokens
    for inference."""
    n_act = cfg.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_act * tokens


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    step: str
    ok: bool
    scheme: str = "auto"
    skipped: Optional[str] = None
    error: Optional[str] = None
    lower_s: float = 0.0
    compile_s: float = 0.0
    # analytic cost model (global / n_chips); see costmodel.py for why
    flops_per_dev: float = 0.0
    hbm_bytes_per_dev: float = 0.0
    # raw XLA cost_analysis values (while-bodies counted ONCE — reference)
    hlo_flops_raw: float = 0.0
    hlo_bytes_raw: float = 0.0
    peak_mem_per_dev: float = 0.0
    arg_mem_per_dev: float = 0.0
    collectives: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # roofline (seconds)
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0
    useful_ratio: float = 0.0

    def as_dict(self):
        return dataclasses.asdict(self)


def should_skip(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return ("full-attention architecture: long_500k requires "
                "sub-quadratic attention (DESIGN.md §3)")
    return None


def run_combo(arch: str, shape_name: str, mesh, *, mesh_name: str,
              tcfg: Optional[TrainConfig] = None,
              scheme: str = "auto",
              keep_hlo: bool = False) -> DryrunResult:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    res = DryrunResult(arch=arch, shape=shape_name, mesh=mesh_name,
                       step=shape.kind, ok=False, scheme=scheme)
    skip = should_skip(cfg, shape)
    if skip:
        res.skipped = skip
        res.ok = True
        return res

    tcfg = tcfg or TrainConfig(remat="full")
    multi_pod = "pod" in mesh.axis_names
    n_chips = int(np.prod(mesh.devices.shape))
    dtype = jnp.bfloat16

    params_abs = jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0), dtype))
    msz = mesh_axis_sizes(mesh).get("model", 1)
    # decode: keep weights resident (model-sharded only) when they fit in
    # half the HBM; per-token data-axis weight gathers otherwise
    decode_fsdp = cfg.n_params() * 2 / msz > 8e9
    fsdp_flag = True if shape.kind != "decode" else decode_fsdp
    pspecs = shard_rules.param_specs(cfg, params_abs, mesh, scheme=scheme,
                                     fsdp=fsdp_flag)
    p_shard = shard_rules.to_named(pspecs, mesh)

    t0 = time.time()
    try:
        if shape.kind == "train":
            rules = shard_rules.trim_batch_axes(
                shard_rules.train_rules(multi_pod, scheme), mesh,
                shape.global_batch)
            batch_abs = ispec.train_inputs(cfg, shape)
            b_shard = shard_rules.to_named(
                shard_rules.batch_specs(batch_abs, mesh, rules), mesh)
            step_fn, opt = make_train_step(cfg, tcfg)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            o_shard = shard_rules.to_named(
                shard_rules.param_specs(cfg, opt_abs, mesh, scheme=scheme)
                if tcfg.optimizer == "sgd" else
                _opt_specs(cfg, opt_abs, pspecs, mesh), mesh)
            with use_rules(mesh, rules):
                jitted = jax.jit(step_fn,
                                 in_shardings=(p_shard, o_shard, b_shard),
                                 out_shardings=(p_shard, o_shard, None),
                                 donate_argnums=(0, 1))
                lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            rules = shard_rules.trim_batch_axes(
                shard_rules.train_rules(multi_pod, scheme), mesh,
                shape.global_batch)
            batch_abs = ispec.prefill_inputs(cfg, shape)
            b_shard = shard_rules.to_named(
                shard_rules.batch_specs(batch_abs, mesh, rules), mesh)
            step_fn = make_prefill_step(cfg)
            with use_rules(mesh, rules):
                jitted = jax.jit(step_fn, in_shardings=(p_shard, b_shard),
                                 out_shardings=None)
                lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            dp = mesh_axis_sizes(mesh).get("data", 1)
            batch_shardable = shape.global_batch % dp == 0 \
                and shape.global_batch >= dp
            rules = shard_rules.decode_rules(
                multi_pod, batch_shardable, scheme,
                shard_rules.kv_head_parallel_ok(cfg, mesh))
            batch_abs = ispec.decode_inputs(cfg, shape)
            cache_abs = jax.eval_shape(
                lambda: decode_mod.init_cache(cfg, shape.global_batch,
                                              shape.seq_len, dtype))
            c_shard = shard_rules.to_named(
                shard_rules.cache_specs(cfg, cache_abs, mesh,
                                        batch_shardable, scheme), mesh)
            b_shard = shard_rules.to_named(
                shard_rules.batch_specs(batch_abs, mesh, rules), mesh)
            step_fn = make_serve_step(cfg)
            pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
            with use_rules(mesh, rules):
                jitted = jax.jit(step_fn,
                                 in_shardings=(p_shard, c_shard, b_shard,
                                               None),
                                 out_shardings=(None, c_shard),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params_abs, cache_abs, batch_abs,
                                       pos_abs)
        res.lower_s = time.time() - t0

        t1 = time.time()
        compiled = lowered.compile()
        res.compile_s = time.time() - t1

        cost = compiled.cost_analysis() or {}
        mem = compiled.memory_analysis()
        res.hlo_flops_raw = float(cost.get("flops", 0.0))
        res.hlo_bytes_raw = float(cost.get("bytes accessed", 0.0))
        remat = tcfg.remat != "none"
        res.flops_per_dev = costmodel.flops_global(
            cfg, shape, remat=remat) / n_chips
        res.hbm_bytes_per_dev = costmodel.hbm_bytes_global(
            cfg, shape, remat=remat, optimizer=tcfg.optimizer) / n_chips
        if mem is not None:
            res.peak_mem_per_dev = float(
                getattr(mem, "temp_size_in_bytes", 0) +
                getattr(mem, "output_size_in_bytes", 0))
            res.arg_mem_per_dev = float(
                getattr(mem, "argument_size_in_bytes", 0))
        hlo = compiled.as_text()
        res.collectives = analyze_collectives(hlo)
        if keep_hlo:
            res.collectives["hlo_len"] = len(hlo)

        res.t_compute = res.flops_per_dev / PEAK_FLOPS
        res.t_memory = res.hbm_bytes_per_dev / HBM_BW
        res.t_collective = res.collectives["wire_bytes"] / LINK_BW
        terms = {"compute": res.t_compute, "memory": res.t_memory,
                 "collective": res.t_collective}
        res.bottleneck = max(terms, key=terms.get)
        res.model_flops = model_flops(cfg, shape)
        total_flops = res.flops_per_dev * n_chips
        res.useful_ratio = res.model_flops / total_flops if total_flops else 0.0
        res.ok = True
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        res.error = f"{type(e).__name__}: {e}"[:2000]
    return res


def _opt_specs(cfg, opt_abs, pspecs, mesh):
    """adamw state: mu/nu shaped like params; count replicated."""
    from jax.sharding import PartitionSpec as P
    return {"mu": pspecs, "nu": pspecs, "count": P()}


def save_result(res: DryrunResult, outdir: str) -> str:
    import os
    os.makedirs(outdir, exist_ok=True)
    suffix = "" if res.scheme == "auto" else f"__{res.scheme}"
    path = f"{outdir}/{res.arch}__{res.shape}__{res.mesh}{suffix}.json"
    with open(path, "w") as f:
        json.dump(res.as_dict(), f, indent=1)
    return path
