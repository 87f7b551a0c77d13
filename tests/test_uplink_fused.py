"""Uplink megakernel validation.

* The pure-jnp reference (`uplink_ref`) is EXPRESSION-IDENTICAL to the
  pre-megakernel engine uplink chain (EF add -> mask -> debias-aggregate
  -> EF update -> masked norms) — asserted bitwise against the legacy
  chain spelled out below, for every DEBIAS_MODE ± error feedback. The
  engine's CPU path runs the reference, so this is what keeps round
  outputs bit-identical to the pre-megakernel scan.
* The Pallas kernel (interpret mode on CPU) matches the reference for
  every mode ± EF ± ssq; with a single (C, P) block the aggregate and
  EF update are bit-exact.
* The scenario-batched (S, C, P, F) grid is bit-identical to S
  independent single-scenario calls, both called directly and through
  the custom_vmap rule the sweep engine hits.
* Engine integration: forcing the kernel path (REPRO_UPLINK_IMPL)
  reproduces the reference-path engine/sweep results.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.kernels.common import DENOM_EPS, RATE_EPS
from repro.kernels.tra_agg.ops import DEBIAS_MODES
from repro.kernels.uplink_fused import ops as up_ops
from repro.kernels.uplink_fused.uplink_fused import pick_blocks

C, P, F = 6, 16, 32
D_UP = P * F - 11                       # partial last packet
PAD = P * F - D_UP


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(7)
    # packetised the way the engine packs: zero-padded partial last packet
    flat = jnp.asarray(rng.normal(size=(C, D_UP)).astype(np.float32))
    xp = jnp.pad(flat, ((0, 0), (0, PAD))).reshape(C, P, F)
    ef = jnp.asarray(rng.normal(size=(C, D_UP)).astype(np.float32))
    mask = jnp.asarray((rng.random((C, P)) > 0.4).astype(np.float32))
    w = jnp.asarray(rng.random(C).astype(np.float32) + 0.1)
    suff = jnp.asarray((rng.random(C) > 0.5).astype(np.float32))
    mult = jnp.asarray(rng.random(C).astype(np.float32) + 0.5)
    pcnt = jnp.full((P,), F, jnp.float32).at[-1].set(F - PAD)
    kept = (mask @ pcnt) / D_UP
    return dict(xp=xp, ef=ef, mask=mask, w=w, suff=suff, mult=mult,
                kept=kept, lr=jnp.float32(0.4))


def legacy_chain(xp, mask, weights, mode, *, kept=None, sufficient=None,
                 loss_rate=None, mult=None, ef_rows=None, want_ssq=False):
    """The pre-megakernel engine uplink, verbatim (PR 2 engine.py):
    multi-pass — EF-adjusted tensor materialised, then masked-einsum
    aggregate, then the EF-update product, then the masked norms."""
    if ef_rows is not None:
        flat = xp.reshape(C, P * F)[:, :D_UP] + ef_rows
        xp = jnp.pad(flat, ((0, 0), (0, PAD))).reshape(C, P, F)
    q_c = weights if mult is None else weights * mult
    if mode == "per_client_rate":
        q_c = q_c / jnp.maximum(kept, 1e-6)
    elif mode == "group_rate":
        q_c = q_c * jnp.where(sufficient.astype(bool), 1.0,
                              1.0 / jnp.maximum(1.0 - loss_rate, 1e-6))
    wm = mask * q_c[:, None]
    if mode == "per_coord_count":
        den = jnp.maximum((mask * weights[:, None]).sum(0), 1e-12)[:, None]
    else:
        den = jnp.maximum(weights.sum(), 1e-12)
    agg = (jnp.einsum("cpf,cp->pf", xp, wm) / den).reshape(-1)[:D_UP]
    new_ef = (xp * (1.0 - mask[:, :, None])).reshape(C, P * F)[:, :D_UP] \
        if ef_rows is not None else None
    ssq = ((xp * xp).sum(-1) * mask).sum(-1) if want_ssq else None
    return agg, new_ef, ssq


def _call(case, mode, *, use_ef, want_ssq=False, **kw):
    return up_ops.uplink_round(
        case["xp"], case["mask"], case["w"], mode=mode, d_up=D_UP,
        ef_rows=case["ef"] if use_ef else None, kept=case["kept"],
        sufficient=case["suff"], loss_rate=case["lr"], mult=case["mult"],
        want_ssq=want_ssq, **kw)


# ---------------------------------------------------------------------------
# fused pass == reference chain (the bit-identity lock for the engine)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", DEBIAS_MODES)
@pytest.mark.parametrize("use_ef", [False, True])
@pytest.mark.parametrize("want_ssq", [False, True])
def test_ref_bitwise_equals_legacy_chain(case, mode, use_ef, want_ssq):
    agg, new_ef, ssq = _call(case, mode, use_ef=use_ef,
                             want_ssq=want_ssq, impl="ref")
    agg0, ef0, ssq0 = legacy_chain(
        case["xp"], case["mask"], case["w"], mode, kept=case["kept"],
        sufficient=case["suff"], loss_rate=case["lr"], mult=case["mult"],
        ef_rows=case["ef"] if use_ef else None, want_ssq=want_ssq)
    np.testing.assert_array_equal(np.asarray(agg), np.asarray(agg0))
    if use_ef:
        np.testing.assert_array_equal(np.asarray(new_ef), np.asarray(ef0))
    else:
        assert new_ef is None
    if want_ssq:
        np.testing.assert_array_equal(np.asarray(ssq), np.asarray(ssq0))
    else:
        assert ssq is None


@pytest.mark.parametrize("mode", DEBIAS_MODES)
@pytest.mark.parametrize("use_ef", [False, True])
def test_kernel_matches_ref(case, mode, use_ef):
    """Tiled interpret-mode megakernel vs the jnp oracle. The EF update
    is element-wise (no reduction), so it is exact even tiled; the
    aggregate/norm accumulators split the client reduction per block."""
    a1, e1, s1 = _call(case, mode, use_ef=use_ef, want_ssq=True,
                       impl="kernel", block_p=8, block_c=3)
    a0, e0, s0 = _call(case, mode, use_ef=use_ef, want_ssq=True,
                       impl="ref")
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5)
    if use_ef:
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e0))


@pytest.mark.parametrize("mode", DEBIAS_MODES)
def test_kernel_single_block_bit_identical(case, mode):
    """With one (C, P) block the kernel's reduction order is the
    reference einsum's — aggregate and EF update are bit-exact."""
    a1, e1, _ = _call(case, mode, use_ef=True, impl="kernel",
                      block_p=P, block_c=C)
    a0, e0, _ = _call(case, mode, use_ef=True, impl="ref")
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e0))


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 5), st.integers(1, 4), st.sampled_from(DEBIAS_MODES))
def test_property_fused_equals_chain(c, pb, mode):
    """Property sweep over cohort/packet shapes: kernel ≡ chain."""
    p = 4 * pb
    f = 128
    d_up = p * f - 3
    rng = np.random.default_rng(c * p)
    xp = jnp.asarray(rng.normal(size=(c, p, f)).astype(np.float32))
    ef = jnp.asarray(rng.normal(size=(c, d_up)).astype(np.float32))
    mask = jnp.asarray((rng.random((c, p)) > 0.3).astype(np.float32))
    w = jnp.asarray(rng.random(c).astype(np.float32) + 0.1)
    suff = jnp.asarray((rng.random(c) > 0.5).astype(np.float32))
    pcnt = jnp.full((p,), f, jnp.float32).at[-1].set(f - 3)
    kept = (mask @ pcnt) / d_up
    out = [up_ops.uplink_round(xp, mask, w, mode=mode, d_up=d_up,
                               ef_rows=ef, kept=kept, sufficient=suff,
                               loss_rate=jnp.float32(0.3), want_ssq=True,
                               impl=impl) for impl in ("kernel", "ref")]
    for k_, r_ in zip(out[0], out[1]):
        np.testing.assert_allclose(np.asarray(k_), np.asarray(r_),
                                   rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# scenario-batched (S, ...) variant: bit-identical to S independent calls
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_batched_variant_bit_identical_to_singles(case, impl):
    S = 3
    rng = np.random.default_rng(11)
    xps = jnp.stack([case["xp"] * s for s in (1.0, 0.5, -1.3)])
    efs = jnp.stack([case["ef"] * s for s in (1.0, 2.0, 0.0)])
    masks = jnp.asarray((rng.random((S, C, P)) > 0.4).astype(np.float32))
    ws = jnp.stack([case["w"] + s for s in (0.0, 0.1, 0.7)])
    suffs = jnp.stack([case["suff"], 1 - case["suff"], case["suff"]])
    lrs = jnp.asarray([0.4, 0.1, 0.7], jnp.float32)
    bat = up_ops.uplink_round_scenarios(
        xps, masks, ws, mode="group_rate", d_up=D_UP, ef_rows=efs,
        sufficient=suffs, loss_rate=lrs, want_ssq=True, impl=impl)
    for i in range(S):
        one = up_ops.uplink_round(
            xps[i], masks[i], ws[i], mode="group_rate", d_up=D_UP,
            ef_rows=efs[i], sufficient=suffs[i], loss_rate=lrs[i],
            want_ssq=True, impl=impl)
        for b, o in zip(bat, one):
            np.testing.assert_array_equal(np.asarray(b[i]), np.asarray(o))


# ---------------------------------------------------------------------------
# bf16-stream / fp32-accumulate contract
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_bf16_stream_contract(case, impl):
    """Both impls honour the contract: inputs rounded to the stream
    dtype, fp32 accumulation, EF rows written back in the stream dtype
    — same dtypes whichever backend resolves."""
    a0, e0, _ = _call(case, "group_rate", use_ef=True, impl="ref")
    a1, e1, _ = _call(case, "group_rate", use_ef=True, impl=impl,
                      stream_dtype=jnp.bfloat16)
    assert a1.dtype == jnp.float32          # fp32 accumulation
    assert e1.dtype == jnp.bfloat16         # EF written in stream dtype
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(e1, np.float32),
                               np.asarray(e0), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# division guards: one source of truth, sane degenerate behaviour
# ---------------------------------------------------------------------------
def test_guard_epsilons_single_source(case):
    assert DENOM_EPS == 1e-12 and RATE_EPS == 1e-6
    # a fully-dropped client under per_client_rate hits the RATE_EPS
    # guard, not DENOM_EPS (which would blow the debias up by 1e12)
    q = up_ops.debias_client_scale(jnp.ones(3), mode="per_client_rate",
                                   kept=jnp.zeros(3))
    np.testing.assert_allclose(np.asarray(q), 1.0 / RATE_EPS)
    # an all-clients-dropped packet under per_coord_count divides by
    # DENOM_EPS-guarded zero and stays finite
    agg, _, _ = up_ops.uplink_round(
        case["xp"], jnp.zeros((C, P)), case["w"],
        mode="per_coord_count", d_up=D_UP, impl="ref")
    assert np.isfinite(np.asarray(agg)).all()


def test_impl_resolution(monkeypatch):
    assert up_ops.resolved_impl("kernel") == "kernel"
    assert up_ops.resolved_impl("ref") == "ref"
    monkeypatch.setenv("REPRO_UPLINK_IMPL", "kernel")
    assert up_ops.resolved_impl() == "kernel"
    monkeypatch.delenv("REPRO_UPLINK_IMPL")
    assert up_ops.resolved_impl() == \
        ("kernel" if jax.default_backend() == "tpu" else "ref")
    with pytest.raises(ValueError, match="uplink impl"):
        up_ops.resolved_impl("jnp")


def test_pick_blocks_divisor_clamped(monkeypatch):
    """TPU blocks obey Mosaic's tiling rule (packet block P or a
    multiple of 8, client block C or a multiple of 128); explicit
    blocks are taken as given (the call pads to whole blocks); a
    backend without a row is refused."""
    assert pick_blocks(10, 18) == (18, 10)  # CPU: one block
    assert pick_blocks(8, 16, block_p=7, block_c=5) == (7, 5)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pick_blocks(10, 36) == (36, 10)  # paper MLP: one block
    assert pick_blocks(64, 4096) == (16, 64)
    bp, bc = pick_blocks(300, 5000)
    assert bp % 8 == 0 and bc == 128
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="no block sizes"):
        pick_blocks(10, 36)


@pytest.mark.parametrize("mode", DEBIAS_MODES)
def test_kernel_padded_blocks_match_ref(case, mode):
    """Blocks that do not divide (C, P) pad with zero clients and
    undelivered packets, which change nothing: the EF update stays
    bit-exact and the reductions match the reference."""
    a1, e1, s1 = _call(case, mode, use_ef=True, want_ssq=True,
                       impl="kernel", block_p=5, block_c=4)
    a0, e0, s0 = _call(case, mode, use_ef=True, want_ssq=True,
                       impl="ref")
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e0))


# ---------------------------------------------------------------------------
# engine / sweep integration with the kernel path forced
# ---------------------------------------------------------------------------
def _mk(algo, ef, seed=0, loss=0.3):
    from repro.core.server import FLConfig
    from repro.core.tra import TRAConfig
    return FLConfig(algo=algo, n_rounds=3, clients_per_round=6,
                    local_steps=2, batch_size=8, seed=seed,
                    error_feedback=ef, eval_every=100,
                    tra=TRAConfig(enabled=True, loss_rate=loss))


@pytest.fixture(scope="module")
def fl_setup():
    from repro.data.synthetic import generate_synthetic
    from repro.network.trace import ClientNetworks
    n = 12
    data = generate_synthetic(np.random.default_rng(0), n_clients=n,
                              alpha=0.5, beta=0.5)
    nets = ClientNetworks(np.linspace(0.5, 20.0, n), np.full(n, 0.05))
    return data, nets


@pytest.mark.parametrize("algo,ef", [("fedavg", True), ("qfedavg", False)])
def test_engine_kernel_path_matches_ref_path(fl_setup, monkeypatch,
                                             algo, ef):
    """The megakernel-backed engine reproduces the reference-path
    results (interpret-mode Pallas in the real round scan; tiled, so
    allclose rather than bitwise)."""
    from jax.flatten_util import ravel_pytree
    from repro.core.server import FederatedServer
    data, nets = fl_setup
    srv0 = FederatedServer(_mk(algo, ef), data, nets)
    srv0.run()
    monkeypatch.setenv("REPRO_UPLINK_IMPL", "kernel")
    srv1 = FederatedServer(_mk(algo, ef), data, nets)
    srv1.run()
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(srv1.params)[0]),
        np.asarray(ravel_pytree(srv0.params)[0]), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.array([r.train_loss for r in srv1.history]),
        np.array([r.train_loss for r in srv0.history]),
        rtol=1e-5, atol=1e-7)
    if ef:
        np.testing.assert_allclose(srv1._ef_mem, srv0._ef_mem,
                                   rtol=1e-5, atol=1e-6)


def test_sweep_kernel_path_bit_identical_to_singles(fl_setup, monkeypatch):
    """Under the sweep's vmap the custom_vmap rule routes the uplink to
    the scenario-batched grid — per-scenario results stay bit-identical
    to independent single-scenario kernel-path runs."""
    from jax.flatten_util import ravel_pytree
    from repro.core.server import FederatedServer
    from repro.core.sweep import SweepEngine
    data, nets = fl_setup
    monkeypatch.setenv("REPRO_UPLINK_IMPL", "kernel")
    cfgs = [_mk("fedavg", True, seed=0, loss=0.1),
            _mk("fedavg", True, seed=5, loss=0.5)]
    eng = SweepEngine.from_configs(cfgs, data, nets)
    states, logs = eng.run()
    for s, cfg in enumerate(cfgs):
        srv = FederatedServer(cfg, data, nets)
        srv.run()
        np.testing.assert_array_equal(
            logs["loss"][s],
            np.array([r.train_loss for r in srv.history], np.float32))
        np.testing.assert_array_equal(
            np.asarray(ravel_pytree(
                jax.tree.map(lambda x: x[s], states.params))[0]),
            np.asarray(ravel_pytree(srv.params)[0]))
        np.testing.assert_array_equal(np.asarray(states.ef_mem[s]),
                                      srv._ef_mem)
