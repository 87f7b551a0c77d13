"""Sweep-engine correctness.

* S-scenario vmap(scan) runs are BIT-IDENTICAL to S independent
  single-scenario runs (fedavg/scaffold/qfedavg, +-TRA, +-error
  feedback, heterogeneous per-scenario datasets, shared datasets).
* The engine's in-scan ``fused_debias_aggregate`` matches
  ``kernels/tra_agg/ops.tra_aggregate_packed`` for all DEBIAS_MODES.
* EngineState buffers are donated (updated in place) by the engine and
  sweep jits.
* Static-signature validation rejects mixed grids.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.engine import fused_debias_aggregate
from repro.core.server import FederatedServer, FLConfig, run_grid
from repro.core.async_agg import AsyncConfig
from repro.core.selection import SelectionConfig
from repro.core.sweep import SweepEngine, scenario_from_config
from repro.core.tra import TRAConfig
from repro.data.synthetic import (generate_synthetic,
                                  stage_scenarios_on_device)
from repro.kernels.tra_agg.ops import DEBIAS_MODES, tra_aggregate_packed
from repro.netsim import DefenseConfig, FaultConfig, NetSimConfig
from repro.netsim.recovery import RecoveryConfig
from repro.network.trace import ClientNetworks

N_CLIENTS = 20


@pytest.fixture(scope="module")
def data():
    return generate_synthetic(np.random.default_rng(0),
                              n_clients=N_CLIENTS, alpha=0.5, beta=0.5)


@pytest.fixture(scope="module")
def data_het():
    """A second, more heterogeneous draw (alpha/beta re-draw)."""
    return generate_synthetic(np.random.default_rng(1),
                              n_clients=N_CLIENTS, alpha=2.0, beta=2.0)


@pytest.fixture(scope="module")
def nets():
    return ClientNetworks(np.linspace(0.5, 20.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))


def _cfg(seed=0, loss_rate=0.2, algo="fedavg", tra_on=True, ef=False,
         **kw):
    kw.setdefault("eval_every", 100)
    return FLConfig(algo=algo, n_rounds=4, clients_per_round=8,
                    local_steps=2, batch_size=8,
                    seed=seed, error_feedback=ef,
                    tra=TRAConfig(enabled=tra_on, loss_rate=loss_rate),
                    **kw)


def _params_vec(states, s):
    return np.asarray(ravel_pytree(
        jax.tree.map(lambda x: x[s], states.params))[0])


# ---------------------------------------------------------------------------
# bit-identity: sweep == S independent single-scenario runs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("algo", ["fedavg", "scaffold", "qfedavg"])
@pytest.mark.parametrize("tra_on,ef", [(False, False), (True, False),
                                       (True, True)])
def test_sweep_bit_identical_to_single_runs(algo, tra_on, ef, data,
                                            data_het, nets):
    """Scenarios vary seed, loss rate AND dataset; each must reproduce
    its independent FederatedServer run bit-for-bit."""
    cfgs = [_cfg(seed=0, loss_rate=0.1, algo=algo, tra_on=tra_on, ef=ef),
            _cfg(seed=3, loss_rate=0.3, algo=algo, tra_on=tra_on, ef=ef)]
    datas = [data, data_het]
    eng = SweepEngine.from_configs(cfgs, datas, nets)
    states, logs = eng.run()
    for s in range(2):
        srv = FederatedServer(cfgs[s], datas[s], nets)
        srv.run()
        single_loss = np.array([r.train_loss for r in srv.history],
                               np.float32)
        np.testing.assert_array_equal(logs["loss"][s], single_loss)
        np.testing.assert_array_equal(
            _params_vec(states, s),
            np.asarray(ravel_pytree(srv.params)[0]))
        if ef:
            np.testing.assert_array_equal(
                np.asarray(states.ef_mem[s]), srv._ef_mem)
        if algo == "scaffold":
            np.testing.assert_array_equal(
                np.asarray(states.c_i[s]), srv._c_i)


def test_sweep_shared_dataset_fast_path(data, nets):
    """Identical dataset objects take the stage-once/broadcast path and
    still match independent runs bit-for-bit (incl. per-round ids)."""
    cfgs = [_cfg(seed=s, loss_rate=0.1 + 0.1 * s) for s in range(3)]
    eng = SweepEngine.from_configs(cfgs, data, nets)
    assert not eng.data_batched          # stage-once path taken
    states, logs = eng.run()
    for s, cfg in enumerate(cfgs):
        srv = FederatedServer(cfg, data, nets)
        srv.run()
        np.testing.assert_array_equal(
            logs["loss"][s],
            np.array([r.train_loss for r in srv.history], np.float32))
        assert logs["ids"].shape == (3, 4, eng.cohort)
        np.testing.assert_array_equal(
            _params_vec(states, s),
            np.asarray(ravel_pytree(srv.params)[0]))


def test_run_grid_histories_and_reports(data, nets):
    """Server-level grid routing: demuxed histories match per-server
    runs, and fairness reports appear on the eval schedule."""
    cfgs = [_cfg(seed=0, eval_every=2), _cfg(seed=1, eval_every=2)]
    histories = run_grid(cfgs, data, nets)
    assert len(histories) == 2
    for cfg, hist in zip(cfgs, histories):
        srv = FederatedServer(cfg, data, nets)
        srv.run()
        assert [r.round for r in hist] == [r.round for r in srv.history]
        np.testing.assert_array_equal(
            np.array([r.train_loss for r in hist], np.float32),
            np.array([r.train_loss for r in srv.history], np.float32))
        # eval boundaries: rounds 1 and 3 (eval_every=2, n_rounds=4)
        assert hist[1].report is not None and hist[3].report is not None
        assert hist[0].report is None
        np.testing.assert_allclose(hist[3].report.sample_average,
                                   srv.history[-1].report.sample_average,
                                   rtol=1e-6)


def test_sweep_rejects_mixed_static_grid(data, nets):
    with pytest.raises(ValueError, match="static"):
        SweepEngine.from_configs(
            [_cfg(algo="fedavg"), _cfg(algo="qfedavg")], data, nets)
    with pytest.raises(ValueError, match="static"):
        SweepEngine.from_configs(
            [_cfg(ef=False), _cfg(ef=True)], data, nets)
    # varying seed / loss rate / selection is fine
    SweepEngine.from_configs(
        [_cfg(seed=0, loss_rate=0.1),
         _cfg(seed=1, loss_rate=0.5, selection="ratio",
              eligible_ratio=0.9)], data, nets)
    # length-mismatched per-scenario sequences must raise, not truncate
    with pytest.raises(ValueError, match="networks"):
        SweepEngine.from_configs(
            [_cfg(seed=s) for s in range(3)], data, [nets, nets])
    with pytest.raises(ValueError, match="datasets"):
        SweepEngine.from_configs(
            [_cfg(seed=s) for s in range(3)], [data, data], nets)


def _full_single_recipe(seed, loss_rate, corrupt_rate, trim, policy):
    """The defended cell of the corruption-tolerance recipe with the
    traced uplink FEC family (the benchmark's full.single program)."""
    return FLConfig(
        n_rounds=4, clients_per_round=12, local_steps=4, batch_size=16,
        eval_every=100, seed=seed,
        tra=TRAConfig(loss_rate=loss_rate, packet_floats=256),
        netsim=NetSimConfig(channel="gilbert_elliott", burst_len=8.0,
                            deadline=True, deadline_s=60.0),
        faults=FaultConfig(enabled=True, corrupt_rate=corrupt_rate,
                           corrupt_scale=0.5, fail_rate=0.1),
        defense=DefenseConfig(screen=True, clip=True, clip_norm=20.0,
                              trim=trim, trim_k=2),
        recovery=RecoveryConfig(policy=policy, traced=True, group=8,
                                retries=2.0, backoff=1.0))


def _traced_sel_async(seed, policy, mode, alpha):
    return _cfg(seed=seed, loss_rate=0.2 + 0.1 * seed,
                sel=SelectionConfig(policy=policy, traced=True),
                netsim=NetSimConfig(channel="gilbert_elliott",
                                    deadline=True, deadline_s=30.0),
                srv=AsyncConfig(mode=mode, traced=True,
                                staleness_alpha=alpha))


CTX_GRIDS = {
    "fedavg": lambda: [_cfg(seed=0, loss_rate=0.1),
                       _cfg(seed=3, loss_rate=0.3)],
    "full_single": lambda: [
        _full_single_recipe(0, 0.3, 0.1, True, "fec"),
        _full_single_recipe(1, 0.2, 0.0, False, "arq")],
    "traced_sel_async": lambda: [
        _traced_sel_async(0, "uniform", "sync", 0.5),
        _traced_sel_async(1, "bandwidth_threshold", "semi_sync", 1.0),
        _traced_sel_async(2, "gradient_norm", "async", 0.25)],
}


@pytest.mark.parametrize("grid", sorted(CTX_GRIDS))
def test_sweep_ctx_stacks_the_single_engine_ctx(grid, data, nets):
    """Slice i of the sweep's context is what a single engine builds for
    config i: every leaf's dtype, shape and value; the shared dataset
    is the single engine's staged dataset."""
    cfgs = CTX_GRIDS[grid]()
    eng = SweepEngine.from_configs(cfgs, data, nets)
    assert eng.ctx.frozen is None and not eng.data_batched
    for i, cfg in enumerate(cfgs):
        single = FederatedServer(cfg, data, nets).engine.ctx
        got = jax.tree.map(lambda x: x[i], eng.ctx._replace(data=None))
        want = single._replace(data=None)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)):
            where = f"cell {i} {jax.tree_util.keystr(path)}"
            assert (a.dtype, a.shape) == (b.dtype, b.shape), where
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=where)
        for a, b in zip(jax.tree.leaves(eng.ctx.data),
                        jax.tree.leaves(single.data)):
            assert (a.dtype, a.shape) == (b.dtype, b.shape)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sweep_refuses_a_scenario_of_another_program(data, nets):
    """A scenario built by hand for another program (here: the fault
    model compiled in) is refused by name, before anything is staged."""
    other = _cfg(seed=1, faults=FaultConfig(enabled=True))
    with pytest.raises(ValueError, match="static") as err:
        SweepEngine(_cfg(), [scenario_from_config(_cfg(), data, nets),
                             scenario_from_config(other, data, nets)])
    assert "scenario 1" in str(err.value)
    assert "faults.enabled" in str(err.value)


def test_stage_scenarios_on_device(data, data_het):
    dd = stage_scenarios_on_device([data, data_het])
    assert dd.train_x.shape[0] == 2
    assert dd.counts.shape == (2, N_CLIENTS)
    np.testing.assert_array_equal(np.asarray(dd.counts[0]),
                                  data.samples_per_client)
    np.testing.assert_array_equal(np.asarray(dd.counts[1]),
                                  data_het.samples_per_client)
    k = 0
    n = int(dd.counts[1, k])
    np.testing.assert_allclose(np.asarray(dd.train_x[1, k, :n]),
                               data_het.train_x[k])
    # cross-scenario padding is zero
    assert float(jnp.abs(dd.train_x[0, k, int(dd.counts[0, k]):]).sum()) \
        == 0.0


# ---------------------------------------------------------------------------
# fused in-scan aggregation == tra_agg kernel ops (all debias modes)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", DEBIAS_MODES)
def test_fused_agg_matches_kernel_ops(mode):
    """The engine's fused aggregation and the packed kernel entry point
    implement the same debias estimators — previously only kept in sync
    by a comment, now locked here."""
    rng = np.random.default_rng(42)
    C, P, F = 6, 16, 32
    d_up = P * F - 11                         # partial last packet
    pad = P * F - d_up
    flat = jnp.asarray(rng.normal(size=(C, d_up)).astype(np.float32))
    pkt_mask = jnp.asarray(
        (rng.random((C, P)) > 0.3).astype(np.float32))
    weights = jnp.asarray(rng.random(C).astype(np.float32) + 0.1)
    sufficient = jnp.asarray(
        (rng.random(C) > 0.5).astype(np.float32))
    loss_rate = jnp.float32(0.3)
    xp = jnp.pad(flat, ((0, 0), (0, pad))).reshape(C, P, F)
    # coordinate-weighted kept fraction (matches the engine's in-scan
    # computation and simulate_uploads' coord.mean())
    pcnt = jnp.full((P,), F, jnp.float32).at[-1].set(F - pad)
    kept = (pkt_mask @ pcnt) / d_up

    fused = fused_debias_aggregate(
        xp, pkt_mask, weights, mode=mode, d_up=d_up, kept=kept,
        sufficient=sufficient, loss_rate=loss_rate)

    # the kernel path consumes pre-masked updates
    coord = jnp.repeat(pkt_mask, F, axis=1)[:, :d_up]
    masked = flat * coord
    xk = jnp.pad(masked, ((0, 0), (0, pad))).reshape(C, P, F)
    kernel = tra_aggregate_packed(
        xk, pkt_mask, weights, mode=mode, kept_frac=kept,
        nominal_rate=jnp.full((C,), 0.3), sufficient=sufficient
    ).reshape(-1)[:d_up]

    np.testing.assert_allclose(np.asarray(fused), np.asarray(kernel),
                               rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# buffer donation: EngineState updated in place across dispatches
# ---------------------------------------------------------------------------
def _ptr(x):
    return x.unsafe_buffer_pointer()


def test_engine_state_buffers_donated(data, nets):
    """donate_argnums on the engine jits: the (N, D_up) error-feedback
    and SCAFFOLD buffers alias input->output instead of being copied."""
    cfg = _cfg(algo="scaffold", ef=True)
    srv = FederatedServer(cfg, data, nets)
    eng = srv.engine
    state = eng.init_state(srv.params)
    eng.run_block(state, 0, 2)                # compile outside the check
    state = eng.init_state(srv.params)
    p_ef, p_ci = _ptr(state.ef_mem), _ptr(state.c_i)
    new_state, _ = eng.run_block(state, 0, 2)
    assert _ptr(new_state.ef_mem) == p_ef
    assert _ptr(new_state.c_i) == p_ci
    with pytest.raises((RuntimeError, ValueError)):  # old buffer gone
        np.asarray(state.ef_mem)
    # the lowered program itself marks the state buffers as donated
    ts = jnp.arange(0, 2, dtype=jnp.int32)
    hlo = eng._block.lower(eng.ctx, new_state, ts).as_text()
    assert "jax.buffer_donor" in hlo or "tf.aliasing_output" in hlo


def test_sweep_state_buffers_donated(data, nets):
    cfgs = [_cfg(seed=s, algo="scaffold", ef=True) for s in range(2)]
    eng = SweepEngine.from_configs(cfgs, data, nets)
    eng.run_block(eng.init_states(), 0, 2)    # compile outside the check
    states = eng.init_states()
    p_ef, p_ci = _ptr(states.ef_mem), _ptr(states.c_i)
    new_states, _ = eng.run_block(states, 0, 2)
    assert _ptr(new_states.ef_mem) == p_ef
    assert _ptr(new_states.c_i) == p_ci
    with pytest.raises((RuntimeError, ValueError)):
        np.asarray(states.ef_mem)


def test_engines_share_compiled_programs(data, nets):
    """Engines whose configs differ only in scenario-varying or
    driver-level knobs (seed, loss rate, round/eval schedule,
    engine mode) share one jitted program — grid cells compile once."""
    s1 = FederatedServer(_cfg(seed=0, loss_rate=0.1), data, nets)
    s2 = FederatedServer(_cfg(seed=9, loss_rate=0.4), data, nets)
    assert s1.engine._block is s2.engine._block
    assert s1.engine._single is s2.engine._single
    s3 = FederatedServer(
        dataclasses.replace(_cfg(seed=0, loss_rate=0.1),
                            engine="per_round", n_rounds=7,
                            eval_every=3), data, nets)
    assert s3.engine._single is s1.engine._single


# ---------------------------------------------------------------------------
# selection-policy axis: traced cross-policy grid == standalone runs
# ---------------------------------------------------------------------------
def test_traced_policy_loss_sweep_cell_bitwise(data, nets):
    """A selection-policy × loss-rate grid compiled as ONE traced
    program: every cell must reproduce its standalone FederatedServer
    run (same traced SelectionConfig) bit-for-bit."""
    from repro.core.selection import SelectionConfig
    from repro.netsim import NetSimConfig
    ns = NetSimConfig(channel="gilbert_elliott", burst_len=4.0)
    cfgs = [_cfg(seed=s, loss_rate=r, netsim=ns,
                 sel=SelectionConfig(policy=p, traced=True,
                                     temperature=tmp))
            for s, (p, tmp) in enumerate(
                [("uniform", 1.0), ("bandwidth_threshold", 0.05),
                 ("loss_aware", 0.5)])
            for r in (0.1, 0.3)]
    eng = SweepEngine.from_configs(cfgs, data, nets)
    states, logs = eng.run()
    for s in (0, 3, 5):  # one cell per policy
        srv = FederatedServer(cfgs[s], data, nets)
        srv.run()
        np.testing.assert_array_equal(
            logs["loss"][s],
            np.array([r.train_loss for r in srv.history], np.float32))
        np.testing.assert_array_equal(
            _params_vec(states, s),
            np.asarray(ravel_pytree(srv.params)[0]))


def test_sweep_rejects_mixed_selection_modes(data, nets):
    """Static policies differing across cells need traced=True; mixing
    traced and untraced cells is two different programs."""
    from repro.core.selection import SelectionConfig
    with pytest.raises(ValueError, match="sel"):
        SweepEngine.from_configs(
            [_cfg(seed=0, sel=SelectionConfig(policy="uniform")),
             _cfg(seed=1, sel=SelectionConfig(
                 policy="bandwidth_threshold"))], data, nets)
    with pytest.raises(ValueError, match="sel"):
        SweepEngine.from_configs(
            [_cfg(seed=0, sel=SelectionConfig(traced=True)),
             _cfg(seed=1, sel=SelectionConfig(traced=False))],
            data, nets)
    # same static policy with different traced knobs is one program
    SweepEngine.from_configs(
        [_cfg(seed=0, sel=SelectionConfig(policy="bandwidth_threshold",
                                          temperature=0.1)),
         _cfg(seed=1, sel=SelectionConfig(policy="bandwidth_threshold",
                                          threshold_mbps=8.0))],
        data, nets)


def test_selection_knobs_share_compiled_programs(data, nets):
    """Traced sel knobs (threshold/temperature/explore) ride
    ScenarioCtx: engines differing only in them share one program."""
    from repro.core.selection import SelectionConfig
    s1 = FederatedServer(
        _cfg(seed=0, sel=SelectionConfig(policy="bandwidth_threshold",
                                         temperature=0.1)), data, nets)
    s2 = FederatedServer(
        _cfg(seed=1, sel=SelectionConfig(policy="bandwidth_threshold",
                                         threshold_mbps=8.0,
                                         explore=0.3)), data, nets)
    assert s1.engine._block is s2.engine._block
