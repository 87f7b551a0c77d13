"""Device-resident telemetry (ISSUE 9).

* Bit-identity lock: ``TelemetryConfig()`` (level="off" — the default)
  computes EXACTLY the frozen PR-8 round step
  (tests/_legacy_engine_v8.py) for fedavg/scaffold/qfedavg, ±TRA,
  ±error feedback, with netsim/faults paths on — the telemetry
  subsystem costs nothing when compiled out.
* Telemetry-on math neutrality: turning the level up changes NO
  training math — losses, cohorts and final params stay bitwise equal
  to the off run.
* One-program grid: a telemetry-on sweep grid compiles to ONE
  vmap(scan) program and its flushed per-scenario RoundRecords match
  an unswept FederatedServer run field-for-field.
* Scan-vs-per_round history parity: block-flushed ``RoundLog`` history
  agrees with the per_round engine field-for-field, and so do the
  telemetry event records both engines stream.
* Checkpoint: level="full" TelemetryState rides ``EngineState``
  through save/load bit-identically like any other carry.
* Program registry: every cache lookup logs the static-signature
  fingerprint; distinct configs get distinct fingerprints, a forged
  collision raises, and the ledger re-check passes.
* Event stream: JSONL round-trip through EventWriter/load_stream,
  monotonic-round enforcement, absence-vs-zero field semantics, and a
  flstat parse of a real stream.
"""
import dataclasses
import io
import json
import os
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core import telemetry as tele_mod
from repro.core.engine import _static_key, static_signature
from repro.core.fairness import fairness_report
from repro.core.mlp import mlp_init
from repro.core.selection import SelectionConfig
from repro.core.server import (FederatedServer, FLConfig, RoundLog,
                               run_grid)
from repro.core.sweep import SweepEngine
from repro.core.telemetry import (ProgramRegistry, TelemetryConfig,
                                  TelemetryState, records_from_logs)
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.netsim import DefenseConfig, FaultConfig, NetSimConfig
from repro.utils.events import (EventWriter, RoundRecord, fingerprint_of,
                                load_stream)
from tests._legacy_engine_v8 import make_legacy_v8_round_step

N_CLIENTS = 20


@pytest.fixture(scope="module")
def data():
    return generate_synthetic(np.random.default_rng(0),
                              n_clients=N_CLIENTS, alpha=0.5, beta=0.5)


@pytest.fixture(scope="module")
def nets():
    from repro.network.trace import ClientNetworks
    return ClientNetworks(np.linspace(0.5, 20.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))


def _cfg(*, algo="fedavg", tra_on=True, ef=False, rounds=4, cpr=8,
         seed=0, level="off", faults_on=False, eval_every=10 ** 6,
         engine="scan"):
    faults = (FaultConfig(enabled=True, corrupt_rate=0.1,
                          corrupt_scale=0.5)
              if faults_on else FaultConfig())
    defense = (DefenseConfig(screen=True, clip=True, clip_norm=20.0)
               if faults_on else DefenseConfig())
    return FLConfig(
        algo=algo, n_rounds=rounds, clients_per_round=cpr,
        local_steps=2, batch_size=8, lr=0.1, eval_every=eval_every,
        seed=seed, error_feedback=ef, engine=engine,
        sel=SelectionConfig(),
        tra=TRAConfig(enabled=tra_on, loss_rate=0.3),
        netsim=NetSimConfig(
            channel="gilbert_elliott" if tra_on else "iid",
            burst_len=8.0, deadline=tra_on, deadline_s=60.0),
        faults=faults, defense=defense,
        telemetry=TelemetryConfig(level=level))


def _vec(params):
    return np.asarray(ravel_pytree(params)[0])


# ---------------------------------------------------------------------------
# bit-identity locks against the frozen PR-8 step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("algo", ["fedavg", "scaffold", "qfedavg"])
@pytest.mark.parametrize("tra_on,ef,faults_on",
                         [(False, False, False), (True, True, False),
                          (True, False, True)])
def test_telemetry_off_bit_identical_to_legacy_v8(algo, tra_on, ef,
                                                  faults_on, data,
                                                  nets):
    """The default ``TelemetryConfig()`` computes exactly the frozen
    PR-8 step — netsim and fault paths included."""
    cfg = _cfg(algo=algo, tra_on=tra_on, ef=ef, faults_on=faults_on)
    srv = FederatedServer(cfg, data, nets)
    eng = srv.engine
    params0 = mlp_init(jax.random.PRNGKey(cfg.seed))

    state, logs = eng.run_block(eng.init_state(params0), 0,
                                cfg.n_rounds)

    legacy = jax.jit(make_legacy_v8_round_step(cfg, eng.cohort))
    lstate = eng.init_state(params0)
    lids, llosses = [], []
    for t in range(cfg.n_rounds):
        lstate, out = legacy(eng.ctx, lstate, jnp.int32(t))
        lids.append(np.asarray(out["ids"]))
        llosses.append(np.asarray(out["loss"]))

    np.testing.assert_array_equal(np.asarray(logs["ids"]),
                                  np.stack(lids))
    np.testing.assert_array_equal(np.asarray(logs["loss"]),
                                  np.stack(llosses))
    np.testing.assert_array_equal(_vec(state.params),
                                  _vec(lstate.params))
    np.testing.assert_array_equal(np.asarray(state.ef_mem),
                                  np.asarray(lstate.ef_mem))


def test_telemetry_off_emits_no_tele_logs(data, nets):
    cfg = _cfg(level="off")
    eng = FederatedServer(cfg, data, nets).engine
    _, logs = eng.run_block(
        eng.init_state(mlp_init(jax.random.PRNGKey(0))), 0, 2)
    assert not [k for k in logs if k.startswith("tele/")]


@pytest.mark.parametrize("level", ["scalars", "full"])
def test_telemetry_on_training_math_unchanged(level, data, nets):
    """Any telemetry level leaves losses/cohorts/params bitwise equal
    to the off run — telemetry reads, never writes."""
    off = _cfg(level="off", tra_on=True, ef=True)
    on = _cfg(level=level, tra_on=True, ef=True)
    p0 = mlp_init(jax.random.PRNGKey(0))
    eoff = FederatedServer(off, data, nets).engine
    eon = FederatedServer(on, data, nets).engine
    soff, loff = eoff.run_block(eoff.init_state(p0), 0, off.n_rounds)
    son, lon = eon.run_block(eon.init_state(p0), 0, on.n_rounds)
    np.testing.assert_array_equal(np.asarray(loff["loss"]),
                                  np.asarray(lon["loss"]))
    np.testing.assert_array_equal(np.asarray(loff["ids"]),
                                  np.asarray(lon["ids"]))
    np.testing.assert_array_equal(_vec(soff.params), _vec(son.params))
    # and the on run flushed telemetry scan outputs
    assert "tele/delivered_frac" in lon
    assert "tele/realized_loss" in lon
    assert "tele/update_norm" in lon


def test_level_is_static_program_structure(data, nets):
    """Telemetry level is part of the static signature (it changes the
    compiled program), so off/scalars/full are distinct cache keys —
    and distinct registry fingerprints."""
    keys = {lvl: _static_key(_cfg(level=lvl))
            for lvl in ("off", "scalars", "full")}
    assert len(set(keys.values())) == 3
    sigs = [static_signature(_cfg(level=lvl))
            for lvl in ("off", "scalars", "full")]
    assert sigs[0] != sigs[1] != sigs[2] and sigs[0] != sigs[2]
    assert len({fingerprint_of(k) for k in keys.values()}) == 3


def test_full_level_accumulates_per_client(data, nets):
    cfg = _cfg(level="full", rounds=6)
    srv = FederatedServer(cfg, data, nets)
    srv.run()
    stats = tele_mod.final_client_stats(srv._state.tele)
    total = cfg.n_rounds * cfg.clients_per_round
    assert stats["part_count"].shape == (N_CLIENTS,)
    assert stats["part_count"].sum() == pytest.approx(total)
    # arrival mass only accrues to participants
    assert np.all(stats["arrival_mass"][stats["part_count"] == 0] == 0)

    with pytest.raises(ValueError):
        tele_mod.final_client_stats(
            tele_mod.init_telemetry_state(TelemetryConfig(), N_CLIENTS))


# ---------------------------------------------------------------------------
# sweep: one program, records match unswept field-for-field
# ---------------------------------------------------------------------------
def test_sweep_one_program_and_records_match_unswept(data, nets,
                                                     tmp_path):
    tele_mod.REGISTRY.reset()
    base = _cfg(level="full", rounds=4, eval_every=2)
    cfgs = [dataclasses.replace(
        base, tra=dataclasses.replace(base.tra, loss_rate=r))
        for r in (0.1, 0.3)]
    grid_path = str(tmp_path / "grid.jsonl")
    run_grid(cfgs, data, nets, events=grid_path)
    assert tele_mod.REGISTRY.programs_for("sweep") == 1
    tele_mod.REGISTRY.assert_unique()

    _, grid_rounds, programs = load_stream(grid_path)
    assert len(grid_rounds) == 2 * base.n_rounds
    assert any(p.get("cache") == "sweep" for p in programs)

    for s, cfg in enumerate(cfgs):
        srv = FederatedServer(cfg, data, nets)
        single_path = str(tmp_path / f"single{s}.jsonl")
        srv.run(events=single_path)
        _, single_rounds, _ = load_stream(single_path)
        mine = [r for r in grid_rounds if r.scenario == s]
        for r in mine:
            r.scenario = 0
        assert mine == single_rounds


def test_sweep_rejects_mixed_telemetry_levels(data, nets):
    """The level is program structure: a grid mixing levels is not one
    program and must be refused up front."""
    cfgs = [_cfg(level="off"), _cfg(level="scalars")]
    with pytest.raises(ValueError):
        SweepEngine.from_configs(cfgs, data, nets)


# ---------------------------------------------------------------------------
# satellite 3: scan-flushed history vs per_round engine, field-for-field
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("level", ["off", "full"])
def test_scan_history_matches_per_round_engine(level, data, nets,
                                               tmp_path):
    scan_cfg = _cfg(level=level, rounds=6, eval_every=3)
    loop_cfg = dataclasses.replace(scan_cfg, engine="per_round")

    scan_path = str(tmp_path / "scan.jsonl")
    loop_path = str(tmp_path / "loop.jsonl")
    scan_hist = FederatedServer(scan_cfg, data, nets).run(
        events=scan_path)
    loop_hist = FederatedServer(loop_cfg, data, nets).run(
        events=loop_path)

    assert len(scan_hist) == len(loop_hist) == scan_cfg.n_rounds
    for a, b in zip(scan_hist, loop_hist):
        assert isinstance(a, RoundLog) and isinstance(b, RoundLog)
        assert a.round == b.round
        assert a.train_loss == b.train_loss
        assert (a.report is None) == (b.report is None)
        if a.report is not None:
            assert a.report.as_dict() == b.report.as_dict()

    # the streamed event records agree field-for-field too
    _, scan_recs, _ = load_stream(scan_path)
    _, loop_recs, _ = load_stream(loop_path)
    assert scan_recs == loop_recs
    if level == "full":
        assert all(r.delivered_frac is not None for r in scan_recs)


# ---------------------------------------------------------------------------
# checkpoint: TelemetryState is an ordinary carry
# ---------------------------------------------------------------------------
def test_telemetry_state_checkpoints_bit_identical(data, nets,
                                                   tmp_path):
    cfg = _cfg(level="full", rounds=4)
    srv = FederatedServer(cfg, data, nets)
    srv.run()
    state = srv._state
    assert np.asarray(state.tele.part_count).shape == (N_CLIENTS,)

    path = str(tmp_path / "ckpt")
    save_checkpoint(path, state)
    restored, _ = load_checkpoint(path, state)
    for name in TelemetryState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(state.tele, name)),
            np.asarray(getattr(restored.tele, name)),
            err_msg=f"tele.{name} not bit-identical after round-trip")


# ---------------------------------------------------------------------------
# program registry: signature logging + uniqueness
# ---------------------------------------------------------------------------
def test_registry_logs_every_lookup_and_asserts_uniqueness(data, nets):
    tele_mod.REGISTRY.reset()
    cfg_a = _cfg(level="off")
    cfg_b = _cfg(level="scalars")
    FederatedServer(cfg_a, data, nets)
    st = tele_mod.REGISTRY.get(
        "engine", fingerprint_of((_static_key(cfg_a),
                                  cfg_a.clients_per_round)))
    assert st is not None and st.hits + st.misses >= 1
    # same config again: a hit on the same fingerprint, no new program
    FederatedServer(cfg_a, data, nets)
    st2 = tele_mod.REGISTRY.get(
        "engine", fingerprint_of((_static_key(cfg_a),
                                  cfg_a.clients_per_round)))
    assert st2.hits >= 1
    FederatedServer(cfg_b, data, nets)
    # every lookup logged a fingerprint; off and scalars are distinct
    # program families (the step cache may already hold either, so
    # count ledger entries, not fresh builds)
    engine_fps = {fp for (kind, fp) in tele_mod.REGISTRY._stats
                  if kind == "engine"}
    assert len(engine_fps) >= 2
    tele_mod.REGISTRY.assert_unique()


def test_registry_raises_on_fingerprint_collision():
    reg = ProgramRegistry()
    fp = reg.record_lookup("engine", ("key-a",), hit=False)
    # forge a collision: different key, same fingerprint slot
    reg._stats[("engine", fp)].key_repr = repr(("key-b",))
    with pytest.raises(RuntimeError, match="collision"):
        reg.record_lookup("engine", ("key-a",), hit=True)


def test_timed_program_records_compile_and_exec():
    reg_before = tele_mod.REGISTRY.get("engine", "deadbeef")
    assert reg_before is None or reg_before.calls == 0
    fn = jax.jit(lambda x: x * 2)
    timed = tele_mod.TimedProgram(fn, "engine", "deadbeef", "block")
    timed(jnp.ones(4))          # compiles
    timed(jnp.ones(4))          # cached
    st = tele_mod.REGISTRY.get("engine", "deadbeef")
    assert st.calls == 2
    assert st.compiles == 1
    assert st.compile_seconds > 0
    # attribute fall-through keeps jit probes working on the wrapper
    assert timed._cache_size() >= 1


def test_timed_program_books_dispatch_bytes_and_spans(tmp_path):
    reg = ProgramRegistry()
    real = tele_mod.REGISTRY
    tele_mod.REGISTRY = reg
    try:
        timed = tele_mod.TimedProgram(jax.jit(lambda x: x + 1), "eval",
                                      "cafe", "eval")
        timed(jnp.ones(3))          # compiles: not a dispatch
        for _ in range(3):
            timed(jnp.ones(3))
        timed.count(h2d=12, d2h=4)
        timed.count(d2h=4)
        with tele_mod.Span("fl.eval.copy"):
            pass
    finally:
        tele_mod.REGISTRY = real
    st = reg.get("eval", "cafe")
    assert (st.calls, st.compiles) == (4, 1)
    assert st.dispatch_seconds > 0 and st.compile_seconds > 0
    assert (st.h2d_bytes, st.d2h_bytes) == (12, 8)
    assert not hasattr(st, "exec_seconds")
    spans = reg.span_stats()
    # the compiling call's span is left out of the host-time counts
    assert spans["fl.eval.dispatch"]["count"] == 3
    assert spans["fl.eval.dispatch"]["seconds"] > 0
    assert spans["fl.eval.copy"]["count"] == 1
    with pytest.raises(ValueError):
        tele_mod.Span("eval.copy")
    # a span that closes while a profiler trace records is not booked
    tele_mod.REGISTRY = reg
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tele_mod.Span("fl.eval.copy"):
                pass
        finally:
            jax.profiler.stop_trace()
    finally:
        tele_mod.REGISTRY = real
    assert reg.span_stats()["fl.eval.copy"]["count"] == 1
    with pytest.raises(ValueError):
        tele_mod.TimedProgram(timed, "eval", "cafe", "nope")


def test_server_counts_block_and_eval_bytes(data, nets):
    reg = ProgramRegistry()
    real = tele_mod.REGISTRY
    tele_mod.REGISTRY = reg
    try:
        srv = FederatedServer(_cfg(rounds=2, eval_every=2), data, nets)
        srv._state, logs = srv.engine.run_block(srv._state, 0, 2)
        srv.evaluate()
    finally:
        tele_mod.REGISTRY = real
    eng = next(st for st in reg._stats.values() if st.kind == "engine")
    ev = next(st for st in reg._stats.values() if st.kind == "eval")
    assert eng.d2h_bytes == sum(v.nbytes for v in logs.values()) > 0
    assert eng.h2d_bytes == 0
    assert ev.calls == 1 and ev.misses == 1
    assert ev.h2d_bytes == (srv.eval_X.nbytes + srv.eval_Y.nbytes
                            + srv.eval_W.nbytes)
    assert ev.d2h_bytes == 3 * N_CLIENTS * 4
    assert set(reg.span_stats()) >= {
        "fl.block.args", "fl.block.copy",
        "fl.eval.upload", "fl.eval.copy", "fl.eval.report"}


# the default FedAvg logs two arrays a block; faults, screening and the
# deadline (as in the benchmark's defended cell) four
LOG_KEYS = [(dict(tra_on=False), {"loss", "ids"}),
            (dict(faults_on=True), {"loss", "ids", "quarantine",
                                    "arrival"})]


class _RecordedLog:
    """A block log or eval output that records when its copy starts and
    converts."""

    def __init__(self, key, arr, calls):
        self.key, self.arr, self.calls = key, arr, calls

    def copy_to_host_async(self):
        self.calls.append(("start", self.key))
        self.arr.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.calls.append(("convert", self.key))
        return np.asarray(self.arr, dtype)


@pytest.mark.parametrize("kw,keys", LOG_KEYS)
def test_run_block_starts_every_log_copy_before_converting(kw, keys,
                                                           data, nets):
    srv = FederatedServer(_cfg(rounds=2, **kw), data, nets)
    eng, real, calls, seen = srv.engine, srv.engine._block, [], {}

    class Block:
        def __call__(self, *args):
            state, logs = real(*args)
            seen.update(logs)
            return state, {k: _RecordedLog(k, v, calls)
                           for k, v in logs.items()}

        def count(self, **kw):
            real.count(**kw)

    eng._block = Block()
    _, out = eng.run_block(srv._state, 0, 2)
    assert set(out) == set(seen) == keys
    starts = [i for i, (op, _) in enumerate(calls) if op == "start"]
    converts = [i for i, (op, _) in enumerate(calls) if op == "convert"]
    assert sorted(k for op, k in calls if op == "start") == sorted(keys)
    assert len(converts) == len(keys) and max(starts) < min(converts)
    for k, v in seen.items():
        ref = np.asarray(v)
        assert isinstance(out[k], np.ndarray)
        assert (out[k].dtype, out[k].shape) == (ref.dtype, ref.shape)
        assert out[k].tobytes() == ref.tobytes()


@pytest.mark.parametrize("kw,keys", LOG_KEYS)
def test_sweep_run_block_starts_every_log_copy_before_converting(
        kw, keys, data, nets):
    """The sweep flushes like the single engine: every copy starts
    before any converts, the block books one fetch, and the logs come
    back scenario-major and bit-identical."""
    reg = ProgramRegistry()
    real_reg = tele_mod.REGISTRY
    tele_mod.REGISTRY = reg
    try:
        eng = SweepEngine.from_configs(
            [_cfg(rounds=2, seed=s, **kw) for s in (0, 1)], data, nets)
        real, calls, seen = eng._block, [], {}

        class Block:
            def __call__(self, *args):
                state, logs = real(*args)
                seen.update(logs)
                return state, {k: _RecordedLog(k, v, calls)
                               for k, v in logs.items()}

            def count(self, **kw):
                real.count(**kw)

        eng._block = Block()
        _, out = eng.run_block(eng.init_states(), 0, 2)
    finally:
        tele_mod.REGISTRY = real_reg
    assert set(out) == set(seen) == keys
    starts = [i for i, (op, _) in enumerate(calls) if op == "start"]
    converts = [i for i, (op, _) in enumerate(calls) if op == "convert"]
    assert sorted(k for op, k in calls if op == "start") == sorted(keys)
    assert len(converts) == len(keys) and max(starts) < min(converts)
    for k, v in seen.items():
        ref = np.moveaxis(np.asarray(v), 0, 1)
        assert (out[k].dtype, out[k].shape) == (ref.dtype, ref.shape)
        assert out[k].shape[:2] == (2, 2)
        assert out[k].tobytes() == ref.tobytes()
    sw = next(st for st in reg._stats.values() if st.kind == "sweep")
    assert (sw.d2h_fetches, sw.d2h_arrays) == (1, len(keys))
    assert sw.d2h_bytes == sum(v.nbytes for v in out.values())


@pytest.mark.parametrize("kw,keys", LOG_KEYS)
def test_run_block_counts_one_fetch_a_block(kw, keys, data, nets):
    reg = ProgramRegistry()
    real = tele_mod.REGISTRY
    tele_mod.REGISTRY = reg
    try:
        srv = FederatedServer(_cfg(rounds=4, **kw), data, nets)
        srv._state, a = srv.engine.run_block(srv._state, 0, 2)
        srv._state, b = srv.engine.run_block(srv._state, 2, 2)
    finally:
        tele_mod.REGISTRY = real
    assert set(a) == set(b) == keys
    eng = next(st for st in reg._stats.values() if st.kind == "engine")
    assert (eng.d2h_fetches, eng.d2h_arrays) == (2, 2 * len(keys))
    assert eng.d2h_bytes == sum(v.nbytes for v in [*a.values(),
                                                    *b.values()])
    rec = eng.as_dict()
    assert (rec["d2h_fetches"], rec["d2h_arrays"]) == (2, 2 * len(keys))


EVAL_OUTPUTS = ("acc", "correct", "n")


@pytest.mark.parametrize("kw,keys", LOG_KEYS)
def test_evaluate_starts_every_output_copy_before_converting(kw, keys,
                                                             data, nets):
    srv = FederatedServer(_cfg(rounds=2, **kw), data, nets)
    srv._state, _ = srv.engine.run_block(srv._state, 0, 2)
    real, calls, seen = srv._eval_fn, [], []

    class Eval:
        def __call__(self, *args):
            out = real(*args)
            seen.extend(out)
            return tuple(_RecordedLog(k, v, calls)
                         for k, v in zip(EVAL_OUTPUTS, out))

        def count(self, **kw):
            real.count(**kw)

    srv._eval_fn = Eval()
    report = srv.evaluate()
    starts = [i for i, (op, _) in enumerate(calls) if op == "start"]
    converts = [i for i, (op, _) in enumerate(calls) if op == "convert"]
    assert sorted(k for op, k in calls if op == "start") \
        == sorted(EVAL_OUTPUTS)
    assert len(converts) == 3 and max(starts) < min(converts)
    # the report the old way: three conversions of the real outputs
    acc, correct, n = (np.asarray(v) for v in seen)
    ref = fairness_report(acc, n, correct)
    assert np.array(dataclasses.astuple(report)).tobytes() \
        == np.array(dataclasses.astuple(ref)).tobytes()


@pytest.mark.parametrize("kw,keys", LOG_KEYS)
def test_evaluate_counts_one_fetch_an_eval(kw, keys, data, nets,
                                           monkeypatch):
    puts = []
    real_put = jax.device_put

    def put(x, *a, **k):
        puts.append(x)
        return real_put(x, *a, **k)

    reg = ProgramRegistry()
    real = tele_mod.REGISTRY
    tele_mod.REGISTRY = reg
    try:
        srv = FederatedServer(_cfg(rounds=2, **kw), data, nets)
        monkeypatch.setattr(jax, "device_put", put)
        srv.evaluate()
        srv.evaluate()
    finally:
        tele_mod.REGISTRY = real
    ev = next(st for st in reg._stats.values() if st.kind == "eval")
    assert (ev.d2h_fetches, ev.d2h_arrays) == (2, 6)
    assert ev.h2d_bytes == 2 * (srv.eval_X.nbytes + srv.eval_Y.nbytes
                                + srv.eval_W.nbytes)
    assert ev.d2h_bytes == 2 * 3 * N_CLIENTS * 4
    assert (ev.calls, ev.compiles) == (2, 1)
    # one put an eval, of the whole eval set
    assert len(puts) == 2
    assert all(len(x) == 3 and x[0] is srv.eval_X for x in puts)


# ---------------------------------------------------------------------------
# event stream + flstat
# ---------------------------------------------------------------------------
def test_event_writer_round_trip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    rec = RoundRecord(round=0, scenario=1, train_loss=1.5,
                      delivered_frac=0.9, cohort=[3, 1],
                      part_quartile=[0.5, 0.25, 0.25, 0.0])
    with EventWriter(path, config_fingerprint="abc123",
                     meta={"n_rounds": 2}) as w:
        w.write_round(rec)
        w.write_round(RoundRecord(round=1, scenario=1, train_loss=1.2))
        w.write_program_stats([{"fingerprint": "abc123",
                                "kind": "engine", "hits": 1}])
    header, rounds, programs = load_stream(path)
    assert header["config_fingerprint"] == "abc123"
    assert header["meta"] == {"n_rounds": 2}
    assert {"git", "platform", "python", "time"} <= set(header["env"])
    assert rounds == [rec, RoundRecord(round=1, scenario=1,
                                       train_loss=1.2)]
    # absence semantics: unset Optional fields stay None, not 0
    assert rounds[1].delivered_frac is None
    # the registry's own kind field must not clobber the event tag
    assert programs and programs[0]["kind"] == "program"
    assert programs[0]["cache"] == "engine"


def test_event_writer_enforces_monotonic_rounds(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with EventWriter(path) as w:
        w.write_round(RoundRecord(round=3, scenario=0))
        w.write_round(RoundRecord(round=2, scenario=1))  # other scenario
        with pytest.raises(ValueError, match="non-monotonic"):
            w.write_round(RoundRecord(round=3, scenario=0))


def test_load_stream_rejects_streams_without_header(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "round", "round": 0}) + "\n")
    with pytest.raises(ValueError, match="no header"):
        load_stream(path)
    with open(path, "a") as f:
        f.write("{not json\n")
    with pytest.raises(ValueError, match="malformed"):
        load_stream(path)


def test_records_from_logs_layouts():
    """Single-engine (k,) and sweep (S,k) layouts demux to the same
    records; keys absent from the logs stay None on the record."""
    k = 3
    single = {"loss": np.arange(k, dtype=np.float32),
              "ids": np.tile(np.array([[2, 0]]), (k, 1)),
              "tele/delivered_frac": np.full(k, 0.5, np.float32)}
    recs = records_from_logs(single, t0=10)
    assert [r.round for r in recs] == [10, 11, 12]
    assert recs[0].cohort == [2, 0]
    assert recs[0].delivered_frac == 0.5
    assert recs[0].realized_loss is None

    stacked = {key: np.stack([v, v]) for key, v in single.items()}
    recs2 = records_from_logs(stacked)
    assert len(recs2) == 2 * k
    assert [r.scenario for r in recs2] == [0] * k + [1] * k


def test_flstat_parses_real_stream(data, nets, tmp_path):
    import importlib
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    flstat = importlib.import_module("flstat")

    cfg = _cfg(level="full", rounds=4, eval_every=2)
    path = str(tmp_path / "ev.jsonl")
    FederatedServer(cfg, data, nets).run(events=path)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert flstat.main([path]) == 0
    assert "scenario 0" in buf.getvalue()
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert flstat.main([path, "--json"]) == 0
    summary = json.loads(buf.getvalue())
    sc = summary["scenarios"]["0"]
    assert sc["rounds"] == cfg.n_rounds
    assert sc["delivered_frac"] is not None
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert flstat.main([path, "--rounds"]) == 0
        assert flstat.main([path, "--programs"]) == 0


def test_programs_count_tokens_and_the_resident_base(data, nets):
    """A token client model books the tokens each block trains and each
    eval scores, and its frozen base once; the MLP books none."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.core.client_model import Zamba2LoRA
    from repro.data.tokens import token_dataset
    from repro.network.trace import ClientNetworks
    arch = dataclasses.replace(
        get_config("zamba2-7b"), n_layers=2, d_model=64, n_heads=2,
        n_kv_heads=2, head_dim=64, d_ff=64, vocab=64, ssm_state=8,
        ssm_chunk=8, hybrid_layer_ids=(1,), attention_hidden_size=128,
        adapter_rank=4)
    model = Zamba2LoRA(arch, rank=2, alpha=4.0, seq_len=8,
                       base_dtype="float32")
    rng = np.random.default_rng(0)
    toks = token_dataset([rng.integers(0, 64, (3, 9)) for _ in range(4)],
                         [rng.integers(0, 64, (2, 5)) for _ in range(4)])
    reg = ProgramRegistry()
    real = tele_mod.REGISTRY
    tele_mod.REGISTRY = reg
    try:
        srv = FederatedServer(FLConfig(
            algo="fedavg", clients_per_round=2, local_steps=2, batch_size=1,
            model=model, seed=1), toks,
            ClientNetworks(nets.upload_mbps[:4], nets.packet_loss[:4]))
        for t in (0, 3):
            srv._state, _ = srv.engine.run_block(srv._state, t, 3)
        srv.evaluate()
        mlp = FederatedServer(_cfg(rounds=2, eval_every=2), data, nets)
        mlp._state, _ = mlp.engine.run_block(mlp._state, 0, 2)
        mlp.evaluate()
    finally:
        tele_mod.REGISTRY = real
    base_bytes = sum(x.nbytes for x in jax.tree.leaves(srv.frozen))
    by_kind = {}
    for st in reg._stats.values():
        by_kind.setdefault(st.kind, []).append(st)
    eng = {st.resident_bytes: st for st in by_kind["engine"]}
    ev = {st.resident_bytes: st for st in by_kind["eval"]}
    # 2 blocks x 3 rounds x 2 clients x 2 steps x 1 sequence x 8 tokens
    assert eng[base_bytes].tokens == 2 * 3 * 2 * 2 * 1 * 8
    # 4 clients x 2 held-out sequences x 4 next tokens
    assert ev[base_bytes].tokens == 4 * 2 * 4
    assert eng[0].tokens == 0 and ev[0].tokens == 0
    assert base_bytes > 0


def test_flstat_programs_renders_dispatch_and_bytes(tmp_path):
    import importlib
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    flstat = importlib.import_module("flstat")
    path = tmp_path / "ev.jsonl"
    new = {"kind": "program", "cache": "eval", "fingerprint": "f1",
           "calls": 3, "dispatch_seconds": 0.25, "h2d_bytes": 4800,
           "d2h_bytes": 120}
    # a stream written before the rename still renders its time
    old = {"kind": "program", "cache": "engine", "fingerprint": "f0",
           "calls": 2, "exec_seconds": 0.5}
    path.write_text(json.dumps({"kind": "header"}) + "\n"
                    + json.dumps(new) + "\n" + json.dumps(old) + "\n")
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert flstat.main([str(path), "--programs"]) == 0
    head, row_new, row_old = buf.getvalue().splitlines()
    assert "dispatch_s" in head and "exec_s" not in head
    assert "h2d_bytes" in head and "d2h_bytes" in head
    assert row_new.split()[-3:] == ["0.250", "4800", "120"]
    assert row_old.split()[-3:] == ["0.500", "0", "0"]
