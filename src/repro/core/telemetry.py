"""Device-resident telemetry for the round-scan engine.

The paper's claims — loss tolerance below a critical packet-loss
fraction, selection bias under thresholding, bottom-quartile fairness —
are statements about *per-round, per-client* signals. Since the engine
compiles K rounds into one ``lax.scan`` (and the sweep vmaps whole
grids), those signals are invisible unless they are accumulated ON
DEVICE and flushed with the block. This module is that layer:

  * ``TelemetryConfig(level=...)`` — a STATIC engine knob:

      - ``"off"``     compiles the whole subsystem out. Locked bitwise
                      against the frozen PR-8 step
                      (tests/_legacy_engine_v8.py), same contract the
                      netsim/selection/async/faults subsystems honour.
      - ``"scalars"`` adds per-round scalars and compact per-cohort
                      aggregates (delivered-packet fraction, realized
                      loss rate, participation share per bandwidth
                      quartile, staleness histogram, quarantine
                      fraction, EF/update norms, debias-scale mean) to
                      the scan outputs. O(k · bins) flush traffic.
      - ``"full"``    additionally carries cumulative per-client
                      aggregates (participation counts, arrival mass,
                      staleness and quarantined-packet sums) through
                      the scan as ``TelemetryState`` inside
                      ``EngineState`` — the (N,) vectors the bias /
                      fairness analyses window over. Checkpoints
                      round-trip it bit-identically like any other
                      carry.

    The level changes the compiled program (extra scan outputs), so it
    is part of the static signature: it must agree across a sweep, and
    it can NOT vary per scenario.

  * ``records_from_logs`` — demuxes flushed block logs (single-engine
    ``(k, ...)`` or sweep-stacked ``(S, k, ...)``) into typed
    ``RoundRecord``s (`repro/utils/events.py`) for the JSONL event
    stream that ``tools/flstat.py`` renders.

  * ``REGISTRY`` / ``TimedProgram`` — the host-side program-timing
    layer wrapping the engine/sweep/eval program caches: every cache
    lookup logs the ``static_signature`` fingerprint (hit or insert),
    every call records host time split compile vs dispatch (the
    enqueue: JAX returns before the device runs the program) and the
    bytes its call site copies to and from the device, and a
    fingerprint collision between two DIFFERENT static keys raises
    immediately — "one program per grid" becomes a measured, logged
    invariant instead of a benchmark-only assertion.

  * ``SPANS`` / ``Span`` — the program's host spans: ``fl.*`` names
    that a profiler trace shows on the same clock as the device, so an
    idle gap on the device can be put down to what the host was doing
    in it; ``REGISTRY`` keeps each span's count and host seconds.

Telemetry reads signals the round already computes (masks, arrival
weights, quarantine counts, EF rows); it never changes the training
math at any level — asserted down to trajectory bit-identity for
``off`` and value-identity sweep-vs-single for ``full``.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.utils.events import RoundRecord, fingerprint_of

logger = logging.getLogger("repro.telemetry")

LEVELS = ("off", "scalars", "full")
N_QUARTILES = 4

# TelemetryConfig fields a scenario may vary without changing program
# structure: none — the level and histogram shape are program structure.
SWEEP_VARYING_TELE_FIELDS = ()


@dataclasses.dataclass
class TelemetryConfig:
    """Static telemetry knobs (module doc). ``stale_bins`` sizes the
    per-round lateness histogram (last bin absorbs everything later,
    including never-arriving uploads pinned at MAX_LATENESS)."""
    level: str = "off"
    stale_bins: int = 8

    def __post_init__(self):
        assert self.level in LEVELS, self.level
        assert self.stale_bins >= 2, self.stale_bins


class TelemetryState(NamedTuple):
    """Cumulative per-client aggregates, a scan carry inside
    ``EngineState``. All fields are (N,) f32 at level="full" and (0,)
    otherwise (the zero-size ride-along pattern every other optional
    carry uses)."""
    part_count: jnp.ndarray    # cohort memberships to date
    arrival_mass: jnp.ndarray  # sum of effective arrival weights
    stale_sum: jnp.ndarray     # sum of observed deadline lateness
    quar_pkts: jnp.ndarray     # quarantined packets attributed


def init_telemetry_state(tcfg: TelemetryConfig,
                         n_clients: int) -> TelemetryState:
    n = n_clients if tcfg.level == "full" else 0
    # four distinct buffers — aliasing one zeros array across the fields
    # trips the engine's donate_argnums ("donate the same buffer twice")
    return TelemetryState(*(jnp.zeros((n,), jnp.float32)
                            for _ in range(4)))


def bandwidth_quartiles(logbw: jnp.ndarray) -> jnp.ndarray:
    """(N,) int32 quartile id per client (0 = slowest 25%) from the
    static log-bandwidth draw. Ties break toward the lower quartile,
    matching ``np.quantile``-based host analyses."""
    qs = jnp.quantile(logbw, jnp.array([0.25, 0.5, 0.75], jnp.float32))
    return jnp.sum(logbw[:, None] > qs[None, :], axis=1).astype(jnp.int32)


def round_telemetry(tcfg: TelemetryConfig, tele: TelemetryState, *,
                    ids: jnp.ndarray,
                    n_clients: int,
                    pkt_mask: jnp.ndarray,
                    loss_mask: jnp.ndarray,
                    old_vec: jnp.ndarray,
                    new_vec: jnp.ndarray,
                    scale: jnp.ndarray,
                    logbw: Optional[jnp.ndarray],
                    ef_new_rows: Optional[jnp.ndarray] = None,
                    arrival: Optional[jnp.ndarray] = None,
                    lateness: Optional[jnp.ndarray] = None,
                    qcnt: Optional[jnp.ndarray] = None,
                    buf_due: Optional[jnp.ndarray] = None,
                    buf_empty_due: float = 0.0,
                    down_frac: Optional[jnp.ndarray] = None,
                    fec_frac: Optional[jnp.ndarray] = None,
                    arq_frac: Optional[jnp.ndarray] = None,
                    bud_escal: Optional[jnp.ndarray] = None,
                    bud_level: Optional[jnp.ndarray] = None):
    """Per-round telemetry, computed from signals the round already
    produced. Called ONLY when the level is not "off" (the caller
    compiles the whole call out otherwise).

    Returns ``(logs, new_tele)``: ``logs`` is a flat dict of
    ``"tele/..."`` scan outputs — only the keys whose subsystems are
    compiled into this program are present, so absence in the flushed
    record means "signal does not exist here", never "zero" — and
    ``new_tele`` is the updated cumulative carry (input carry at
    level="scalars").
    """
    C, P = pkt_mask.shape
    onehot = jnp.zeros((n_clients,), jnp.float32).at[ids].add(1.0)
    logs: Dict[str, jnp.ndarray] = {
        # post-deadline kept-packet fraction: what the server aggregates
        "tele/delivered_frac": pkt_mask.mean(),
        # channel-only realized drop fraction (iid draw or GE chain) —
        # deadline/server-mode folding excluded by construction
        "tele/realized_loss": 1.0 - loss_mask.mean(),
        "tele/update_norm": jnp.linalg.norm(new_vec - old_vec),
        "tele/debias_scale_mean": scale.mean(),
    }
    if logbw is not None and logbw.shape[0] == n_clients:
        qid = bandwidth_quartiles(logbw)
        shares = jnp.zeros((N_QUARTILES,), jnp.float32
                           ).at[qid].add(onehot) / C
        logs["tele/part_quartile"] = shares
    if ef_new_rows is not None:
        logs["tele/ef_norm"] = jnp.linalg.norm(ef_new_rows)
    if arrival is not None:
        logs["tele/arrival_mean"] = arrival.mean()
    if lateness is not None:
        b = jnp.clip(lateness, 0.0, tcfg.stale_bins - 1).astype(jnp.int32)
        logs["tele/stale_hist"] = jnp.zeros(
            (tcfg.stale_bins,), jnp.float32).at[b].add(1.0)
    if qcnt is not None:
        logs["tele/quar_frac"] = qcnt.sum() / (C * P)
    if buf_due is not None and buf_due.shape[0] > 0:
        logs["tele/buf_fill"] = (buf_due < buf_empty_due).mean()
    # full-duplex / recovery signals (PR-10): realized downlink drop
    # fraction, packet fractions the FEC parity prepass and the ARQ
    # retries recovered, and the loss-budget controller's escalation
    # count and mean policy level. All None-gated so v9 call sites
    # produce identical logs (keys absent, not zero).
    if down_frac is not None:
        logs["tele/downlink_loss"] = down_frac
    if fec_frac is not None:
        logs["tele/fec_recovered"] = fec_frac
    if arq_frac is not None:
        logs["tele/arq_recovered"] = arq_frac
    if bud_escal is not None:
        logs["tele/budget_escalations"] = bud_escal
    if bud_level is not None:
        logs["tele/rec_level_mean"] = bud_level

    if tcfg.level == "full":
        tele = TelemetryState(
            part_count=tele.part_count.at[ids].add(1.0),
            arrival_mass=tele.arrival_mass.at[ids].add(
                arrival if arrival is not None
                else jnp.ones((C,), jnp.float32)),
            stale_sum=tele.stale_sum.at[ids].add(
                lateness if lateness is not None
                else jnp.zeros((C,), jnp.float32)),
            quar_pkts=tele.quar_pkts.at[ids].add(
                qcnt if qcnt is not None
                else jnp.zeros((C,), jnp.float32)),
        )
    return logs, tele


# map from flushed log keys to RoundRecord fields; vector-valued keys
# become lists on the record
_SCALAR_KEYS = {
    "tele/delivered_frac": "delivered_frac",
    "tele/realized_loss": "realized_loss",
    "tele/update_norm": "update_norm",
    "tele/ef_norm": "ef_norm",
    "tele/debias_scale_mean": "debias_scale_mean",
    "tele/arrival_mean": "arrival_mean",
    "tele/quar_frac": "quar_frac",
    "tele/buf_fill": "buf_fill",
    "tele/downlink_loss": "downlink_loss",
    "tele/fec_recovered": "fec_recovered",
    "tele/arq_recovered": "arq_recovered",
    "tele/budget_escalations": "budget_escalations",
    "tele/rec_level_mean": "rec_level_mean",
}
_VECTOR_KEYS = {
    "tele/part_quartile": "part_quartile",
    "tele/stale_hist": "stale_hist",
}


def records_from_logs(logs: Dict[str, np.ndarray], *, t0: int = 0,
                      scenario0: int = 0,
                      with_cohort: bool = True) -> List[RoundRecord]:
    """Demux flushed block logs into typed per-round records.

    Accepts both layouts the engines flush: single-engine ``(k, ...)``
    and sweep scenario-major ``(S, k, ...)`` (detected from
    ``logs["loss"].ndim``). Records are ordered scenario-major,
    round-ascending — exactly the order ``EventWriter.write_round``
    enforces. ``t0`` is the absolute round index of the block's first
    round; ``scenario0`` offsets scenario ids for chunked grids.
    """
    loss = np.asarray(logs["loss"])
    stacked = loss.ndim == 2
    S = loss.shape[0] if stacked else 1
    k = loss.shape[1] if stacked else loss.shape[0]

    def cell(v, s, i):
        a = np.asarray(v)
        return a[s, i] if stacked else a[i]

    out: List[RoundRecord] = []
    for s in range(S):
        for i in range(k):
            rec = RoundRecord(round=t0 + i, scenario=scenario0 + s,
                              train_loss=float(cell(logs["loss"], s, i)))
            if with_cohort and "ids" in logs:
                rec.cohort = [int(x) for x in cell(logs["ids"], s, i)]
            for key, field in _SCALAR_KEYS.items():
                if key in logs:
                    setattr(rec, field, float(cell(logs[key], s, i)))
            for key, field in _VECTOR_KEYS.items():
                if key in logs:
                    setattr(rec, field,
                            [float(x) for x in cell(logs[key], s, i)])
            out.append(rec)
    return out


def final_client_stats(tele: TelemetryState) -> Dict[str, np.ndarray]:
    """Host view of the cumulative per-client aggregates (level="full").
    For sweep-stacked state the arrays keep their leading (S,) axis."""
    if np.asarray(tele.part_count).shape[-1] == 0:
        raise ValueError(
            "per-client telemetry aggregates need "
            "TelemetryConfig(level='full') — this state carries the "
            "compiled-out zero-size placeholders")
    return {"part_count": np.asarray(tele.part_count),
            "arrival_mass": np.asarray(tele.arrival_mass),
            "stale_sum": np.asarray(tele.stale_sum),
            "quar_pkts": np.asarray(tele.quar_pkts)}


# ---------------------------------------------------------------------------
# program-timing registry: the step caches' observability layer
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ProgramStat:
    """Counters for one compiled program family (one static signature
    x cohort/shape family), keyed by the fingerprint of the cache key
    the engine/sweep caches use."""
    fingerprint: str
    kind: str                   # "engine" | "sweep" | "launch" | "eval"
    key_repr: str               # full static cache key (diagnosable!)
    hits: int = 0               # cache lookups that found the program
    misses: int = 0             # cache lookups that built it
    calls: int = 0              # dispatches through the timing wrapper
    compiles: int = 0           # dispatches that traced+compiled
    compile_seconds: float = 0.0  # wall time of compiling dispatches
    # host time of the cached calls: argument flattening, donation and
    # the enqueue — not the device's execution, which JAX does not wait
    # for before it returns
    dispatch_seconds: float = 0.0
    h2d_bytes: int = 0          # host->device bytes its call sites sent
    d2h_bytes: int = 0          # device->host bytes they copied back
    d2h_fetches: int = 0        # device->host fetches (one wait each)
    d2h_arrays: int = 0         # arrays those fetches copied

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        # the full key repr is large; the registry keeps it for
        # collision diagnosis, event streams carry a digest
        d["key_repr"] = (self.key_repr[:200] + "..."
                         if len(self.key_repr) > 200 else self.key_repr)
        return d


class ProgramRegistry:
    """Process-wide ledger of every compiled round-step program.

    ``record_lookup`` is called by the engine/sweep caches on EVERY
    lookup with the full static key; the signature fingerprint is
    logged (`repro.telemetry` logger, DEBUG) so two configs silently
    colliding onto one program is now diagnosable — and actively
    impossible: a fingerprint observed with two different keys raises
    ``RuntimeError`` at lookup time.
    """

    def __init__(self):
        self._stats: Dict[Any, ProgramStat] = {}
        # span name -> [count, host seconds] (``Span``)
        self._spans: Dict[str, List[float]] = {}
        self.compiles = 0           # compiling dispatches, all programs

    def reset(self) -> None:
        self._stats.clear()
        self._spans.clear()
        self.compiles = 0

    def record_lookup(self, kind: str, key: Any, *, hit: bool) -> str:
        fp = fingerprint_of(key)
        st = self._stats.get((kind, fp))
        key_repr = repr(key)
        if st is None:
            st = ProgramStat(fingerprint=fp, kind=kind,
                             key_repr=key_repr)
            self._stats[(kind, fp)] = st
        elif st.key_repr != key_repr:
            raise RuntimeError(
                f"static-signature fingerprint collision: {kind} "
                f"programs for two DIFFERENT static keys share "
                f"fingerprint {fp} — cache keying is broken\n"
                f"  key A: {st.key_repr[:300]}\n"
                f"  key B: {key_repr[:300]}")
        if hit:
            st.hits += 1
        else:
            st.misses += 1
        logger.debug("%s step-cache %s: signature %s", kind,
                     "hit" if hit else "insert", fp)
        return fp

    def _stat(self, kind: str, fp: str) -> ProgramStat:
        st = self._stats.get((kind, fp))
        if st is None:  # timing without a lookup (tests driving fns)
            st = ProgramStat(fingerprint=fp, kind=kind, key_repr="")
            self._stats[(kind, fp)] = st
        return st

    def record_call(self, kind: str, fp: str, seconds: float,
                    compiled: bool) -> None:
        st = self._stat(kind, fp)
        st.calls += 1
        if compiled:
            st.compiles += 1
            st.compile_seconds += seconds
            self.compiles += 1
        else:
            st.dispatch_seconds += seconds

    def record_bytes(self, kind: str, fp: str, *, h2d: int = 0,
                     d2h: int = 0, arrays: int = 0) -> None:
        st = self._stat(kind, fp)
        st.h2d_bytes += h2d
        st.d2h_bytes += d2h
        if arrays:
            st.d2h_fetches += 1
            st.d2h_arrays += arrays

    def record_span(self, name: str, seconds: float) -> None:
        c = self._spans.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += seconds

    def stats(self) -> List[Dict[str, Any]]:
        return [st.as_dict() for st in self._stats.values()]

    def span_stats(self) -> Dict[str, Dict[str, float]]:
        """Per span name: how many closed (``count``) and their host
        time (``seconds``), less those in which a program compiled or
        that closed while a profiler trace recorded (``Span``)."""
        return {name: {"count": c[0], "seconds": c[1]}
                for name, c in self._spans.items()}

    def get(self, kind: str, fp: str) -> Optional[ProgramStat]:
        return self._stats.get((kind, fp))

    def assert_unique(self) -> None:
        """Every fingerprint maps to exactly one static key (collisions
        raise eagerly in record_lookup; this re-checks the ledger and
        that no fingerprint is duplicated across kinds with mismatched
        keys — the test-suite entry point for the invariant)."""
        by_fp: Dict[str, str] = {}
        for (kind, fp), st in self._stats.items():
            if not st.key_repr:
                continue
            if fp in by_fp and by_fp[fp] != st.key_repr:
                raise RuntimeError(
                    f"fingerprint {fp} maps to two static keys")
            by_fp[fp] = st.key_repr

    def programs_for(self, kind: str) -> int:
        """Number of distinct program families built (cache misses) for
        one cache kind — benchmarks' one-program-per-grid probe."""
        return sum(1 for (k, _), st in self._stats.items()
                   if k == kind and st.misses > 0)


REGISTRY = ProgramRegistry()


class TimedProgram:
    """Transparent timing wrapper around one cached jitted callable.

    Every call is wall-clocked inside the host span
    ``fl.<span>.dispatch`` and recorded against the program's signature
    fingerprint; a call that grew the jit's compiled-program count is
    booked as a compile (trace+lower+compile included), everything else
    as dispatch. JAX returns once the program is enqueued, so dispatch
    time is host time, not the device's. ``count`` books the bytes a
    call site copies to and from the device, and its fetches back.
    Attribute access falls through to the wrapped function, so
    ``_cache_size()`` probes and donation checks keep working on the
    wrapped object.
    """

    def __init__(self, fn, kind: str, fingerprint: str, span: str):
        self._fn = fn
        self._kind = kind
        self._fp = fingerprint
        self._span = f"fl.{span}.dispatch"
        if self._span not in SPANS:
            raise ValueError(f"no host span {self._span!r} in SPANS")

    def __call__(self, *args, **kwargs):
        with Span(self._span):
            probe = getattr(self._fn, "_cache_size", None)
            n0 = probe() if probe is not None else -1
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            n1 = probe() if probe is not None else -1
            REGISTRY.record_call(self._kind, self._fp, dt,
                                 compiled=n1 > n0 >= 0)
        return out

    def count(self, *, h2d: int = 0, d2h: int = 0,
              arrays: int = 0) -> None:
        """Book ``h2d`` bytes sent to the device and ``d2h`` bytes
        copied back for this program; ``arrays`` > 0 books the copy
        back as one fetch of that many arrays."""
        REGISTRY.record_bytes(self._kind, self._fp, h2d=h2d, d2h=d2h,
                              arrays=arrays)

    def __getattr__(self, name):
        return getattr(self._fn, name)


# ---------------------------------------------------------------------------
# host spans: the program's own names on the profiler's clock
# ---------------------------------------------------------------------------
# Every host span the program opens. Children nest in their parent
# (``fl.block`` holds ``fl.block.args`` ... ``fl.block.copy``). The
# names start with ``fl.`` so that they never collide with a caller's
# own spans.
SPANS = {
    "fl.block": "RoundScanEngine.run_block: one block of rounds, its "
                "logs on the host",
    "fl.block.args": "the block's round indices, a device array",
    "fl.block.dispatch": "the block program's call: argument "
                         "flattening, state donation, enqueue",
    "fl.block.copy": "the wait for the block program and the logs' "
                     "device-to-host copy",
    "fl.round.dispatch": "the single-round program's call",
    "fl.sweep.dispatch": "the sweep block program's call",
    "fl.launch.dispatch": "a launch-route step's call",
    "fl.eval": "FederatedServer.evaluate: one fairness eval",
    "fl.eval.upload": "the eval set's host-to-device copy",
    "fl.eval.dispatch": "the eval program's call",
    "fl.eval.copy": "the wait for the eval program and the copy of "
                    "its outputs",
    "fl.eval.report": "fairness_report on the host",
}


class Span:
    """``with Span("fl.block.copy"): ...`` — a host span of the program.

    It writes a ``jax.profiler.TraceAnnotation`` under the span's plain
    name, which a profiler trace shows on the device's clock, and books
    the span's host seconds in ``REGISTRY.span_stats()``, which costs
    two clock reads. The counts read the steady state with the profiler
    off: a span in which a registered program compiled, or that closes
    while a trace records (the profiler slows the host), is not booked.
    With the profiler off the annotation costs an enter and an exit.
    """
    __slots__ = ("name", "_ann", "_c0", "_t0")

    def __init__(self, name: str):
        if name not in SPANS:
            raise ValueError(f"no host span {name!r} in SPANS")
        self.name = name

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._c0 = REGISTRY.compiles
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if REGISTRY.compiles == self._c0 \
                and not TraceAnnotation.is_enabled():
            REGISTRY.record_span(self.name, dt)
        return False
