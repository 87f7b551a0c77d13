#!/usr/bin/env python
"""flstat — round-inspection CLI for telemetry event streams.

Reads the JSONL event files the engine flushes
(``FederatedServer.run(events=...)``, ``run_grid(..., events=...)``,
``launch/fl_train.py --events-out``) and renders them for a terminal:

  python tools/flstat.py EVENTS.jsonl                 # summary
  python tools/flstat.py EVENTS.jsonl --rounds        # per-round table
  python tools/flstat.py EVENTS.jsonl --scenario 1    # one scenario
  python tools/flstat.py EVENTS.jsonl --programs      # compile ledger
  python tools/flstat.py EVENTS.jsonl --json          # machine summary

The summary view prints, per scenario: round count, final/min train
loss with a sparkline of the trajectory, mean delivered fraction vs
mean realized (channel) loss, cohort-share per bandwidth quartile
(slowest..fastest — the paper's Fig-3 selection-bias signal), mean
staleness histogram, and quarantine/buffer means when those subsystems
were compiled in. Absent columns mean the signal was not instrumented
in that run (level="off" subsystem), never zero.

stdlib-only on purpose: event files travel; this tool must run where
jax is not installed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.utils.events import RoundRecord, load_stream  # noqa: E402

BLOCKS = "▁▂▃▄▅▆▇█"


def sparkline(xs: Sequence[float], width: int = 24) -> str:
    """Unicode mini-chart of a series, downsampled to ``width`` by
    bucket means."""
    xs = [x for x in xs if x is not None]
    if not xs:
        return ""
    if len(xs) > width:
        n = len(xs)
        xs = [sum(xs[i * n // width:(i + 1) * n // width])
              / max(len(xs[i * n // width:(i + 1) * n // width]), 1)
              for i in range(width)]
    lo, hi = min(xs), max(xs)
    span = (hi - lo) or 1.0
    return "".join(BLOCKS[int((x - lo) / span * (len(BLOCKS) - 1))]
                   for x in xs)


def _mean(xs: List[Optional[float]]) -> Optional[float]:
    vs = [x for x in xs if x is not None]
    return sum(vs) / len(vs) if vs else None


def _vec_mean(rows: List[Optional[List[float]]]
              ) -> Optional[List[float]]:
    rows = [r for r in rows if r is not None]
    if not rows:
        return None
    n = len(rows[0])
    return [sum(r[i] for r in rows) / len(rows) for i in range(n)]


def _fmt(x: Optional[float], w: int = 7, p: int = 4) -> str:
    return f"{x:{w}.{p}f}" if x is not None else " " * (w - 1) + "-"


def scenario_summary(recs: List[RoundRecord]) -> Dict[str, object]:
    losses = [r.train_loss for r in recs]
    out: Dict[str, object] = {
        "rounds": len(recs),
        "final_loss": losses[-1] if losses else None,
        "min_loss": min(x for x in losses if x is not None)
        if any(x is not None for x in losses) else None,
        "loss_spark": sparkline(losses),
        "delivered_frac": _mean([r.delivered_frac for r in recs]),
        "realized_loss": _mean([r.realized_loss for r in recs]),
        "update_norm": _mean([r.update_norm for r in recs]),
        "ef_norm": _mean([r.ef_norm for r in recs]),
        "debias_scale_mean": _mean(
            [r.debias_scale_mean for r in recs]),
        "arrival_mean": _mean([r.arrival_mean for r in recs]),
        "quar_frac": _mean([r.quar_frac for r in recs]),
        "buf_fill": _mean([r.buf_fill for r in recs]),
        "part_quartile": _vec_mean([r.part_quartile for r in recs]),
        "stale_hist": _vec_mean([r.stale_hist for r in recs]),
        "downlink_loss": _mean([r.downlink_loss for r in recs]),
        "fec_recovered": _mean([r.fec_recovered for r in recs]),
        "arq_recovered": _mean([r.arq_recovered for r in recs]),
        "budget_escalations": _mean(
            [r.budget_escalations for r in recs]),
        "rec_level_mean": _mean([r.rec_level_mean for r in recs]),
    }
    return out


def print_summary(header, rounds: List[RoundRecord]) -> None:
    meta = header.get("meta") or {}
    env = header.get("env") or {}
    print(f"stream: config {header.get('config_fingerprint')}  "
          f"git {env.get('git')}  jax {env.get('jax')} "
          f"[{env.get('backend')}]")
    if meta:
        print("meta:   " + " ".join(f"{k}={v}" for k, v in meta.items()))
    scenarios = sorted({r.scenario for r in rounds})
    for s in scenarios:
        recs = [r for r in rounds if r.scenario == s]
        sm = scenario_summary(recs)
        print(f"\nscenario {s}: {sm['rounds']} rounds   "
              f"loss {_fmt(sm['final_loss'])} final / "
              f"{_fmt(sm['min_loss'])} min   {sm['loss_spark']}")
        line = []
        if sm["delivered_frac"] is not None:
            line.append(f"delivered {sm['delivered_frac']:.3f}")
        if sm["realized_loss"] is not None:
            line.append(f"realized-loss {sm['realized_loss']:.3f}")
        if sm["update_norm"] is not None:
            line.append(f"|update| {sm['update_norm']:.3f}")
        if sm["ef_norm"] is not None:
            line.append(f"|EF| {sm['ef_norm']:.3f}")
        if sm["debias_scale_mean"] is not None:
            line.append(f"debias-scale {sm['debias_scale_mean']:.3f}")
        if line:
            print("  uplink:  " + "  ".join(line))
        if sm["part_quartile"] is not None:
            q = sm["part_quartile"]
            print("  cohort share by bandwidth quartile "
                  "(slowest..fastest): "
                  + "  ".join(f"q{i}={x:.3f}" for i, x in enumerate(q))
                  + f"   {sparkline(q, width=len(q))}")
        line = []
        if sm["arrival_mean"] is not None:
            line.append(f"arrival-weight {sm['arrival_mean']:.3f}")
        if sm["buf_fill"] is not None:
            line.append(f"buffer-fill {sm['buf_fill']:.3f}")
        if sm["quar_frac"] is not None:
            line.append(f"quarantined {sm['quar_frac']:.4f}")
        if line:
            print("  server:  " + "  ".join(line))
        line = []
        if sm["downlink_loss"] is not None:
            line.append(f"downlink-loss {sm['downlink_loss']:.3f}")
        if sm["fec_recovered"] is not None:
            line.append(f"fec-recovered {sm['fec_recovered']:.4f}")
        if sm["arq_recovered"] is not None:
            line.append(f"arq-recovered {sm['arq_recovered']:.4f}")
        if sm["budget_escalations"] is not None:
            line.append(
                f"escalations {sm['budget_escalations']:.2f}/round")
        if sm["rec_level_mean"] is not None:
            line.append(f"rec-level {sm['rec_level_mean']:.2f}")
        if line:
            print("  recovery: " + "  ".join(line))
        if sm["stale_hist"] is not None:
            h = sm["stale_hist"]
            print(f"  staleness histogram (rounds late, last bin "
                  f"absorbs tail): {sparkline(h, width=len(h))}  "
                  + " ".join(f"{x:.1f}" for x in h))


def print_rounds(rounds: List[RoundRecord],
                 scenario: Optional[int]) -> None:
    recs = [r for r in rounds
            if scenario is None or r.scenario == scenario]
    cols = [("scn", lambda r: f"{r.scenario:3d}"),
            ("round", lambda r: f"{r.round:5d}"),
            ("loss", lambda r: _fmt(r.train_loss, 9)),
            ("deliv", lambda r: _fmt(r.delivered_frac, 6, 3)),
            ("chloss", lambda r: _fmt(r.realized_loss, 6, 3)),
            ("|upd|", lambda r: _fmt(r.update_norm, 7, 3)),
            ("arriv", lambda r: _fmt(r.arrival_mean, 6, 3)),
            ("quar", lambda r: _fmt(r.quar_frac, 6, 3)),
            ("buf", lambda r: _fmt(r.buf_fill, 5, 2)),
            ("cohort", lambda r: "" if r.cohort is None
             else ",".join(str(c) for c in r.cohort))]
    print("  ".join(name for name, _ in cols))
    for r in recs:
        print("  ".join(fn(r) for _, fn in cols))


def print_programs(programs: List[dict]) -> None:
    if not programs:
        print("no program events in stream (writer closed early?)")
        return
    print(f"{'cache':8} {'fingerprint':17} {'hit':>4} {'miss':>4} "
          f"{'calls':>5} {'compiles':>8} {'compile_s':>9} "
          f"{'dispatch_s':>10} {'h2d_bytes':>11} {'d2h_bytes':>11}")
    for p in programs:
        # streams written before the rename carry ``exec_seconds``
        dispatch = p.get("dispatch_seconds", p.get("exec_seconds", 0.0))
        print(f"{p.get('cache', '?'):8} {p.get('fingerprint', '?'):17} "
              f"{p.get('hits', 0):4d} {p.get('misses', 0):4d} "
              f"{p.get('calls', 0):5d} {p.get('compiles', 0):8d} "
              f"{p.get('compile_seconds', 0.0):9.3f} "
              f"{dispatch:10.3f} {p.get('h2d_bytes', 0):11d} "
              f"{p.get('d2h_bytes', 0):11d}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render telemetry event streams (see module doc)")
    ap.add_argument("events", help="JSONL event file")
    ap.add_argument("--rounds", action="store_true",
                    help="per-round table instead of the summary")
    ap.add_argument("--programs", action="store_true",
                    help="program-timing ledger (compile/dispatch/"
                    "cache/bytes)")
    ap.add_argument("--scenario", type=int, default=None,
                    help="restrict --rounds to one scenario")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable per-scenario summary")
    args = ap.parse_args(argv)

    header, rounds, programs = load_stream(args.events)
    if args.json:
        scenarios = sorted({r.scenario for r in rounds})
        out = {"config_fingerprint": header.get("config_fingerprint"),
               "meta": header.get("meta"),
               "scenarios": {
                   str(s): {k: v for k, v in scenario_summary(
                       [r for r in rounds if r.scenario == s]).items()
                       if k != "loss_spark"}
                   for s in scenarios},
               "programs": programs}
        print(json.dumps(out, indent=2))
        return 0
    if args.programs:
        print_programs(programs)
        return 0
    if args.rounds:
        print_rounds(rounds, args.scenario)
        return 0
    print_summary(header, rounds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
