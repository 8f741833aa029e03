"""Observability report: run a traced workload, export the artifacts.

Drives a conflict-aware ``TxnService`` stream with tracing enabled and a
shared ``MetricsRegistry``, then writes

  results/obs_trace.json     Chrome ``trace_event`` JSON of the run's
                             ``service/*`` host spans (admission, epoch
                             formation, dispatches, joins), admission-
                             decision instants, ``engine/gc_sweep`` and
                             ``engine/reassign_k`` spans AND the flight
                             recorder's per-ticket async lifecycle lanes
                             — load it in Perfetto or chrome://tracing;
  results/obs_health.json    {"meta", "health", "counters", "phases"}:
                             the post-run MVCC health gauges, the full
                             registry snapshot, and host-time stats per
                             span name from the span ring (spans are
                             unfenced: a dispatch span is the host's
                             enqueue cost, a join span its wait);

and prints a markdown health report. ``--validate`` re-reads the
exported trace and checks the Chrome trace invariants (B/E LIFO
matching, monotonic timestamps) — the CI obs-smoke gate.

    PYTHONPATH=src python -m benchmarks.obs_report [--quick] [--validate]
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR
from repro.core.engine import BohmEngine
from repro.core.txn import Workload, make_batch
from repro.obs import (FlightRecorder, PhaseTracer, run_metadata,
                       stitch_chrome_trace, validate_chrome_trace)
from repro.runtime import setup_compile_cache
from repro.service import TxnService

T, OPS, R = 64, 4, 256


def _workload() -> Workload:
    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def read_only(vals, args):
        return vals, jnp.zeros((), bool)

    return Workload(name="inc", n_read=OPS, n_write=OPS, payload_words=2,
                    branches=(rmw, read_only))


N_PARTS = 8


def _batch(rng, part=None, ops=OPS, t=T):
    """Partition-local batches: each batch's keys stay inside one of
    ``N_PARTS`` record ranges, so the admission window sees disjoint
    batches (merge / overlap / hop) AND same-partition collisions
    (conflict fallback) — the trace shows every decision kind.
    Partition 0 is RESERVED for the interactive point batch, so its
    queue jump is always hop-legal."""
    if part is None:
        part = int(rng.integers(1, N_PARTS))
    lo, hi = part * R // N_PARTS, (part + 1) * R // N_PARTS
    reads = rng.integers(lo, hi, (t, ops))
    wmask = rng.random((t, ops)) < 0.5
    writes = np.where(wmask, reads, -1)
    types = rng.integers(0, 2, t)
    args = rng.integers(1, 5, (t, 1))
    return make_batch(reads, writes, types, args)


def run(n_batches: int, spill: bool) -> dict:
    tracer = PhaseTracer(enabled=True)
    recorder = FlightRecorder(enabled=True)
    eng = BohmEngine(R, _workload(), ring_slots=8,
                     spill_slots=64 if spill else 0,
                     tracer=tracer)
    svc = TxnService(eng, max_inflight=2, admission_window=4,
                     flight=recorder)
    rng = np.random.default_rng(0)
    tickets = svc.submit_many([_batch(rng) for _ in range(n_batches)])
    # deterministic scheduler-decision tail: two same-partition bulk
    # batches are HELD (they conflict, so neither merges), then an
    # interactive point batch on the reserved partition jumps them
    # (admission/hop + admission/class_promote), and two commuting
    # width-mismatched batches dispatch as one exec chain
    # (admission/chain_depth)
    tickets += svc.submit_many([_batch(rng, part=3), _batch(rng, part=3)])
    tickets.append(svc.submit(_batch(rng, part=0, ops=2, t=16),
                              latency_class="interactive"))
    tickets += svc.submit_many([_batch(rng, part=1, ops=3),
                                _batch(rng, part=2, ops=5)])
    snap = svc.begin_snapshot()
    for t in tickets:
        svc.wait(t)
    svc.release_snapshot(snap)
    eng.gc_sweep()
    svc.drain()

    health = svc.health()
    counters = eng.metrics.snapshot(include_gauges=False)
    phases = []
    for name, durs in sorted(tracer.span_durations().items()):
        d = np.asarray(durs) * 1e3
        phases.append({"phase": name, "count": len(durs),
                       "mean_ms": round(float(d.mean()), 4),
                       "p50_ms": round(float(np.percentile(d, 50)), 4),
                       "max_ms": round(float(d.max()), 4)})

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    trace_path = RESULTS_DIR / "obs_trace.json"
    # one Perfetto file: sync phase spans + per-ticket async flight lanes
    with open(trace_path, "w") as f:
        json.dump(stitch_chrome_trace(tracer, recorder), f, indent=1)
    health_path = RESULTS_DIR / "obs_health.json"
    with open(health_path, "w") as f:
        json.dump({"meta": run_metadata(), "health": health,
                   "counters": counters, "phases": phases}, f, indent=2,
                  default=str)
    return {"trace_path": trace_path, "health_path": health_path,
            "health": health, "counters": counters, "phases": phases}


def report(out: dict) -> None:
    print("## Observability report\n")
    print("### Phase spans\n")
    print("| span | count | mean ms | p50 ms | max ms |")
    print("|---|---|---|---|---|")
    for p in out["phases"]:
        print(f"| {p['phase']} | {p['count']} | {p['mean_ms']} | "
              f"{p['p50_ms']} | {p['max_ms']} |")
    print("\n### Health gauges\n")
    print("| gauge | value |")
    print("|---|---|")
    for k, v in out["health"].items():
        if isinstance(v, (list, dict)):
            continue
        print(f"| {k} | {v} |")
    slo = out["health"].get("flight_slo") or {}
    if slo:
        print("\n### Flight SLO (per latency class)\n")
        print("| class | count | p50 ms | p99 ms | mean ms |")
        print("|---|---|---|---|---|")
        for cls, g in sorted(slo.items()):
            print(f"| {cls} | {g['count']} | {g['p50_ms']} | "
                  f"{g['p99_ms']} | {g['mean_ms']} |")
    blocking = out["health"].get("flight_blocking_records") or []
    if blocking:
        print("\n### Blocking records (conflict attribution top-K)\n")
        print("| record | blocks |")
        print("|---|---|")
        for rec, n_ in blocking:
            print(f"| {rec} | {n_} |")
    print("\n### Counters\n")
    print("| counter | value |")
    print("|---|---|")
    for k, v in sorted(out["counters"].items()):
        if isinstance(v, (int, float)):
            print(f"| {k} | {v} |")
    print(f"\ntrace: {out['trace_path']}")
    print(f"health: {out['health_path']}")


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short stream (CI smoke)")
    ap.add_argument("--validate", action="store_true",
                    help="re-read the exported trace and check Chrome "
                         "trace invariants (CI gate)")
    ap.add_argument("--batches", type=int, default=None)
    ap.add_argument("--spill", action="store_true",
                    help="attach a spill tier so spill gauges are live")
    args = ap.parse_args()

    n = args.batches or (8 if args.quick else 32)
    out = run(n, spill=args.spill)
    report(out)

    if args.validate:
        trace = json.loads(out["trace_path"].read_text())
        counts = validate_chrome_trace(trace)
        assert counts["spans"] > 0, "trace exported no spans"
        assert any(e["ph"] == "i" for e in trace["traceEvents"]), \
            "trace exported no admission-decision instants"
        names = {e.get("name") for e in trace["traceEvents"]}
        missing = {"admission/hop", "admission/chain_depth",
                   "admission/class_promote"} - names
        assert not missing, f"scheduler instants missing: {missing}"
        assert counts["async_lanes"] > 0, "no flight-recorder async lanes"
        print(f"trace valid: {counts}")


if __name__ == "__main__":
    main()
