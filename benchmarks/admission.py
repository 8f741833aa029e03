"""Out-of-order admission benchmark — reordering vs FIFO-prefix merging.

A skewed update stream interleaves two batch species:

  cold    YCSB RMW over one of several disjoint key stripes
          (round-robin; 10 ops in disjoint_cold, ``MIX_OPS`` short
          update txns in mixed) — cold batches of different stripes
          commute, so the scheduler merges them into one CC epoch
          and/or chains their exec phases;
  hot     a hot-key storm: ``MIX_HOT_BURST`` back-to-back batches all
          RMW the SAME contended stripe (the stripe rotates per burst).
          Burst members conflict with each other but commute with the
          cold stripes and with other bursts — the head-of-line case:
          the FIFO-prefix scheduler (PR 3) stops its merge scan at the
          second burst member, so most of the burst dispatches as
          singleton epochs, while the out-of-order scheduler hops the
          rest of the burst and pairs each member with later disjoint
          cold work.

Streams:

  disjoint_cold   cold only — the merge/chain best case;
  mixed           a same-stripe burst every ``MIX_HOT_PERIOD``
                  admissions (the acceptance stream: OOO >= 1.3x
                  fifo_w4 and >= 1.5x barriered);
  latency_class   interactive point batches interleaved with bulk
                  scans — reports per-class p50/p99 ticket latency
                  (the ``latency_class="interactive"`` queue-jump win).

Cells per stream: ``barriered`` (pipelined=False, window=1 — host joins
every batch), ``fifo_w2``/``fifo_w4`` (PR 3's FIFO-prefix merge,
``reorder=False``), and ``ooo`` (reorder + deep exec chaining,
window=16, max_inflight_execs=4). Reported per cell:

  txn_s              committed transactions / second over the timed stream
  vs_barriered       throughput ratio over the barriered baseline
  vs_fifo4           throughput ratio over the fifo_w4 cell
  merged_batches     batches folded into a preceding CC epoch
  hopped_batches     hop events (a queued batch jumped by a later one)
  overlapped_execs   execs dispatched ahead of a pending commit
  chain_depth_max    deepest exec chain against one store snapshot

The scheduled result is property-tested byte-identical to sequential
``run_batch`` calls (tests/test_scheduler_props.py); this benchmark only
quantifies the throughput side. Single-device logical substrate (no
subprocess needed — the scheduler decisions are host-side).
"""
from __future__ import annotations

import json
import sys
import time

import jax
import numpy as np

from benchmarks.common import RESULTS_DIR, write_csv
from repro.core.engine import BohmEngine
from repro.core.txn import make_batch
from repro.core.workloads import make_ycsb
from repro.obs import (FlightRecorder, PhaseTracer, stitch_chrome_trace,
                       validate_chrome_trace)
from repro.runtime import setup_compile_cache
from repro.service import TxnService
from repro.service.txn_service import LATENCY_CLASSES

_CLASS_NAMES = {rank: name for name, rank in LATENCY_CLASSES.items()}

N_RECORDS = 8192
BATCH = 64
N_BATCHES = 24
RING_SLOTS = 8
# disjoint_cold: 4 stripes over the whole key space (PR 3's stream)
N_STRIPES = 4
# mixed: 8 stripes carved from [HOT_RANGE, N_RECORDS); stripes
# 0..MIX_BURST_STRIPES-1 are the contended ones (one per burst,
# rotating), the rest carry the round-robin cold traffic. HOT_RANGE is
# reserved for the latency stream's interactive point batches.
HOT_RANGE = N_RECORDS // 16
MIX_STRIPES = 8
MIX_BURST_STRIPES = 3
MIX_HOT_BURST = 3
MIX_HOT_PERIOD = 8
# mixed models short update txns (4 RMW): the dispatch-overhead-bound
# regime where admission order dominates, i.e. where head-of-line
# blocking actually costs throughput
MIX_OPS = 4
# latency_class: interactive point batches on the reserved range
INTER_T, INTER_OPS = 16, 2
INTER_EVERY = 6

# window 16 sees across one full burst period, so an epoch can pick up
# commuting members of DIFFERENT bursts (one per contended stripe)
OOO_KW = dict(max_inflight=4, admission_window=16, max_inflight_execs=4)


def _span_batch(rng, lo: int, hi: int, ops: int = 10, t: int = BATCH):
    """RMW batch over [lo, hi): distinct records per txn (paper: '10
    unique records'), cheap probe."""
    recs = rng.integers(lo, hi, size=(t, ops))
    for col in range(1, ops):
        dup = (recs[:, col:col + 1] == recs[:, :col]).any(axis=1)
        recs[dup, col] = lo + (recs[dup, col] - lo + col) % (hi - lo)
    return make_batch(recs, recs.copy(), np.zeros(t, np.int32),
                      np.zeros((t, 1), np.int32))


def _cold_batch(rng, stripe: int):
    lo = stripe * (N_RECORDS // N_STRIPES)
    return _span_batch(rng, lo, lo + N_RECORDS // N_STRIPES)


def _mix_cold_batch(rng, stripe: int):
    width = (N_RECORDS - HOT_RANGE) // MIX_STRIPES
    lo = HOT_RANGE + stripe * width
    return _span_batch(rng, lo, lo + width, ops=MIX_OPS)


def _stream(rng, kind: str):
    out, cold = [], 0
    n_cold_stripes = MIX_STRIPES - MIX_BURST_STRIPES
    for i in range(N_BATCHES):
        if kind == "mixed":
            if i % MIX_HOT_PERIOD < MIX_HOT_BURST:
                # the whole burst hits ONE contended stripe
                out.append(_mix_cold_batch(
                    rng, (i // MIX_HOT_PERIOD) % MIX_BURST_STRIPES))
            else:
                out.append(_mix_cold_batch(
                    rng, MIX_BURST_STRIPES + cold % n_cold_stripes))
                cold += 1
        else:
            out.append(_cold_batch(rng, i % N_STRIPES))
    return out


def _cells():
    """(name, TxnService kwargs) — barriered and FIFO baselines plus the
    out-of-order scheduler at its working point."""
    return [
        ("barriered", dict(max_inflight=2, pipelined=False,
                           admission_window=1)),
        ("fifo_w2", dict(max_inflight=2, admission_window=2,
                         reorder=False)),
        ("fifo_w4", dict(max_inflight=2, admission_window=4,
                         reorder=False)),
        ("ooo", dict(**OOO_KW)),
    ]


_DECISION_KEYS = ("merged_batches", "overlapped_execs", "hopped_batches",
                  "class_promotions", "chain_depth_max")


def bench_stream(kind: str, rng, n_passes: int) -> list:
    wl = make_ycsb(payload_words=2)
    batches = _stream(rng, kind)
    cells = _cells()
    svcs, times = {}, {}
    for name, kw in cells:
        eng = BohmEngine(N_RECORDS, wl, ring_slots=RING_SLOTS)
        svc = TxnService(eng, **kw)
        svc.submit_many(batches)       # untimed warmup pass: compiles
        svc.drain()                    # every epoch shape the stream hits
        svcs[name] = svc
        times[name] = []
    for i in range(n_passes):          # store keeps rolling between passes
        order = cells if i % 2 == 0 else cells[::-1]
        for name, _ in order:          # alternate order: no drift bias
            svc = svcs[name]
            # per-pass counters: the reported row holds ONE stream's
            # scheduler decisions, not n_passes times them
            svc.stats.update({k: 0 for k in _DECISION_KEYS})
            t0 = time.perf_counter()
            svc.submit_many(batches)
            svc.drain()
            times[name].append(time.perf_counter() - t0)

    n_txn = N_BATCHES * BATCH
    base_dt = min(times["barriered"])
    fifo_dt = min(times["fifo_w4"])
    rows = []
    for name, kw in cells:
        dt = min(times[name])
        svc = svcs[name]
        rows.append({
            "stream": kind,
            "mode": name,
            "admission_window": kw.get("admission_window", 1),
            "batch": BATCH,
            "txn_s": round(n_txn / dt),
            "us_per_txn": round(1e6 * dt / n_txn, 2),
            "merged_batches": svc.stats["merged_batches"],
            "hopped_batches": svc.stats["hopped_batches"],
            "overlapped_execs": svc.stats["overlapped_execs"],
            "chain_depth_max": svc.stats["chain_depth_max"],
            "window_occupancy": svc.stats["admission_window_occupancy"],
            "vs_barriered": round(base_dt / dt, 3),
            "vs_fifo4": round(fifo_dt / dt, 3),
        })
    return rows


# ---------------------------------------------------------------------------
# latency-class stream: per-class p50/p99 ticket latency
# ---------------------------------------------------------------------------
def _latency_stream(rng):
    """(batch, latency_class) pairs: bulk full-range scans (mutually
    conflicting) with an interactive point batch every INTER_EVERY
    admissions on the reserved range (commutes with every bulk)."""
    out = []
    for i in range(N_BATCHES):
        if i % INTER_EVERY == INTER_EVERY - 1:
            out.append((_span_batch(rng, 0, HOT_RANGE, ops=INTER_OPS,
                                    t=INTER_T), "interactive"))
        else:
            out.append((_span_batch(rng, HOT_RANGE, N_RECORDS),
                        "bulk"))
    return out


def _run_latency_pass(svc, stream):
    """Burst-submit the stream, recording each ticket's completion time
    SINCE BURST START (every request arrives at t0, so queue position is
    the latency — the regime where an interactive batch jumping queued
    bulk work shows up directly). Pending INTERACTIVE tickets are swept
    after every submit: an interactive submit is already a flush point
    (it disables the admission hold), so the sweep observes the early
    completion the class promotion bought without perturbing how the
    scheduler batches the bulk traffic."""
    t0 = time.perf_counter()
    pending = {}
    lats = {"interactive": [], "bulk": []}

    def _sweep(only_interactive):
        for t in sorted(pending):
            if only_interactive and pending[t] != "interactive":
                continue
            res = svc.poll(t)
            if res is not None:
                jax.block_until_ready(res.read_vals)
                lats[pending.pop(t)].append(time.perf_counter() - t0)

    for batch, cls in stream:
        pending[svc.submit(batch, latency_class=cls)] = cls
        if any(c == "interactive" for c in pending.values()):
            _sweep(only_interactive=True)
    while pending:
        _sweep(only_interactive=False)
    svc.drain()
    return lats


# latency cells get a deep plan window (max_inflight=32 > stream length):
# submission is then pure async dispatch — no backpressure join ever
# blocks the submit loop — so a ticket's recorded completion time
# reflects its DISPATCH position, exactly what latency classes reorder.
# (The barriered cell joins per epoch by construction.)
LAT_CELLS = [
    ("barriered", dict(max_inflight=2, pipelined=False,
                       admission_window=1)),
    ("fifo_w4", dict(max_inflight=32, admission_window=4,
                     reorder=False)),
    ("ooo", dict(max_inflight=32, admission_window=8,
                 max_inflight_execs=4)),
]


def bench_latency(rng, n_passes: int) -> list:
    wl = make_ycsb(payload_words=2)
    stream = _latency_stream(rng)
    n_txn = sum(b.size for b, _ in stream)
    rows = []
    for name, kw in LAT_CELLS:
        eng = BohmEngine(N_RECORDS, wl, ring_slots=RING_SLOTS)
        svc = TxnService(eng, **kw)
        _run_latency_pass(svc, stream)          # warmup: compiles shapes
        best = None
        for _ in range(n_passes):
            t0 = time.perf_counter()
            lats = _run_latency_pass(svc, stream)
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, lats)
        dt, lats = best
        for cls in ("interactive", "bulk"):
            ms = 1e3 * np.asarray(lats[cls])
            rows.append({
                "stream": "latency_class",
                "mode": name,
                "class": cls,
                "n_tickets": len(ms),
                "p50_ms": round(float(np.percentile(ms, 50)), 3),
                "p99_ms": round(float(np.percentile(ms, 99)), 3),
                "max_ms": round(float(ms.max()), 3),
                "txn_s": round(n_txn / dt),
                "class_promotions": svc.stats["class_promotions"],
            })
    return rows


def trace_stream(kind: str = "mixed") -> None:
    """One traced pass over the stream (SEPARATE from the timed cells,
    which keep the ring off): exports ``results/admission_trace.json``,
    a Chrome-trace view of the scheduler's ``service/*`` host spans and
    its merge / hop / chain / class-promotion decisions."""
    rng = np.random.default_rng(47)
    wl = make_ycsb(payload_words=2)
    eng = BohmEngine(N_RECORDS, wl, ring_slots=RING_SLOTS,
                     tracer=PhaseTracer(enabled=True))
    svc = TxnService(eng, **OOO_KW)
    svc.submit_many(_stream(rng, kind))
    # a couple of interactive point batches behind the tail of the
    # stream guarantee admission/class_promote fires in the trace
    svc.submit(_span_batch(rng, 0, HOT_RANGE, ops=INTER_OPS, t=INTER_T),
               latency_class="interactive")
    # two merge-INCOMPATIBLE (different widths) but commuting batches at
    # the tail form adjacent singleton epochs that dispatch as one exec
    # chain — admission/chain_depth fires deterministically
    width = (N_RECORDS - HOT_RANGE) // MIX_STRIPES
    lo = HOT_RANGE + 3 * width
    svc.submit_many([_span_batch(rng, lo, lo + width, ops=5),
                     _span_batch(rng, lo + width, lo + 2 * width, ops=7)])
    svc.drain()
    eng.gc_sweep()
    path = RESULTS_DIR / "admission_trace.json"
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    eng.tracer.export(path)
    trace = json.loads(path.read_text())
    counts = validate_chrome_trace(trace)
    names = {e.get("name") for e in trace.get("traceEvents", [])}
    missing = {"admission/hop", "admission/chain_depth",
               "admission/class_promote"} - names
    if missing:
        raise AssertionError(f"scheduler instants missing: {missing}")
    print(f"trace: {path} ({counts['spans']} spans, "
          f"{counts['instants']} instants)")


def flight_stream(kind: str = "mixed") -> None:
    """One flight-recorded pass over the stream (separate from the timed
    cells — the stitched export also enables the phase tracer's ring):
    every ticket is waited
    individually so lifecycle records complete at retrieval, then

      * ``results/admission_flight_trace.json`` — the PhaseTracer spans
        with one Chrome nestable-async LANE per ticket (cat="flight",
        id=ticket) stitched in on a shared clock, validated including
        the async b/n/e invariants;
      * ``results/admission_flight.json`` — per-ticket latency breakdown
        twin (queue / formation / exec / commit_defer, summing to
        end-to-end);
      * ``results/admission_flight_blocking.json`` — the top-K blocking
        records heatmap with per-kind attribution counts."""
    rng = np.random.default_rng(47)
    wl = make_ycsb(payload_words=2)
    tracer = PhaseTracer(enabled=True)
    recorder = FlightRecorder(enabled=True)
    eng = BohmEngine(N_RECORDS, wl, ring_slots=RING_SLOTS, tracer=tracer)
    svc = TxnService(eng, **OOO_KW, flight=recorder)
    tickets = svc.submit_many(_stream(rng, kind))
    tickets.append(svc.submit(
        _span_batch(rng, 0, HOT_RANGE, ops=INTER_OPS, t=INTER_T),
        latency_class="interactive"))
    for t in tickets:
        svc.wait(t)
    svc.drain()

    trace = stitch_chrome_trace(tracer, recorder)
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    path = RESULTS_DIR / "admission_flight_trace.json"
    path.write_text(json.dumps(trace, indent=1))
    counts = validate_chrome_trace(json.loads(path.read_text()))
    if counts["async_lanes"] != len(tickets):
        raise AssertionError(
            f"expected {len(tickets)} ticket lanes, exported "
            f"{counts['async_lanes']}")

    rows = []
    for f in recorder.records():
        bd = f.breakdown()
        rows.append({
            "ticket": f.ticket,
            "class": _CLASS_NAMES.get(f.latency_class, f.latency_class),
            "epoch": f.epoch, "epoch_batches": f.epoch_batches,
            "chain_depth": f.chain_depth, "hops": f.hops,
            "blocked_events": len(f.blocked),
            **{f"{k}_ms": round(v * 1e3, 4) for k, v in bd.items()},
        })
    write_csv("admission_flight", rows, print_rows=False)
    heat = [{"record": rec, "blocks": n}
            for rec, n in recorder.blocking_top(16)]
    for kind_, n in sorted(recorder.block_kinds.items()):
        heat.append({"record": f"kind:{kind_}", "blocks": n})
    write_csv("admission_flight_blocking", heat, print_rows=False)
    q = recorder.class_quantiles()
    print(f"flight trace: {path} ({counts['async_lanes']} ticket lanes, "
          f"{counts['async_spans']} async spans, {counts['spans']} spans)")
    for rank, row in q.items():
        print(f"  class {rank}: p50={row['p50'] * 1e3:.2f}ms "
              f"p99={row['p99'] * 1e3:.2f}ms n={row['count']}")


def run(quick: bool = False, trace: bool = False,
        flight: bool = False) -> list:
    rng = np.random.default_rng(47)
    n_passes = 3 if quick else 5
    rows = []
    for kind in ("disjoint_cold", "mixed"):
        rows.extend(bench_stream(kind, rng, n_passes))
    write_csv("admission", rows)
    lat_rows = bench_latency(rng, max(2, n_passes - 1))
    write_csv("admission_latency", lat_rows)
    if trace:
        trace_stream()
    if flight:
        flight_stream()
    return rows + lat_rows


if __name__ == "__main__":
    setup_compile_cache()
    run(quick="--quick" in sys.argv, trace="--trace" in sys.argv,
        flight="--flight" in sys.argv)
