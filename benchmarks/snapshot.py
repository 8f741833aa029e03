"""Snapshot-read benchmark — the paper's Fig 9/10 scenario: a stream of
update batches (SmallBank full mix / YCSB 10RMW) concurrent with
long-running read-only scans at OLDER snapshot timestamps.

Bohm's headline: reads never block writes and perform zero bookkeeping.
With the cross-batch version ring the engine can actually serve such scans
— each cell streams ``N_BATCHES`` update batches while a reader pinned at
the pre-stream snapshot repeatedly scans records through the Pallas
``mvcc_resolve`` path. Reported per cell:

  upd_txn_s        update-batch transaction throughput
  scan_reads_s     snapshot-read throughput (resolved reads / second)
  scan_found_frac  fraction of scan reads whose version survived the
                   K-ring (1.0 = the pinned snapshot stayed fully readable)
  occ_max/mean     ring occupancy after the stream (the pinned reader
                   holds the watermark down -> occupancy grows; unpinned
                   it stays at the no-reader steady state)
  evicted/overwrote  GC + overflow counters of the final barrier

Wall-clock numbers on the CPU substrate measure interpret-mode Pallas and
XLA-CPU scatter throughput, not TPU performance — relative trends
(pinned vs unpinned occupancy, scan survival) are the deliverable.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import write_csv
from repro.core.engine import BohmEngine
from repro.core.workloads import (gen_scan_batch, gen_smallbank_batch,
                                  gen_ycsb_batch, make_smallbank,
                                  make_ycsb)
from repro.runtime import setup_compile_cache

N_RECORDS = 8192
BATCH = 512
SCAN_TXNS = 256
SCAN_OPS = 8
N_BATCHES = 8
RING_SLOTS = 8


def _update_batches(kind: str, rng):
    if kind == "smallbank":
        wl = make_smallbank()
        batches = [gen_smallbank_batch(rng, BATCH, N_RECORDS // 2)
                   for _ in range(N_BATCHES)]
    else:
        wl = make_ycsb(payload_words=2)
        batches = [gen_ycsb_batch(rng, BATCH, N_RECORDS, theta=0.6,
                                  mix="10rmw") for _ in range(N_BATCHES)]
    return wl, batches


def bench_cell(kind: str, pinned: bool, rng) -> dict:
    wl, batches = _update_batches(kind, rng)
    eng = BohmEngine(N_RECORDS, wl, ring_slots=RING_SLOTS)
    scans = [gen_scan_batch(rng, SCAN_TXNS, N_RECORDS, ops=SCAN_OPS)
             for _ in range(2)]

    # warm-up/compile both paths outside the timed region
    eng.run_batch(batches[0])
    eng.run_readonly_batch(scans[0])
    snap = eng.begin_snapshot() if pinned else None

    t0 = time.perf_counter()
    metrics = None
    found = []
    for i, batch in enumerate(batches[1:]):
        _, metrics = eng.run_batch(batch)
        _, _, sm = eng.run_readonly_batch(scans[i % len(scans)], snap)
        found.append(sm["found_frac"])    # stays on device: no sync in loop
    jax.block_until_ready(eng.store.base)
    dt = time.perf_counter() - t0
    found = [float(f) for f in found]

    n_upd = (N_BATCHES - 1) * BATCH
    n_reads = (N_BATCHES - 1) * SCAN_TXNS * SCAN_OPS
    row = {
        "workload": kind, "pinned_reader": pinned,
        "upd_txn_s": round(n_upd / dt),
        "scan_reads_s": round(n_reads / dt),
        "scan_found_frac": round(min(found), 4),
        "occ_max": int(metrics["ring_occ_max"]),
        "occ_mean": round(float(metrics["ring_occ_mean"]), 2),
        "evicted": int(metrics["ring_evicted"]),
        "overwrote_live": int(metrics["ring_overwrote_live"]),
    }
    if snap is not None:
        eng.release_snapshot(snap)
    return row


def run() -> list:
    rng = np.random.default_rng(29)
    rows = []
    for kind in ("smallbank", "ycsb"):
        for pinned in (False, True):
            rows.append(bench_cell(kind, pinned, rng))
    write_csv("snapshot", rows)
    return rows


if __name__ == "__main__":
    setup_compile_cache()
    run()
