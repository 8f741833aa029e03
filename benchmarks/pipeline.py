"""Pipelined transaction service benchmark — paper §3/Fig. 3 overlap.

An update stream (YCSB 10RMW) runs through ``repro.service.TxnService``
at 1/2/4 store shards, pipelined (CC(b+1) dispatched while exec(b) is in
flight, host joins only at the end) vs barriered (host joins every
batch). Reported per cell:

  txn_s        committed transactions / second over the timed stream
  us_per_txn   inverse, microseconds
  substrate    'mesh' (shard_map over real devices) or 'logical'
               (vmapped shards on one device) — bit-identical state
               either way (tests/test_store.py)
  speedup rows summarise pipelined / barriered per shard count

The pipelined schedule can only remove host-device synchronisation, never
add work, so pipelined >= barriered at equal batch size is the expected
(and asserted-by-eyeball) outcome; on TPU the same schedule additionally
overlaps CC compute with exec compute on separate cores.

Mesh shards take their devices from ``jax.devices()``: the chips that
exist, or — under ``JAX_PLATFORMS=cpu`` — 4 virtual CPU devices, which
``main`` asks for before JAX starts.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

from benchmarks.common import write_csv
from repro.core.engine import BohmEngine
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.runtime import cc_mesh, force_cpu_devices, setup_compile_cache
from repro.service import TxnService

N_RECORDS = 8192
BATCH = 256
N_BATCHES = 8
RING_SLOTS = 8


def bench_shards(n_shards: int, rng, n_batches: int,
                 n_passes: int) -> list:
    """Both modes at one shard count, stream passes INTERLEAVED
    (barriered, pipelined, barriered, ...) so slow machine drift hits
    both modes equally; best pass per mode is reported."""
    wl = make_ycsb(payload_words=2)
    # a mesh wider than the physical cores is oversubscription theater —
    # stay on the (bit-identical) logical substrate there
    use_mesh = 1 < n_shards <= min(jax.device_count(),
                                   os.cpu_count() or 1)
    mesh = cc_mesh(n_shards) if use_mesh else None
    batches = [gen_ycsb_batch(rng, BATCH, N_RECORDS, theta=0.6,
                              mix="10rmw") for _ in range(n_batches + 1)]
    svcs, times = {}, {}
    for pipelined in (False, True):
        eng = BohmEngine(N_RECORDS, wl, mesh=mesh, n_shards=n_shards,
                         ring_slots=RING_SLOTS)
        svc = TxnService(eng, max_inflight=2, pipelined=pipelined)
        svc.submit(batches[0])    # compile both phases outside the timing
        svc.drain()
        svcs[pipelined] = svc
        times[pipelined] = []
    for i in range(n_passes):     # store keeps rolling between passes
        order = (False, True) if i % 2 == 0 else (True, False)
        for pipelined in order:   # alternate order: no who-runs-first bias
            svc = svcs[pipelined]
            t0 = time.perf_counter()
            svc.submit_many(batches[1:])
            svc.drain()
            times[pipelined].append(time.perf_counter() - t0)

    n_txn = n_batches * BATCH
    rows = []
    for pipelined in (False, True):
        dt = min(times[pipelined])
        rows.append({
            "n_shards": n_shards,
            "mode": "pipelined" if pipelined else "barriered",
            "substrate": "mesh" if use_mesh else "logical",
            "batch": BATCH,
            "txn_s": round(n_txn / dt),
            "us_per_txn": round(1e6 * dt / n_txn, 2),
            "planned_ahead_max": svcs[pipelined].stats[
                "planned_ahead_max"],
            "pipelined_over_barriered": "",
        })
    rows.append({
        "n_shards": n_shards, "mode": "speedup",
        "substrate": rows[-1]["substrate"], "batch": BATCH,
        "txn_s": "", "us_per_txn": "", "planned_ahead_max": "",
        "pipelined_over_barriered": round(
            min(times[False]) / min(times[True]), 3),
    })
    return rows


def run(quick: bool = False) -> list:
    rng = np.random.default_rng(31)
    n_batches = 3 if quick else N_BATCHES
    n_passes = 3 if quick else 5
    rows = []
    for n_shards in (1, 2, 4):
        rows.extend(bench_shards(n_shards, rng, n_batches, n_passes))
    write_csv("pipeline", rows)
    return rows


def main() -> None:
    force_cpu_devices(4)
    setup_compile_cache()
    run(quick="--quick" in sys.argv)


if __name__ == "__main__":
    main()
