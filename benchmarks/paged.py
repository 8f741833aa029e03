"""Paged physical version storage benchmark — slab vs dense footprint.

The hot/cold spill stream (same generator, pins and sweep cadence as
benchmarks/spill.py) runs against three storage configs that answer one
question: what does a unit of PHYSICAL memory buy you?

  dense_kmax    adaptive dense rings at k_max physical slots per record
                — the PR-4 configuration: best found-rate, but every
                record (including the idle tail) pays k_max slots;
  dense_budget  dense rings allocated at exactly the slot budget
                (k = RING_SLOTS, no adaptive headroom) — what a dense
                layout affords at the paged slab's physical size;
  paged         the page slab at the SAME physical budget as
                dense_budget (R x RING_SLOTS slots): cold records hold
                one page, the freed pages let hot records grow toward
                k_max — adaptive reach at flat-budget memory, plus the
                paged commit tax (page-table maintenance + free-list
                allocation inside the timed region; honest numbers in
                the JSON twin).

Reported per cell: physical footprint (slots and words, page tables
included), slab occupancy / free pages / allocation failures, found-rate
of historical reads at the held pins, and txn/s over the timed stream.
Expected shape (CPU substrate): found_rate dense_budget < paged <=
dense_kmax with phys_words(paged) ~= phys_words(dense_budget) ~=
phys_words(dense_kmax) / (K_MAX / RING_SLOTS).
Single-device logical substrate.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import write_csv
from benchmarks.spill import (BATCH, COLD_N, HOT_N, N_BATCHES, N_RECORDS,
                              OPS, _hotset_batch, _run_stream)
from repro.core.engine import BohmEngine
from repro.core.workloads import make_ycsb
from repro.runtime import setup_compile_cache

RING_SLOTS = 4
K_MAX = 16
PAGE_SLOTS = 2
SPILL_BUCKETS = 32
SPILL_SLOTS = 2

CONFIGS = (
    ("dense_kmax", dict(ring_slots=RING_SLOTS, adaptive_k=True,
                        k_max=K_MAX, spill_buckets=SPILL_BUCKETS,
                        spill_slots=SPILL_SLOTS)),
    ("dense_budget", dict(ring_slots=RING_SLOTS,
                          spill_buckets=SPILL_BUCKETS,
                          spill_slots=SPILL_SLOTS)),
    ("paged", dict(ring_slots=RING_SLOTS, adaptive_k=True, k_max=K_MAX,
                   paged=True, page_slots=PAGE_SLOTS,
                   pages_per_shard=N_RECORDS * RING_SLOTS // PAGE_SLOTS,
                   spill_buckets=SPILL_BUCKETS,
                   spill_slots=SPILL_SLOTS)),
)


def bench_config(name: str, kw: dict, batches, n_passes: int) -> dict:
    wl = make_ycsb(payload_words=2, ops=OPS)
    times = []
    eng = pins = None
    for i in range(n_passes + 1):          # pass 0 = compile warmup
        eng = BohmEngine(N_RECORDS, wl, **kw)
        t0 = time.perf_counter()
        pins = _run_stream(eng, batches)
        dt = time.perf_counter() - t0
        if i > 0:
            times.append(dt)

    probe_recs = np.arange(HOT_N + COLD_N)
    found = []
    for pin in pins:
        _, f = eng.snapshot_read(probe_recs, pin)
        found.append(np.asarray(f))
    found_rate = float(np.concatenate(found).mean())

    n_txn = len(batches) * BATCH
    dt = min(times)
    storage = eng.storage_stats()
    k = np.asarray(eng.k_by_record())
    row = {
        "config": name,
        "phys_slots": storage["physical_slots"],
        "phys_kwords": round(storage["physical_version_words"] / 1000),
        "dense_equiv_kwords": round(storage["dense_equiv_words"] / 1000),
        "slot_occupancy": storage["slot_occupancy"],
        "found_rate": round(found_rate, 4),
        "txn_s": round(n_txn / dt),
        "us_per_txn": round(1e6 * dt / n_txn, 2),
        "k_min_eff": int(k.min()),
        "k_max_eff": int(k.max()),
        "spill_dropped": eng.spill_stats()["spill_dropped"],
    }
    if storage["layout"] == "paged":
        row.update(pages_mapped=storage["pages_mapped"],
                   pages_free=storage["pages_free"],
                   alloc_failed=storage["alloc_failed"])
    else:
        row.update(pages_mapped=0, pages_free=0, alloc_failed=0)
    return row


def run(quick: bool = False) -> list:
    rng = np.random.default_rng(67)
    # quick trims TIMING passes only — found_rate needs the full stream
    # to converge (same policy as benchmarks/spill.py)
    n_passes = 1 if quick else 4
    batches = [_hotset_batch(rng) for _ in range(N_BATCHES)]
    rows = [bench_config(name, kw, batches, n_passes)
            for name, kw in CONFIGS]
    base = next(r for r in rows if r["config"] == "dense_budget")
    for r in rows:
        r["found_vs_budget"] = round(r["found_rate"]
                                     / max(base["found_rate"], 1e-9), 3)
        r["txn_s_vs_budget"] = round(r["txn_s"] / base["txn_s"], 3)
        r["words_vs_budget"] = round(r["phys_kwords"]
                                     / max(base["phys_kwords"], 1), 3)
    write_csv("paged", rows)
    return rows


if __name__ == "__main__":
    setup_compile_cache()
    run(quick="--quick" in sys.argv)
