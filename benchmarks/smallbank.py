"""SmallBank benchmark — paper §5.3, Figures 8 (full mix, 100 customers),
9 (read-only Balance mix), 10 (read-only vs contention).

Contention is controlled by the number of customers (fewer customers =
hotter accounts). Driven through the arena's ``ProtocolEngine`` adapters:
all five protocols (plus the conflict-aware Bohm scheduler) stream the
same seeded batches per cell, long-format rows with committed throughput,
abort rate, native proxies and the serializability verdict, written as
the PR-standard JSON twin via ``benchmarks.common.write_csv``. Stores
start at balance 1000 so TransactSaving's overdraft-abort branch stays
live (the workload-logic abort path, distinct from CC aborts).
"""
from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_csv
from repro.arena import ArenaCell, make_protocols, run_cell
from repro.core.workloads import gen_smallbank_batch, make_smallbank
from repro.obs import MetricsRegistry
from repro.runtime import setup_compile_cache

BATCH = 2048
N_BATCHES = 4
FULL_MIX = (0.2, 0.2, 0.2, 0.2, 0.2)
BALANCE_ONLY = (1.0, 0.0, 0.0, 0.0, 0.0)


def bench_cell(n_customers: int, mix, label: str, rng, protos,
               base) -> list:
    n_records = max(2 * n_customers, 2)
    cell = ArenaCell(
        f"smallbank-{label}-c{n_customers}", "smallbank", n_records,
        [gen_smallbank_batch(rng, BATCH, n_customers, mix=mix)
         for _ in range(N_BATCHES)], mix=label)
    rows = run_cell(cell, protos, iters=2, base=base)
    for r in rows:
        r["customers"] = n_customers
    return rows


def run(sweep_customers: bool = True) -> list:
    rng = np.random.default_rng(13)
    registry = MetricsRegistry()
    rows = []
    sizes = [100] + ([25, 1000, 10_000, 100_000] if sweep_customers
                     else [])
    for n in sizes:
        # one protocol set per store size (shapes change with R)
        protos = make_protocols(max(2 * n, 2), make_smallbank(), registry)
        # accounts start at 1000 (paper setup): overdraft aborts stay rare
        # but reachable
        base = jnp.full((max(2 * n, 2), 2), 1000, jnp.int32)
        rows.extend(bench_cell(n, FULL_MIX, "full", rng, protos, base))
        rows.extend(bench_cell(n, BALANCE_ONLY, "balance", rng, protos,
                               base))
    write_csv("smallbank", rows)
    return rows


if __name__ == "__main__":
    setup_compile_cache()
    run(sweep_customers="--quick" not in sys.argv)
