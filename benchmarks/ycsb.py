"""YCSB benchmark — paper §5.2, Figures 5 (low contention), 6 (theta=0.9),
7 (2RMW-8R vs theta). Bohm vs 2PL / Hekaton / OCC / SI.

Driven through the arena's ``ProtocolEngine`` adapters
(``repro.arena.protocols``): every protocol streams the same seeded
batches at matched batch size, rows are long-format (one per
cell x protocol) with committed throughput, abort rate, native cost
proxies and the tag-replay serializability verdict, written as the
PR-standard JSON twin (``{"meta": ..., "rows": [...]}``) via
``benchmarks.common.write_csv``.
"""
from __future__ import annotations

import sys

import numpy as np

from benchmarks.common import write_csv
from repro.arena import ArenaCell, make_protocols, run_cell
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.obs import MetricsRegistry
from repro.runtime import setup_compile_cache

N_RECORDS = 262_144
BATCH = 1024
N_BATCHES = 4
PAYLOAD_WORDS = 8          # 32B payload stand-in for YCSB's 1000B records


def run(sweep_theta: bool = True, num_records: int = N_RECORDS,
        batch: int = BATCH, payload_words: int = PAYLOAD_WORDS) -> list:
    rng = np.random.default_rng(7)
    registry = MetricsRegistry()
    protos = make_protocols(num_records,
                            make_ycsb(payload_words=payload_words),
                            registry)

    # Fig 5 (low contention) + Fig 6 (high contention)
    points = [(theta, mix) for theta in (0.0, 0.9)
              for mix in ("10rmw", "2rmw8r")]
    if sweep_theta:                       # Fig 7: 2RMW-8R vs theta
        points += [(theta, "2rmw8r")
                   for theta in (0.5, 0.7, 0.8, 0.95, 0.99)]

    rows = []
    for theta, mix in points:
        cell = ArenaCell(
            f"ycsb-{mix}-z{theta:g}", "ycsb", num_records,
            [gen_ycsb_batch(rng, batch, num_records, theta=theta,
                            mix=mix) for _ in range(N_BATCHES)],
            theta=theta, mix=mix)
        rows.extend(run_cell(cell, protos, iters=2))
    write_csv("ycsb", rows)
    return rows


if __name__ == "__main__":
    setup_compile_cache()
    run(sweep_theta="--quick" not in sys.argv)
