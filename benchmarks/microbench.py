"""Microbenchmark — paper §5.1, Figure 4: concurrency-control scalability.

1M records, 10RMW transactions, uniform access. The paper varies CC threads
(lines) x execution threads (x-axis). Substrate mapping (DESIGN.md §8):

  CC threads   -> ``cc`` mesh-axis shards: the record-partitioned
                  ``cc_plan_sharded`` shard_map — each shard plans only the
                  records it owns, zero communication (paper §4.1.2);
  exec threads -> execution-wavefront vector lanes == batch size (every
                  wave is one fused data-parallel step over all ready txns).

cc_shards > 1 take their devices from ``jax.devices()``: the chips that
exist, or — under ``JAX_PLATFORMS=cpu`` — 8 virtual CPU devices, which
``main`` asks for before JAX starts.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import time_fn, write_csv
from repro.core.engine import BohmEngine
from repro.core.workloads import gen_ycsb_batch, make_microbench
from repro.runtime import cc_mesh, force_cpu_devices, setup_compile_cache

N_RECORDS = 1_000_000
OPS = 10


def run(cc_shards=(1, 2, 4, 8), batch_sizes=(256, 512, 1024, 2048)) -> list:
    rng = np.random.default_rng(3)
    wl = make_microbench()
    n_dev = jax.device_count()
    rows = []
    for n_cc in cc_shards:
        if n_cc > n_dev:
            continue
        mesh = cc_mesh(n_cc) if n_cc > 1 else None
        for batch_size in batch_sizes:
            eng = BohmEngine(N_RECORDS, wl, mesh=mesh)
            batch = gen_ycsb_batch(rng, batch_size, N_RECORDS, theta=0.0,
                                   mix="10rmw")
            _, metrics = eng.run_batch(batch)
            t = time_fn(eng._step, eng.store, batch)
            rows.append({
                "cc_shards": n_cc, "batch": batch_size,
                "txn_s": round(batch_size / t),
                "rmw_ops_s": round(batch_size * OPS / t),
                "waves": int(metrics["waves"]),
                "us_per_txn": round(1e6 * t / batch_size, 2),
            })
    write_csv("microbench", rows)
    return rows


def main() -> None:
    force_cpu_devices(8)
    setup_compile_cache()
    run()


if __name__ == "__main__":
    main()
