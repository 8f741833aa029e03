"""Benchmark harness entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

  microbench  Fig 4   CC-shard scalability over the cc mesh
  ycsb        Fig 5-7 Bohm vs 2PL/SI/OCC, low/high contention + theta sweep
  smallbank   Fig 8-10 full mix + read-only vs contention
  snapshot    Fig 9/10 scenario: update stream + pinned snapshot scans
              through the version ring (occupancy, GC, scan survival)
  pipeline    §3/Fig 3 overlap: TxnService update stream at 1/2/4 store
              shards, pipelined vs barriered
  admission   conflict-aware admission: merged CC epochs + exec-exec
              overlap vs the barriered baseline, hot/cold skewed streams
  spill       hierarchical version storage: fixed-K drop vs spill vs
              adaptive-K on a pinned hot-set update stream (found-rate
              for historical reads + txn/s at equal memory budget)
  paged       paged physical storage: page slab vs dense rings on the
              same stream — found-rate per word of physical memory,
              slab occupancy, the paged commit tax
  kernels     Pallas kernels vs jnp oracles (interpret-mode wall times)
  serving     Bohm-MVCC paged KV serving engine step latency
  arena       cross-protocol arena: all five protocols over the full
              workload matrix at matched batch sizes + anomaly gauntlet
              (headline claim + serializability verdicts in one twin)

Every suite runs in a process of its own and this parent never imports
JAX, so a suite that needs the chip always finds it free. Each suite is
started as ``python -m benchmarks.<name> [--quick]`` and sets itself up
(compile cache; under ``JAX_PLATFORMS=cpu`` the mesh suites ask for
virtual CPU devices, on an accelerator they use the devices that exist).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# suite (= module name under benchmarks/) -> title
SUITES = {
    "microbench": "microbench (Fig 4)",
    "ycsb": "ycsb (Figs 5-7)",
    "smallbank": "smallbank (Figs 8-10)",
    "snapshot": "snapshot (Figs 9/10 scenario)",
    "pipeline": "pipeline (Fig 3 overlap)",
    "admission": "admission (conflict-aware scheduler)",
    "spill": "spill (hierarchical version storage)",
    "paged": "paged (page-slab physical storage)",
    "kernels": "kernels",
    "serving": "serving",
    "arena": "arena (cross-protocol matrix + gauntlet)",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow sweep dimensions")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: " + ",".join(SUITES))
    args = ap.parse_args()
    only = args.only.split(",") if args.only else list(SUITES)
    unknown = [n for n in only if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s): {','.join(unknown)}")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    for name, title in SUITES.items():
        if name not in only:
            continue
        print(f"== {title} ==", flush=True)
        # each module's __main__ does its own set-up and reads --quick
        cmd = [sys.executable, "-m", f"benchmarks.{name}"]
        if args.quick:
            cmd.append("--quick")
        subprocess.run(cmd, check=True, cwd=str(ROOT), env=env)


if __name__ == "__main__":
    main()
