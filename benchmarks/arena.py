"""Protocol arena — the paper's headline claim as one cross-protocol run.

Every protocol (Bohm barriered, Bohm conflict-aware, Hekaton-pessimistic
MVCC, OCC, 2PL, SI) over the full workload matrix (YCSB uniform/zipfian
theta sweep, SmallBank, disjoint/mixed update streams, pinned snapshot
scans) at MATCHED batch sizes, plus the anomaly gauntlet. One JSON twin
(``benchmarks/results/arena.json``); every row carries committed
throughput, abort rate, the protocol's native cost proxies, and the
tag-replay MVSG serializability verdict.

The two claims checked after the run:
  * headline: on the most contended zipfian update stream the best Bohm
    variant sustains throughput >= Hekaton and OCC (which burn their
    advantage on read-tracking / validation aborts) — printed, and a
    warning on miss (wall-clock, so CI noise must not fail the job);
  * gauntlet ground truth: SI (and only SI) flagged NON-SERIALIZABLE,
    exactly on the anomaly scenarios — asserted hard (deterministic).

    PYTHONPATH=src python -m benchmarks.arena [--quick]
"""
from __future__ import annotations

import argparse
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from benchmarks.common import write_csv
from repro.arena import (PROTOCOL_NAMES, arena_matrix, run_gauntlet,
                         run_matrix)
from repro.obs import MetricsRegistry
from repro.runtime import setup_compile_cache


def markdown_pivot(rows: List[Dict]) -> str:
    """cells x protocols committed-throughput pivot + verdict flags
    (``!`` marks a NON-SERIALIZABLE verdict)."""
    protos = list(dict.fromkeys(r["protocol"] for r in rows))
    by_cell: Dict[str, Dict[str, str]] = defaultdict(dict)
    for r in rows:
        flag = "" if r["verdict"] == "serial-equivalent" else " !"
        by_cell[r["cell"]][r["protocol"]] = f"{r['txn_s']:.0f}{flag}"
    lines = ["| cell | " + " | ".join(protos) + " |",
             "|---|" + "---|" * len(protos)]
    for cell, vals in by_cell.items():
        lines.append("| " + cell + " | "
                     + " | ".join(vals.get(p, "-") for p in protos)
                     + " |")
    return "\n".join(lines)


def check_headline(rows: List[Dict]) -> bool:
    """Best Bohm variant >= Hekaton and OCC on the most contended
    zipfian 10RMW stream."""
    zipf = [r for r in rows
            if r["kind"] == "ycsb" and r["mix"] == "10rmw"
            and r["theta"] > 0]
    if not zipf:
        return True
    top = max(r["theta"] for r in zipf)
    cell = {r["protocol"]: r["txn_s"] for r in zipf
            if r["theta"] == top}
    bohm = max(cell.get("bohm", 0), cell.get("bohm-ca", 0))
    ok = all(bohm >= cell.get(b, 0) for b in ("hekaton", "occ"))
    print(f"\nheadline (ycsb-10rmw theta={top}): bohm={bohm:.0f} txn/s "
          f"vs hekaton={cell.get('hekaton', 0):.0f} "
          f"occ={cell.get('occ', 0):.0f} -> "
          + ("PASS" if ok else "MISS (wall-clock — inspect the twin)"))
    return ok


def check_gauntlet(rows: List[Dict]) -> None:
    bad = [r for r in rows if not r["as_expected"]]
    for r in bad:
        print(f"gauntlet UNEXPECTED: {r['cell']} / {r['protocol']}: "
              f"{r['verdict']} (expected serializable="
              f"{r['expected_serializable']})")
    if bad:
        raise SystemExit("anomaly gauntlet ground truth violated")
    flagged = sum(r["verdict"] != "serial-equivalent" for r in rows)
    print(f"gauntlet: {len(rows)} rows, {flagged} SI anomalies flagged, "
          "every serializable protocol certified -> PASS")


def run(quick: bool = False, iters: int = 2, seed: int = 0,
        protocols: Sequence[str] = PROTOCOL_NAMES,
        only_cells: Optional[Sequence[str]] = None) -> List[Dict]:
    registry = MetricsRegistry()
    cells = arena_matrix(quick, seed)
    if only_cells:
        cells = [c for c in cells if c.name in only_cells]
    rows = run_matrix(cells=cells, iters=iters, protocols=protocols,
                      registry=registry,
                      progress=lambda msg: print(f"  {msg}", flush=True))
    grows = run_gauntlet(protocols=protocols, registry=registry)

    # one twin: matrix rows + gauntlet rows share the schema (matrix rows
    # get empty expectation columns so the CSV header is the union)
    for r in rows:
        r.setdefault("expected_serializable", "")
        r.setdefault("as_expected", "")
    all_rows = rows + grows
    write_csv("arena", all_rows, print_rows=False)

    cert = registry.view("arena/")
    if cert.get("certify_calls"):
        print(f"\ncertify cost: {cert['certify_calls']} calls / "
              f"{cert['certify_txns']} txns in "
              f"{cert['certify_wall_us'] / 1e3:.1f} ms "
              "(registry view arena/)")
    print("\n" + markdown_pivot(rows))
    check_headline(rows)
    check_gauntlet(grows)
    return all_rows


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller store/batches, fewer theta points")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--protocols", default=None,
                    help=f"comma subset of {','.join(PROTOCOL_NAMES)}")
    ap.add_argument("--cells", default=None,
                    help="comma subset of matrix cell names")
    args = ap.parse_args()
    run(quick=args.quick, iters=args.iters, seed=args.seed,
        protocols=(args.protocols.split(",") if args.protocols
                   else PROTOCOL_NAMES),
        only_cells=args.cells.split(",") if args.cells else None)


if __name__ == "__main__":
    main()
