"""Summarise benchmark artifacts into one markdown report.

Three sections, each emitted only when its artifacts exist under
``benchmarks/results/``:

  * the MVCC benchmark tables — the JSON twins written by
    ``benchmarks.run`` (pipeline, admission, spill, paged): scheduler
    wins and storage found-rate/footprint trades, selected columns per
    benchmark;
  * the observability section — phase span stats, health gauges and the
    provenance stamp from ``benchmarks.obs_report`` artifacts;
  * the EXPERIMENTS.md optimized-vs-baseline roofline summary from the
    dry-run artifacts (unchanged from the original tool).

    PYTHONPATH=src python -m benchmarks.summarize
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.launch.roofline import analyze_cell

RESULTS = Path(__file__).resolve().parent / "results"

# benchmark name -> (title, ordered columns to surface; None = all)
BENCH_TABLES = {
    "pipeline": ("pipeline — pipelined vs barriered (Fig 3 overlap)",
                 ["n_shards", "mode", "substrate", "txn_s",
                  "pipelined_over_barriered"]),
    "admission": ("admission — out-of-order scheduler vs FIFO-prefix "
                  "vs barriered",
                  ["stream", "mode", "admission_window", "txn_s",
                   "vs_barriered", "vs_fifo4", "merged_batches",
                   "hopped_batches", "overlapped_execs",
                   "chain_depth_max"]),
    "admission_latency": ("admission latency classes — per-class ticket "
                          "latency (interactive jumps bulk)",
                          ["mode", "class", "n_tickets", "p50_ms",
                           "p99_ms", "max_ms", "txn_s",
                           "class_promotions"]),
    "spill": ("spill — hierarchical storage found-rate at equal budget",
              ["config", "found_rate", "found_vs_drop", "txn_s",
               "txn_s_vs_drop", "spill_admitted", "spill_dropped",
               "k_min_eff", "k_max_eff"]),
    "paged": ("paged — page slab vs dense rings, found-rate per word",
              ["config", "phys_slots", "phys_kwords", "found_rate",
               "found_vs_budget", "txn_s", "txn_s_vs_budget",
               "pages_mapped", "pages_free", "alloc_failed"]),
    "admission_flight": ("admission flight — per-ticket latency "
                         "breakdown (queue/formation/exec/commit_defer "
                         "sum to end-to-end)",
                         ["ticket", "class", "epoch", "epoch_batches",
                          "chain_depth", "hops", "blocked_events",
                          "queue_ms", "formation_ms", "exec_ms",
                          "commit_defer_ms", "total_ms"]),
    "admission_flight_blocking": ("admission flight — blocking-records "
                                  "heatmap (conflict attribution, "
                                  "top-K witnesses + per-kind counts)",
                                  ["record", "blocks"]),
    "arena": ("arena — cross-protocol matrix + anomaly gauntlet "
              "(committed txn/s, MVSG verdicts)",
              ["cell", "protocol", "txn_s", "abort_rate", "verdict",
               "as_expected", "proxy"]),
    "ycsb": ("ycsb — Figs 5-7 via arena adapters (committed txn/s)",
             ["cell", "protocol", "theta", "mix", "txn_s", "abort_rate",
              "verdict", "proxy"]),
    "smallbank": ("smallbank — Figs 8-10 via arena adapters",
                  ["cell", "protocol", "customers", "mix", "txn_s",
                   "abort_rate", "verdict", "proxy"]),
}


def bench_rows(name: str):
    path = RESULTS / f"{name}.json"
    if not path.exists():
        return None
    data = json.loads(path.read_text())
    # twins are {"meta": ..., "rows": [...]} since the obs PR; bare-list
    # artifacts from older runs still summarise
    rows = data.get("rows") if isinstance(data, dict) else data
    return rows if isinstance(rows, list) and rows else None


def bench_meta(name: str):
    path = RESULTS / f"{name}.json"
    if not path.exists():
        return None
    data = json.loads(path.read_text())
    return data.get("meta") if isinstance(data, dict) else None


def _latency_rows_from_flight():
    """Fallback for the ``admission_latency`` table: a run that only
    produced the flight twin (e.g. ``--quick --flight`` without the
    latency cells) still gets its per-class quantiles, computed from the
    per-ticket end-to-end breakdowns."""
    flight = bench_rows("admission_flight")
    if flight is None:
        return None
    by_class = {}
    for r in flight:
        if "total_ms" in r:
            by_class.setdefault(r.get("class", "?"), []).append(
                float(r["total_ms"]))
    rows = []
    for cls, ms in sorted(by_class.items()):
        arr = np.asarray(ms)
        rows.append({
            "mode": "flight", "class": cls, "n_tickets": len(ms),
            "p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
            "max_ms": round(float(arr.max()), 3),
        })
    return rows or None


def print_bench_tables() -> bool:
    """The MVCC benchmark section; returns True when anything printed."""
    printed = False
    for name, (title, columns) in BENCH_TABLES.items():
        rows = bench_rows(name)
        if rows is None and name == "admission_latency":
            rows = _latency_rows_from_flight()
        if rows is None:
            continue
        cols = [c for c in (columns or list(rows[0].keys()))
                if any(c in r for r in rows)]
        if not cols:
            continue
        print(f"\n### {title}\n")
        print("| " + " | ".join(cols) + " |")
        print("|" + "---|" * len(cols))
        for r in rows:
            print("| " + " | ".join(str(r.get(c, "")) for c in cols)
                  + " |")
        printed = True
    if not printed:
        print("(no benchmark JSON twins under benchmarks/results/ — "
              "run `python -m benchmarks.run` first)")
    return printed


def print_obs_section() -> bool:
    """Observability artifacts (``benchmarks.obs_report``): phase span
    stats, selected health gauges, and the provenance stamp."""
    path = RESULTS / "obs_health.json"
    if not path.exists():
        return False
    data = json.loads(path.read_text())
    print("\n## Observability (obs_report artifacts)\n")
    meta = data.get("meta") or {}
    if meta:
        print(f"run: jax {meta.get('jax_version')} / "
              f"{meta.get('backend')} x{meta.get('device_count')} / "
              f"git {meta.get('git_sha')} / {meta.get('timestamp')}\n")
    phases = data.get("phases") or []
    if phases:
        print("| span | count | mean ms | p50 ms | max ms |")
        print("|---|---|---|---|---|")
        for p in phases:
            print(f"| {p['phase']} | {p['count']} | {p['mean_ms']} | "
                  f"{p['p50_ms']} | {p['max_ms']} |")
    health = data.get("health") or {}
    gauges = [k for k in ("watermark_lag", "active_pins", "live_versions",
                          "ring_fill_p50", "ring_fill_max",
                          "pressure_max", "admission_queue_depth")
              if k in health]
    if gauges:
        print("\n| gauge | value |")
        print("|---|---|")
        for k in gauges:
            print(f"| {k} | {health[k]} |")
    trace = RESULTS / "obs_trace.json"
    if trace.exists():
        print(f"\ntrace: {trace} (load in Perfetto / chrome://tracing)")
    return True


def rows_from(path: Path, mesh: str):
    data = json.loads(path.read_text())
    out = {}
    for key, rec in sorted(data.items()):
        if not key.endswith(f"|{mesh}"):
            continue
        r = analyze_cell(key, rec)
        if r:
            out[(r["arch"], r["shape"])] = r
    return out


def gmean(xs):
    xs = [x for x in xs if x > 0]
    return float(np.exp(np.mean(np.log(xs)))) if xs else 0.0


def main():
    print("## MVCC benchmarks (JSON twins)")
    print_bench_tables()
    print_obs_section()

    base_path = RESULTS / "dryrun_baseline.json"
    opt_path = RESULTS / "dryrun_opt.json"
    if not (base_path.exists() and opt_path.exists()):
        return            # no roofline artifacts — benchmark tables only
    print("\n## Roofline (dry-run artifacts)\n")
    base = rows_from(base_path, "single")
    opt = rows_from(opt_path, "single")
    keys = sorted(set(base) & set(opt))

    def agg(rows, field, keys_):
        return gmean([rows[k][field] for k in keys_])

    train = [k for k in keys if k[1] == "train_4k"]
    serve = [k for k in keys if k[1] in ("decode_32k", "long_500k")]
    pre = [k for k in keys if k[1] == "prefill_32k"]

    lines = []
    lines.append("| cell group | metric | baseline | optimized | ratio |")
    lines.append("|---|---|---|---|---|")
    for name, ks in [("train_4k (10)", train), ("prefill_32k (10)", pre),
                     ("decode (12)", serve)]:
        for metric, label, fmt in [
                ("t_memory_s", "memory term", 1e3),
                ("t_collective_s", "collective term", 1e3),
                ("t_compute_s", "compute term", 1e3)]:
            b = agg(base, metric, ks)
            o = agg(opt, metric, ks)
            lines.append(f"| {name} | {label} (gmean ms) | {b*fmt:.2f} | "
                         f"{o*fmt:.2f} | {o/b:.2f}x |")
        if name.startswith("train"):
            b = agg(base, "roofline_fraction", ks)
            o = agg(opt, "roofline_fraction", ks)
            of = agg(opt, "roofline_fraction_fused", ks)
            lines.append(f"| {name} | roofline fraction (gmean) | "
                         f"{b:.1%} | {o:.1%} ({of:.1%} fused) | {o/b:.2f}x |")
    print("\n".join(lines))

    # per-cell optimized table (markdown) for the appendix
    print("\nPer-cell optimized (single-pod):\n")
    print("| arch | shape | comp ms | mem ms | memF ms | coll ms | "
          "dominant | useful | roofl | roofF |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for k in keys:
        r = opt[k]
        print(f"| {r['arch']} | {r['shape']} | {r['t_compute_s']*1e3:.2f} | "
              f"{r['t_memory_s']*1e3:.2f} | {r['t_memory_fused_s']*1e3:.2f} |"
              f" {r['t_collective_s']*1e3:.3f} | {r['dominant']} | "
              f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.1%} | "
              f"{r['roofline_fraction_fused']:.1%} |")


if __name__ == "__main__":
    main()
