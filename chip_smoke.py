#!/usr/bin/env python3
"""Chip smoke test: Bohm's served update stream and pinned snapshot reads
at the paper's scale on a TPU, checked against the serial oracle.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cc record-partitioned store

The deployment is the paper's §5.2.2 YCSB high-contention cell
(``YCSB_HIGH_2RMW8R``: 1,000,000 records of 8 int32 words, 1024-txn
batches of 2RMW-8R, zipf theta = 0.9), built through
``repro.configs.bohm_workloads.build`` and driven through ``TxnService``
with an admission window of 4 and pipelining on. At theta = 0.9 every
pair of batches shares a hot key, so the window never merges two
batches into one epoch: merged epochs are not exercised here (the run
reports ``merged_batches``). A reader pins a snapshot a third of the way
in and holds it while updates continue, so live evictions reach the
spill pool; read-only scans at that snapshot resolve through the
``mvcc_resolve`` and ``mvcc_resolve_masked`` kernels.

One chip: the default dense ring + spill engine must match
``serial_oracle`` exactly (head store and every update read), every
``found`` snapshot read must match ``serial_oracle_prefix`` at the pin,
and the spill tier must have been used; then the same stream runs
through the paged store, whose reads must equal the dense engine's.

``--chips 4``: only the sharded phase — the same stream through
``BohmEngine(mesh=cc x 4)`` and through a one-shard engine; head store,
update reads and snapshot reads must be byte-identical, and each chip
must hold a quarter of the records' rings.

Without a TPU (``JAX_PLATFORMS=cpu`` included) it refuses and exits
non-zero; it never falls back to the CPU. Any failed check exits
non-zero before the last line, which on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

N_BATCHES = 24          # update batches in the stream
PIN_AT = 8              # the snapshot pins after this many batches
ADMISSION_WINDOW = 4
SEED = 0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def run_served(cfg, n_batches: int, pin_at: int, **engine_kw) -> dict:
    """One engine through ``TxnService``: the update stream, a pin after
    ``pin_at`` batches held to the end, and one snapshot scan per batch
    after the pin. Returns host copies of everything compared."""
    import jax

    from repro.configs.bohm_workloads import build
    from repro.core.workloads import gen_scan_batch
    from repro.service import TxnService

    eng, gen = build(cfg, seed=SEED, **engine_kw)
    batches = [gen() for _ in range(n_batches)]
    rng = np.random.default_rng(SEED + 1)
    scans = [gen_scan_batch(rng, cfg.batch_size, cfg.num_records,
                            theta=cfg.theta)
             for _ in range(n_batches - pin_at)]
    svc = TxnService(eng, admission_window=ADMISSION_WINDOW, pipelined=True)
    t0 = time.perf_counter()
    tickets, snap, scan_out = [], None, []
    for i, batch in enumerate(batches):
        if i == pin_at:
            snap = svc.begin_snapshot()
        tickets.append(svc.submit(batch))
        if snap is not None:
            vals, found, _ = svc.run_readonly_batch(scans[i - pin_at], snap)
            scan_out.append((vals, found))
    reads = [svc.wait(t).read_vals for t in tickets]
    svc.drain()
    jax.block_until_ready([v for v, _ in scan_out])
    wall = time.perf_counter() - t0
    out = {
        "engine": eng, "batches": batches, "scans": scans,
        "pin_ts": snap.ts, "wall_s": wall,
        "head": np.asarray(eng.store.base),
        "reads": np.concatenate([np.asarray(r) for r in reads]),
        "scan_vals": np.stack([np.asarray(v) for v, _ in scan_out]),
        "scan_found": np.stack([np.asarray(f) for _, f in scan_out]),
        "epochs": int(eng.metrics.value("engine/commits")),
        "merged": int(svc.stats["merged_batches"]),
    }
    svc.release_snapshot(snap)
    return out


def check_against_oracle(cfg, run: dict) -> None:
    """Head store and update reads == ``serial_oracle``; every found
    snapshot read == ``serial_oracle_prefix`` at the pin."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import serial_oracle, serial_oracle_prefix

    wl = run["engine"].workload
    everything = jax.tree.map(lambda *xs: np.concatenate(xs),
                              *run["batches"])
    base0 = jnp.zeros((cfg.num_records, wl.payload_words), jnp.int32)
    final, reads = jax.jit(functools.partial(serial_oracle, workload=wl))(
        base0, everything)
    check(np.array_equal(run["head"], np.asarray(final)),
          "head store differs from serial_oracle")
    check(np.array_equal(run["reads"], np.asarray(reads)),
          "update reads differ from serial_oracle")
    at_pin = np.asarray(jax.jit(functools.partial(
        serial_oracle_prefix, workload=wl, n_txns=run["pin_ts"]))(
        base0, everything))
    want = np.stack([at_pin[s.read_set] for s in run["scans"]])
    found = run["scan_found"]
    check(np.array_equal(run["scan_vals"][found], want[found]),
          "a found snapshot read differs from serial_oracle_prefix")


def check_spill_used(eng) -> None:
    over, spill = eng.overflow_stats(), eng.spill_stats()
    print(f"  spill: live_evictions={over['total_overwrites']} "
          f"admitted={spill['spill_admitted']} "
          f"dropped={spill['spill_dropped']} "
          f"occupancy={spill['spill_occupancy']}", flush=True)
    check(over["total_overwrites"] > 0 and spill["spill_admitted"] > 0,
          "the pinned stream never reached the spill tier")


def report(name: str, cfg, run: dict, compile_s: float) -> None:
    found_frac = float(run["scan_found"].mean())
    print(f"  {name}: records={cfg.num_records} epochs={run['epochs']} "
          f"merged_batches={run['merged']} "
          f"txns={len(run['batches']) * cfg.batch_size} "
          f"snapshot_reads={run['scan_found'].size} "
          f"pin_ts={run['pin_ts']} found_frac={found_frac} "
          f"compile_s={compile_s:.1f}", flush=True)
    print(f"  {name}: smoke timing (wall clock, compile included, not a "
          f"metric) {run['wall_s']:.2f}s", flush=True)


def same_results(a: dict, b: dict, what: str) -> None:
    for key in ("head", "reads", "scan_vals", "scan_found"):
        check(np.array_equal(a[key], b[key]),
              f"{what}: {key} differ")


def phase_one_chip(cfg, n_batches: int, pin_at: int, compile_s) -> None:
    print("phase dense (ring + spill, TxnService):", flush=True)
    t = compile_s()
    dense = run_served(cfg, n_batches, pin_at)
    report("dense", cfg, dense, compile_s() - t)
    check_against_oracle(cfg, dense)
    check_spill_used(dense["engine"])
    # the spill pool only drops under saturation; without a drop every
    # pinned read must resolve
    if dense["engine"].spill_stats()["spill_dropped"] == 0:
        check(bool(dense["scan_found"].all()),
              "a pinned read went unfound with no spill drop")
    print("  dense == serial_oracle (head, update reads, found snapshot "
          "reads at the pin)", flush=True)
    del dense["engine"]

    print("phase paged (page slab + spill, same stream):", flush=True)
    t = compile_s()
    paged = run_served(cfg, n_batches, pin_at, paged=True)
    report("paged", cfg, paged, compile_s() - t)
    same_results(dense, paged, "paged vs dense")
    print("  paged == dense (head, update reads, snapshot reads)",
          flush=True)


def phase_four_chips(cfg, n_batches: int, pin_at: int, compile_s,
                     devices) -> None:
    from repro.runtime import cc_mesh

    mesh = cc_mesh(devices=devices)
    print("phase sharded (cc x 4 mesh, TxnService):", flush=True)
    t = compile_s()
    sharded = run_served(cfg, n_batches, pin_at, mesh=mesh)
    report("sharded", cfg, sharded, compile_s() - t)
    rings = sharded["engine"].store.versions.rings
    n = rings.begin.shape[0]
    local = rings.begin.shape[1]
    held = sorted((str(s.device), s.data.shape[:2])
                  for s in rings.begin.addressable_shards)
    print(f"  ring shards (device, [shards, records]): {held}", flush=True)
    check(n == 4 and local == -(-cfg.num_records // 4)
          and len({d for d, _ in held}) == 4
          and all(shape == (1, local) for _, shape in held),
          "the rings are not split R/4 per chip over 4 devices")
    check_spill_used(sharded["engine"])
    del sharded["engine"]

    print("phase one shard (same stream, one chip):", flush=True)
    t = compile_s()
    single = run_served(cfg, n_batches, pin_at)
    report("one-shard", cfg, single, compile_s() - t)
    same_results(single, sharded, "cc x 4 vs one shard")
    print("  cc x 4 == one shard (head, update reads, snapshot reads)",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cc-sharded phase on four chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: FAIL: no TPU — JAX found {devices[0].platform} "
            f"devices; this smoke test runs only on the chip")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: FAIL: --chips {args.chips} but "
                         f"only {len(devices)} TPU devices")
    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.runtime import setup_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke: FAIL: the repro package is not "
                         f"under {src}: {e}")
    from repro.configs.bohm_workloads import YCSB_HIGH_2RMW8R

    cache = setup_compile_cache()
    compiled = [0.0]                 # backend compile seconds so far

    def on_event(event, secs, **_):
        if event == COMPILE_EVENT:
            compiled[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    cfg = YCSB_HIGH_2RMW8R
    print(f"config: {cfg}", flush=True)
    if args.chips == 4:
        phase_four_chips(cfg, N_BATCHES, PIN_AT, lambda: compiled[0],
                         devices[:4])
    else:
        phase_one_chip(cfg, N_BATCHES, PIN_AT, lambda: compiled[0])
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"memory {d}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
