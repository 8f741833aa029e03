"""TxnService (repro.service): the pipelined schedule must be
BYTE-IDENTICAL to sequential ``run_batch`` calls — final store, ring
state, per-batch read values, and snapshot reads, including a snapshot
pinned MID-pipeline — plus ticket/poll semantics and the sharded
subprocess variant (4 host devices). The conflict-aware admission window
(merged CC epochs + exec-exec overlap) carries the same property over
randomized YCSB / SmallBank streams: identical per-ticket results, head
store, snapshot reads, and — after one watermark GC sweep canonicalises
merged epochs' deferred eviction of invisible versions — identical ring
state, at 1/2 logical shards in-process and 4 mesh shards in a
subprocess."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.txn import Workload, make_batch
from repro.core.workloads import (gen_scan_batch, gen_smallbank_batch,
                                  gen_ycsb_batch, make_smallbank,
                                  make_ycsb)
from repro.service import TxnService

T, OPS, R = 16, 3, 32


def _inc_workload():
    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def read_only(vals, args):
        return vals, jnp.zeros((), bool)

    return Workload(name="inc", n_read=OPS, n_write=OPS, payload_words=2,
                    branches=(rmw, read_only))


def _random_batch(seed: int):
    rng = np.random.default_rng(seed)
    reads = rng.integers(0, R, (T, OPS))
    wmask = rng.random((T, OPS)) < 0.6
    writes = np.where(wmask, reads, -1)
    types = rng.integers(0, 2, T)
    args = rng.integers(1, 5, (T, 1))
    return make_batch(reads, writes, types, args)


def _run_sequential(batches, pin_after, n_shards=1):
    eng = BohmEngine(R, _inc_workload(), ring_slots=8, n_shards=n_shards)
    reads, snap = [], None
    for i, b in enumerate(batches):
        r, _ = eng.run_batch(b)
        reads.append(np.asarray(r))
        if i == pin_after:
            snap = eng.begin_snapshot()
    return eng, reads, snap


def _run_service(batches, pin_after, n_shards=1, pipelined=True,
                 burst=False):
    eng = BohmEngine(R, _inc_workload(), ring_slots=8, n_shards=n_shards)
    svc = TxnService(eng, max_inflight=2, pipelined=pipelined)
    snap, tickets = None, []
    if burst:
        assert pin_after is None
        tickets = svc.submit_many(batches)
    else:
        for i, b in enumerate(batches):
            tickets.append(svc.submit(b))
            if i == pin_after:
                snap = svc.begin_snapshot()
    reads = [np.asarray(svc.wait(t).read_vals) for t in tickets]
    svc.drain()
    return eng, svc, reads, snap


def _assert_stores_equal(e0, e1):
    np.testing.assert_array_equal(np.asarray(e0.snapshot()),
                                  np.asarray(e1.snapshot()))
    np.testing.assert_array_equal(np.asarray(e0.store.base_ts),
                                  np.asarray(e1.store.base_ts))
    for f in ("begin", "end", "payload", "head"):
        np.testing.assert_array_equal(
            np.asarray(getattr(e0.store.versions.rings, f)),
            np.asarray(getattr(e1.store.versions.rings, f)), f)


# ---------------------------------------------------------------------------
# 1. pipelined == barriered == sequential, snapshot pinned mid-pipeline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("pipelined", [True, False])
def test_service_equals_sequential(n_shards, pipelined):
    for seed0 in (0, 100):
        batches = [_random_batch(seed0 + s) for s in range(6)]
        e0, reads0, snap0 = _run_sequential(batches, pin_after=1,
                                            n_shards=n_shards)
        e1, svc, reads1, snap1 = _run_service(batches, pin_after=1,
                                              n_shards=n_shards,
                                              pipelined=pipelined)
        for a, b in zip(reads0, reads1):
            np.testing.assert_array_equal(a, b)
        _assert_stores_equal(e0, e1)
        # the mid-pipeline snapshot reads exactly the pinned prefix state
        assert snap0.ts == snap1.ts
        v0, f0 = e0.snapshot_read(np.arange(R), snap0)
        v1, f1 = e1.snapshot_read(np.arange(R), snap1)
        # found maps may legitimately contain False (a hot record can
        # outgrow K even with the pin); they must be IDENTICAL though
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        assert int(np.asarray(f0).sum()) > R // 2
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        # read-only scan batches agree at the pinned snapshot too
        scan = gen_scan_batch(np.random.default_rng(1), 8, R, ops=OPS)
        s0, g0, _ = e0.run_readonly_batch(scan, snap0)
        s1, g1, _ = svc.run_readonly_batch(scan, snap1)
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


def test_burst_submit_plans_ahead():
    """submit_many fills the CC plan window to max_inflight before the
    first exec join — the paper's CC(b+1)-overlaps-exec(b) shape."""
    batches = [_random_batch(s) for s in range(6)]
    e0, reads0, _ = _run_sequential(batches, pin_after=None)
    e1, svc, reads1, _ = _run_service(batches, pin_after=None, burst=True)
    for a, b in zip(reads0, reads1):
        np.testing.assert_array_equal(a, b)
    _assert_stores_equal(e0, e1)
    assert svc.stats["planned_ahead_max"] == 2


# ---------------------------------------------------------------------------
# 2. ticket semantics
# ---------------------------------------------------------------------------
def test_poll_wait_semantics():
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng, max_inflight=2)
    t0 = svc.submit(_random_batch(0))
    t1 = svc.submit(_random_batch(1))
    assert t1 == t0 + 1
    r1 = svc.wait(t1)
    assert r1.ticket == t1 and r1.read_vals.shape == (T, OPS, 2)
    # after waiting on a later ticket, the earlier one is realised too
    r0 = svc.poll(t0)
    assert r0 is not None and r0.ticket == t0
    with pytest.raises(KeyError):
        svc.wait(99)
    svc.drain()
    assert svc.stats["submitted"] == 2


def test_service_timestamp_mirror_matches_engine():
    """Plan-time timestamp mirroring: after submit returns, the engine's
    snapshot clock covers the submitted batch (reads enqueue behind the
    dispatched commit)."""
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng)
    svc.submit(_random_batch(0))
    assert eng.current_ts() == T
    svc.submit(_random_batch(1))
    assert eng.current_ts() == 2 * T
    svc.drain()
    v, f = eng.snapshot_read(np.arange(R))
    assert bool(f.all())
    np.testing.assert_array_equal(np.asarray(v), np.asarray(eng.snapshot()))


# ---------------------------------------------------------------------------
# 3. conflict-aware admission: merged CC epochs + exec-exec overlap must be
# byte-identical to sequential run_batch calls — per-ticket reads, head
# store, snapshot reads at a pin landed MID-WINDOW (while batches are held
# in the admission queue), and ring state once a single watermark sweep
# canonicalises the merged epochs' deferred eviction of invisible versions.
# ---------------------------------------------------------------------------
def _stream(kind: str, seed: int, n: int):
    """(workload, engine R, batches) for one randomized stream."""
    rng = np.random.default_rng(seed)
    if kind == "ycsb_uniform":
        return make_ycsb(), 64, [gen_ycsb_batch(rng, T, 64, theta=0.0,
                                                mix="10rmw")
                                 for _ in range(n)]
    if kind == "ycsb_zipf":
        return make_ycsb(), 64, [gen_ycsb_batch(rng, T, 64, theta=0.9,
                                                mix="2rmw8r")
                                 for _ in range(n)]
    if kind == "smallbank":
        return make_smallbank(), 64, [gen_smallbank_batch(rng, T, 32)
                                      for _ in range(n)]
    if kind == "striped":
        # round-robin disjoint key stripes: the mergeable/overlappable
        # best case (4 stripes of 16 records over R=64)
        wl = _inc_workload()
        batches = []
        for i in range(n):
            lo = 16 * (i % 4)
            reads = rng.integers(lo, lo + 16, (T, OPS))
            writes = np.where(rng.random((T, OPS)) < 0.6, reads, -1)
            batches.append(make_batch(reads, writes,
                                      rng.integers(0, 2, T),
                                      rng.integers(1, 5, (T, 1))))
        return wl, 64, batches
    raise ValueError(kind)


def _assert_rings_equal_after_sweep(e0, e1):
    """Merged epochs commit through one barrier and so defer the GC of
    versions no legal reader can see; one sweep at the (identical)
    current watermark restores the canonical state on both sides."""
    e0.gc_sweep()
    e1.gc_sweep()
    _assert_stores_equal(e0, e1)


@pytest.mark.parametrize("kind", ["ycsb_uniform", "ycsb_zipf",
                                  "smallbank", "striped"])
@pytest.mark.parametrize("n_shards", [1, 2])
def test_conflict_aware_equals_sequential(kind, n_shards):
    for seed in (0, 7):
        wl, R_k, batches = _stream(kind, seed, 7)
        # sequential barriered oracle, pin after batch 1
        e0 = BohmEngine(R_k, wl, ring_slots=8, n_shards=n_shards)
        reads0, snap0 = [], None
        for i, b in enumerate(batches):
            r, _ = e0.run_batch(b)
            reads0.append(np.asarray(r))
            if i == 1:
                snap0 = e0.begin_snapshot()
        # conflict-aware schedule; window > batches-before-pin so the pin
        # lands while batches 0..1 are still HELD in the admission queue
        e1 = BohmEngine(R_k, wl, ring_slots=8, n_shards=n_shards)
        svc = TxnService(e1, max_inflight=2, admission_window=3)
        tickets, snap1 = [], None
        for i, b in enumerate(batches):
            tickets.append(svc.submit(b))
            if i == 1:
                snap1 = svc.begin_snapshot()
        reads1 = [np.asarray(svc.wait(t).read_vals) for t in tickets]
        svc.drain()

        for a, b in zip(reads0, reads1):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(e0.snapshot()),
                                      np.asarray(e1.snapshot()))
        np.testing.assert_array_equal(np.asarray(e0.store.base_ts),
                                      np.asarray(e1.store.base_ts))
        assert int(e0.store.ts_counter) == int(e1.store.ts_counter)
        assert snap0.ts == snap1.ts
        v0, f0 = e0.snapshot_read(np.arange(R_k), snap0)
        v1, f1 = e1.snapshot_read(np.arange(R_k), snap1)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        scan = gen_scan_batch(np.random.default_rng(2), 8, R_k, ops=OPS)
        s0, g0, _ = e0.run_readonly_batch(scan, snap0)
        s1, g1, _ = svc.run_readonly_batch(scan, snap1)
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
        _assert_rings_equal_after_sweep(e0, e1)


def test_conflict_aware_merges_and_overlaps_on_disjoint_stream():
    """The scheduler decision metrics: a striped stream must actually
    produce merged epochs (window 4) and overlapped execs (window 2 —
    adjacent two-stripe epochs are still disjoint), and a fully
    conflicting hot stream must fall back to zero of either."""
    wl, R_k, batches = _stream("striped", 3, 8)
    e = BohmEngine(R_k, wl, ring_slots=8)
    svc = TxnService(e, max_inflight=2, admission_window=4)
    svc.submit_many(batches)
    svc.drain()
    assert svc.stats["merged_batches"] > 0
    assert svc.stats["admission_window_occupancy"] == 4

    e2 = BohmEngine(R_k, wl, ring_slots=8)
    svc2 = TxnService(e2, max_inflight=2, admission_window=2)
    svc2.submit_many(batches)
    svc2.drain()
    assert svc2.stats["overlapped_execs"] > 0

    # hot stream: every batch writes record 0 -> no merges, no overlaps
    hot = [make_batch(np.zeros((T, OPS)), np.zeros((T, OPS)),
                      np.zeros(T), np.ones((T, 1))) for _ in range(4)]
    e3 = BohmEngine(R_k, wl, ring_slots=8)
    svc3 = TxnService(e3, max_inflight=2, admission_window=4)
    svc3.submit_many(hot)
    svc3.drain()
    assert svc3.stats["merged_batches"] == 0
    assert svc3.stats["overlapped_execs"] == 0
    # conflicting stream still matches the sequential oracle (fallback
    # is the ordinary barriered path)
    e4 = BohmEngine(R_k, wl, ring_slots=8)
    for b in hot:
        e4.run_batch(b)
    np.testing.assert_array_equal(np.asarray(e3.snapshot()),
                                  np.asarray(e4.snapshot()))
    _assert_rings_equal_after_sweep(e4, e3)


def test_burst_conflict_aware_equals_burst_fifo():
    """submit_many through the conflict-aware window == the FIFO
    pipelined schedule == sequential, and a merged epoch's tickets each
    get their own read-value slice."""
    wl, R_k, batches = _stream("striped", 11, 6)
    e0 = BohmEngine(R_k, wl, ring_slots=8)
    reads0 = [np.asarray(e0.run_batch(b)[0]) for b in batches]
    e1 = BohmEngine(R_k, wl, ring_slots=8)
    svc = TxnService(e1, max_inflight=2, admission_window=3)
    tickets = svc.submit_many(batches)
    reads1 = [np.asarray(svc.wait(t).read_vals) for t in tickets]
    svc.drain()
    assert svc.stats["merged_batches"] > 0
    for a, b in zip(reads0, reads1):
        assert a.shape == b.shape == (T, OPS, 2)
        np.testing.assert_array_equal(a, b)
    _assert_rings_equal_after_sweep(e0, e1)


# ---------------------------------------------------------------------------
# 4. sharded pipeline property sweep (subprocess, 4 host devices):
# mesh-sharded TxnService == unsharded sequential engine, byte-identical,
# including a snapshot pinned mid-pipeline.
# ---------------------------------------------------------------------------
_SHARDED_PIPELINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.engine import BohmEngine
    from repro.core.txn import Workload, make_batch
    from repro.service import TxnService

    R, T, OPS = 32, 16, 3
    from repro.runtime import cc_mesh
    mesh = cc_mesh(4)

    def rand_batch(seed):
        rng = np.random.default_rng(seed)
        reads = rng.integers(0, R, (T, OPS))
        wmask = rng.random((T, OPS)) < 0.6
        writes = np.where(wmask, reads, -1)
        return make_batch(reads, writes, rng.integers(0, 2, T),
                          rng.integers(1, 5, (T, 1)))

    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def ro(vals, args):
        return vals, jnp.zeros((), bool)

    wl = Workload("inc", OPS, OPS, 2, (rmw, ro))
    for seed0 in (0, 50):
        batches = [rand_batch(seed0 + i) for i in range(5)]
        e0 = BohmEngine(R, wl, ring_slots=8)
        r0, snap0 = [], None
        for i, b in enumerate(batches):
            r, _ = e0.run_batch(b)
            r0.append(np.asarray(r))
            if i == 1:
                snap0 = e0.begin_snapshot()
        e1 = BohmEngine(R, wl, mesh=mesh, ring_slots=8)
        svc = TxnService(e1, max_inflight=2)
        tickets, snap1 = [], None
        for i, b in enumerate(batches):
            tickets.append(svc.submit(b))
            if i == 1:
                snap1 = svc.begin_snapshot()
        r1 = [np.asarray(svc.wait(t).read_vals) for t in tickets]
        svc.drain()
        for a, b in zip(r0, r1):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(e0.snapshot()),
                                      np.asarray(e1.snapshot()))
        v0, f0 = e0.snapshot_read(np.arange(R), snap0)
        v1, f1 = e1.snapshot_read(np.arange(R), snap1)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        assert bool(f0.all())
    print("SHARDED_PIPELINE_OK")
""")


def test_sharded_pipeline_property_sweep():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c",
                          _SHARDED_PIPELINE_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(root), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_PIPELINE_OK" in out.stdout


# ---------------------------------------------------------------------------
# 5. conflict-aware sharded sweep (subprocess, 4 host devices): the merged/
# overlapped schedule on a 4-device mesh store == unsharded sequential
# engine — per-ticket reads, head store, mid-window pinned snapshot, and
# (post-sweep) the unsharded ring state.
# ---------------------------------------------------------------------------
_CONFLICT_AWARE_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.engine import BohmEngine
    from repro.core.txn import Workload, make_batch
    from repro.core.workloads import gen_ycsb_batch, make_ycsb
    from repro.service import TxnService
    from repro.store import unshard

    R, T, OPS = 64, 16, 3
    from repro.runtime import cc_mesh
    mesh = cc_mesh(4)

    def striped_batch(rng, stripe):
        lo = 16 * (stripe % 4)
        reads = rng.integers(lo, lo + 16, (T, OPS))
        writes = np.where(rng.random((T, OPS)) < 0.6, reads, -1)
        return make_batch(reads, writes, rng.integers(0, 2, T),
                          rng.integers(1, 5, (T, 1)))

    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def ro(vals, args):
        return vals, jnp.zeros((), bool)

    wl_inc = Workload("inc", OPS, OPS, 2, (rmw, ro))
    wl_ycsb = make_ycsb()
    for seed0, (wl, gen) in ((0, (wl_inc, "striped")),
                             (50, (wl_ycsb, "ycsb"))):
        rng = np.random.default_rng(seed0)
        if gen == "striped":
            batches = [striped_batch(rng, i) for i in range(6)]
        else:
            batches = [gen_ycsb_batch(rng, T, R, theta=0.6, mix="10rmw")
                       for _ in range(6)]
        e0 = BohmEngine(R, wl, ring_slots=8)
        r0, snap0 = [], None
        for i, b in enumerate(batches):
            r, _ = e0.run_batch(b)
            r0.append(np.asarray(r))
            if i == 1:
                snap0 = e0.begin_snapshot()
        e1 = BohmEngine(R, wl, mesh=mesh, ring_slots=8)
        svc = TxnService(e1, max_inflight=2, admission_window=3)
        tickets, snap1 = [], None
        for i, b in enumerate(batches):
            tickets.append(svc.submit(b))
            if i == 1:
                snap1 = svc.begin_snapshot()
        r1 = [np.asarray(svc.wait(t).read_vals) for t in tickets]
        svc.drain()
        if gen == "striped":
            assert svc.stats["merged_batches"] > 0, svc.stats
        for a, b in zip(r0, r1):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(e0.snapshot()),
                                      np.asarray(e1.snapshot()))
        assert snap0.ts == snap1.ts
        v0, f0 = e0.snapshot_read(np.arange(R), snap0)
        v1, f1 = e1.snapshot_read(np.arange(R), snap1)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
        e0.gc_sweep(); e1.gc_sweep()
        g0, g1 = unshard(e0.store.versions), unshard(e1.store.versions)
        for f in ("begin", "end", "payload", "head"):
            np.testing.assert_array_equal(np.asarray(getattr(g0, f)),
                                          np.asarray(getattr(g1, f)), f)
    print("CONFLICT_AWARE_SHARDED_OK")
""")


def test_conflict_aware_sharded_property_sweep():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c",
                          _CONFLICT_AWARE_SHARDED_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(root), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "CONFLICT_AWARE_SHARDED_OK" in out.stdout
