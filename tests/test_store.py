"""Sharded version store (repro.store.sharded): n_shards > 1 must be
BIT-IDENTICAL to the single ring — state, metrics, and snapshot reads —
for any batch stream; plus the per-record overflow histogram and the
mesh-backed shard_map substrate (subprocess, 4 host devices)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.plan import cc_plan
from repro.core.txn import Workload, make_batch
from repro.kernels import ops
from repro.store import (commit_sharded, commit_versions,
                         gather_windows_sharded, init_ring,
                         init_sharded_store, resolve_sharded,
                         store_occupancy, to_global, unshard)

T, OPS = 16, 3


def _inc_workload():
    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def read_only(vals, args):
        return vals, jnp.zeros((), bool)

    return Workload(name="inc", n_read=OPS, n_write=OPS, payload_words=2,
                    branches=(rmw, read_only))


def _random_batch(seed: int, R: int):
    rng = np.random.default_rng(seed)
    reads = rng.integers(0, R, (T, OPS))
    wmask = rng.random((T, OPS)) < 0.6
    writes = np.where(wmask, reads, -1)
    types = rng.integers(0, 2, T)
    args = rng.integers(1, 5, (T, 1))
    return make_batch(reads, writes, types, args)


# ---------------------------------------------------------------------------
# 1. store-level: sharded commit/resolve == single ring, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R", [32, 33])          # divisible and ragged
@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_commit_bit_identical(R, n_shards):
    rng = np.random.default_rng(7)
    base = jnp.asarray(rng.integers(0, 50, (R, 2)), jnp.int32)
    base_ts = jnp.zeros((R,), jnp.int32)
    ring = init_ring(base, base_ts, 4)
    store = init_sharded_store(base, base_ts, 4, n_shards=n_shards)

    ts_base = 1
    for seed in range(3):
        batch = _random_batch(seed, R)
        plan = cc_plan(batch, jnp.int32(ts_base))
        w_data = jnp.asarray(rng.integers(0, 99, (T * OPS, 2)), jnp.int32)
        wm = jnp.int32(ts_base)               # no readers: barrier GC
        ring, m1 = commit_versions(ring, plan.w_rec, plan.w_key,
                                   plan.w_valid, plan.w_begin_ts,
                                   plan.w_end_ts, w_data, wm)
        store, m2 = commit_sharded(store, plan.w_rec, plan.w_key,
                                   plan.w_valid, plan.w_begin_ts,
                                   plan.w_end_ts, w_data, wm)
        ts_base += T

        g = unshard(store)
        for f in ("begin", "end", "payload", "head"):
            np.testing.assert_array_equal(np.asarray(getattr(g, f)),
                                          np.asarray(getattr(ring, f)), f)
        for k in ("ring_evicted", "ring_overflow_dropped",
                  "ring_overwrote_live", "ring_occ_max"):
            assert int(m2[k]) == int(m1[k]), k
        np.testing.assert_array_equal(
            np.asarray(to_global(store, m2["ring_overwrote_rec"])),
            np.asarray(m1["ring_overwrote_rec"]))

        # per-shard kernel resolution == single-ring kernel resolution
        recs = jnp.arange(R, dtype=jnp.int32)
        ts_vec = jnp.full((R,), ts_base - 1, jnp.int32)
        v2, f2 = resolve_sharded(store, recs, ts_vec)
        b0, e0, p0 = ring.begin[recs], ring.end[recs], ring.payload[recs]
        v1, f1 = ops.mvcc_resolve(b0, e0, p0, ts_vec, interpret=True)
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(f2), np.asarray(f1))
        # gathered windows come from the owning shard
        bg, eg, pg = gather_windows_sharded(store, recs)
        np.testing.assert_array_equal(np.asarray(bg), np.asarray(b0))
        np.testing.assert_array_equal(np.asarray(eg), np.asarray(e0))
        np.testing.assert_array_equal(np.asarray(pg), np.asarray(p0))


# ---------------------------------------------------------------------------
# 2. engine-level: n_shards > 1 engine == single-shard engine end to end
# ---------------------------------------------------------------------------
def test_engine_sharded_store_matches_unsharded():
    R = 48
    wl = _inc_workload()
    e1 = BohmEngine(R, wl, ring_slots=8)
    e4 = BohmEngine(R, wl, ring_slots=8, n_shards=4)
    snaps1, snaps4 = [], []
    for seed in range(4):
        batch = _random_batch(seed, R)
        r1, m1 = e1.run_batch(batch)
        r4, m4 = e4.run_batch(batch)
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r4))
        assert int(m1["ring_occ_max"]) == int(m4["ring_occ_max"])
        snaps1.append(e1.begin_snapshot())
        snaps4.append(e4.begin_snapshot())
    np.testing.assert_array_equal(np.asarray(e1.snapshot()),
                                  np.asarray(e4.snapshot()))
    np.testing.assert_array_equal(np.asarray(store_occupancy(
        e1.store.versions)), np.asarray(store_occupancy(e4.store.versions)))
    for s1, s4 in zip(snaps1, snaps4):
        v1, f1 = e1.snapshot_read(np.arange(R), s1)
        v4, f4 = e4.snapshot_read(np.arange(R), s4)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v4))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f4))


# ---------------------------------------------------------------------------
# 3. per-record overflow histogram: the hot key is identified
# ---------------------------------------------------------------------------
def test_overflow_histogram_identifies_hot_record():
    def bump(vals, args):
        return vals.at[..., 0].add(1), jnp.zeros((), bool)

    wl = Workload(name="hot", n_read=1, n_write=1, payload_words=1,
                  branches=(bump,))
    eng = BohmEngine(8, wl, ring_slots=2, n_shards=2)
    hot = make_batch(np.zeros((8, 1)), np.zeros((8, 1)),
                     np.zeros(8), np.zeros((8, 1)))
    eng.run_batch(hot)
    eng.begin_snapshot()                 # pin: later versions must survive
    for _ in range(3):
        eng.run_batch(hot)               # K=2 ring: record 0 overflows

    counts = np.asarray(eng.overflow_by_record())
    assert counts.shape == (8,)
    assert counts[0] > 0                 # the hot key is visible...
    assert (counts[1:] == 0).all()       # ...and only the hot key
    stats = eng.overflow_stats(top_k=3)
    assert stats["total_overwrites"] == counts[0]
    assert stats["records_affected"] == 1
    assert stats["top_records"][0][0] == 0
    hist_total = sum(n for _, n in stats["histogram"])
    assert hist_total == 8               # every record in exactly 1 bucket


def test_overflow_stats_empty_histogram():
    """No overflow ever: totals zero, no top records, every record sits
    in the first histogram bucket."""
    eng = BohmEngine(8, _inc_workload(), ring_slots=8)
    eng.run_batch(_random_batch(0, 8))       # K=8 ring: nothing overflows
    stats = eng.overflow_stats()
    assert stats["total_overwrites"] == 0
    assert stats["records_affected"] == 0
    assert stats["top_records"] == []
    assert stats["histogram"][0] == ("0", 8)
    assert sum(n for _, n in stats["histogram"]) == 8


def test_overflow_stats_top_k_larger_than_record_count():
    """top_k > R must clamp, not crash, and still report only the
    records that actually overflowed."""
    def bump(vals, args):
        return vals.at[..., 0].add(1), jnp.zeros((), bool)

    wl = Workload(name="hot", n_read=1, n_write=1, payload_words=1,
                  branches=(bump,))
    eng = BohmEngine(4, wl, ring_slots=2)
    hot = make_batch(np.zeros((8, 1)), np.zeros((8, 1)),
                     np.zeros(8), np.zeros((8, 1)))
    eng.begin_snapshot()                     # pin: overwrites count
    eng.run_batch(hot)
    stats = eng.overflow_stats(top_k=100)
    assert len(stats["top_records"]) <= 4
    assert stats["top_records"][0][0] == 0
    assert stats["records_affected"] == 1


@pytest.mark.parametrize("n_shards", [2, 4])
def test_overflow_stats_bucket_edges_stable_across_shardings(n_shards):
    """The same stream must produce the IDENTICAL stats dict — totals,
    top-k, and every histogram bucket edge — through a sharded store and
    the single ring (the histogram is computed on the re-globalised
    per-record counts, so partitioning must be invisible)."""
    def bump(vals, args):
        return vals.at[..., 0].add(1), jnp.zeros((), bool)

    wl = Workload(name="hot", n_read=1, n_write=1, payload_words=1,
                  branches=(bump,))
    engines = [BohmEngine(8, wl, ring_slots=2, n_shards=n)
               for n in (1, n_shards)]
    rng = np.random.default_rng(5)
    recs = rng.integers(0, 3, (6, 8, 1))     # 3 hot-ish records, 6 batches
    for eng in engines:
        eng.begin_snapshot()                 # pin: versions must survive
        for i in range(6):
            eng.run_batch(make_batch(recs[i], recs[i],
                                     np.zeros(8), np.zeros((8, 1))))
    s1, sn = (e.overflow_stats(top_k=8) for e in engines)
    assert s1["total_overwrites"] > 0        # the stream does overflow
    assert s1 == sn
    np.testing.assert_array_equal(np.asarray(engines[0].overflow_by_record()),
                                  np.asarray(engines[1].overflow_by_record()))


# ---------------------------------------------------------------------------
# 4. mesh substrate: shard_map commit/resolve == logical == single ring
# (subprocess with 4 forced host devices — repo convention)
# ---------------------------------------------------------------------------
_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import functools
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.engine import BohmEngine
    from repro.core.txn import Workload, make_batch
    from repro.store import unshard

    R, T, OPS = 33, 16, 3
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
    # engine on the mesh: sharded CC plan AND sharded store commit/resolve
    e_mesh = BohmEngine(R, wl, mesh=mesh)
    e_one = BohmEngine(R, wl)
    assert e_mesh.n_shards == 4

    def held(x):        # {device: leading shard-axis extent it holds}
        return {str(s.device): s.data.shape[0]
                for s in x.addressable_shards}

    # placed at init: each device holds one shard's ring and spill pool
    for leaf in jax.tree.leaves(e_mesh.store.versions):
        assert held(leaf) == {str(d): 1 for d in mesh.devices.flat}
    snap_m = snap_o = None
    for i in range(3):
        batch = rand_batch(i)
        r_m, _ = e_mesh.run_batch(batch)
        r_o, _ = e_one.run_batch(batch)
        np.testing.assert_array_equal(np.asarray(r_m), np.asarray(r_o))
        if i == 0:
            snap_m = e_mesh.begin_snapshot()
            snap_o = e_one.begin_snapshot()
    np.testing.assert_array_equal(np.asarray(e_mesh.snapshot()),
                                  np.asarray(e_one.snapshot()))
    g = unshard(e_mesh.store.versions)
    s = unshard(e_one.store.versions)
    for f in ("begin", "end", "payload", "head"):
        np.testing.assert_array_equal(np.asarray(getattr(g, f)),
                                      np.asarray(getattr(s, f)), f)
    v_m, f_m = e_mesh.snapshot_read(np.arange(R), snap_m)
    v_o, f_o = e_one.snapshot_read(np.arange(R), snap_o)
    np.testing.assert_array_equal(np.asarray(v_m), np.asarray(v_o))
    np.testing.assert_array_equal(np.asarray(f_m), np.asarray(f_o))
    vals, found, m = e_mesh.run_readonly_batch(rand_batch(9))
    assert float(m["found_frac"]) == 1.0
    # and the commits keep it there
    assert held(e_mesh.store.versions.rings.begin) == \
        {str(d): 1 for d in mesh.devices.flat}
    print("MESH_STORE_OK")
""")


def test_sharded_store_mesh_substrate():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(root), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_STORE_OK" in out.stdout
