"""CC-phase unit tests: version ordering, end timestamps, read resolution,
duplicate write-set handling, and equivalence of the record-partitioned
(shard_map) planner."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import (INF_TS, batch_footprint, cc_plan,
                             footprints_conflict, merge_batches,
                             merge_footprints)
from repro.core.txn import make_batch


def test_versions_sorted_by_record_then_ts():
    writes = np.array([[3, 1], [1, -1], [3, 2]])
    reads = np.full((3, 2), -1)
    batch = make_batch(reads, writes, np.zeros(3), np.zeros((3, 1)))
    p = cc_plan(batch, jnp.int32(100))
    w = np.asarray(p.w_rec)[np.asarray(p.w_valid)]
    t = np.asarray(p.w_txn)[np.asarray(p.w_valid)]
    assert w.tolist() == [1, 1, 2, 3, 3]
    assert t.tolist() == [0, 1, 2, 0, 2]     # ts order within each record


def test_end_ts_is_successor_begin():
    writes = np.array([[5], [5], [5]])
    batch = make_batch(np.full((3, 1), -1), writes, np.zeros(3),
                       np.zeros((3, 1)))
    p = cc_plan(batch, jnp.int32(0))
    valid = np.asarray(p.w_valid)
    ends = np.asarray(p.w_end_local)[valid]
    assert ends.tolist() == [1, 2, 3]        # succ ts, then T (=infinity)
    assert np.asarray(p.commit_mask)[valid].tolist() == [False, False, True]


def test_rmw_reads_predecessor():
    """A txn that reads+writes record r sees the LAST earlier write."""
    writes = np.array([[7], [7], [7]])
    reads = np.array([[7], [7], [7]])
    batch = make_batch(reads, writes, np.zeros(3), np.zeros((3, 1)))
    p = cc_plan(batch, jnp.int32(0))
    dep = np.asarray(p.r_dep_txn)[:, 0]
    assert dep.tolist() == [-1, 0, 1]        # base, then chain


def test_read_after_unrelated_writes_resolves_base():
    writes = np.array([[3], [-1]])
    reads = np.array([[4], [4]])
    batch = make_batch(reads, writes, np.zeros(2), np.zeros((2, 1)))
    p = cc_plan(batch, jnp.int32(0))
    assert np.asarray(p.r_dep_txn).flatten().tolist() == [-1, -1]


def test_reader_never_sees_later_write():
    """txn 0 reads r; txn 1 writes r — anti-dependency respected."""
    writes = np.array([[-1], [9]])
    reads = np.array([[9], [-1]])
    batch = make_batch(reads, writes, np.zeros(2), np.zeros((2, 1)))
    p = cc_plan(batch, jnp.int32(0))
    assert int(p.r_dep_txn[0, 0]) == -1      # reads the base version


def test_duplicate_write_set_entries_stable_order():
    """A txn whose write-set names the same record twice must keep program
    order under the (record, ts) sort: ties on the composite key are broken
    by write column (stable sort), so the LAST write is the segment-final
    version and the earlier duplicate gets begin == end (never visible)."""
    writes = np.array([[5, 5]])
    reads = np.array([[5, 5]])
    batch = make_batch(reads, writes, np.zeros(1), np.zeros((1, 1)))
    p = cc_plan(batch, jnp.int32(7))
    valid = np.asarray(p.w_valid)
    assert valid.tolist() == [True, True]
    # both versions carry ts 7; only the column-1 write commits
    assert np.asarray(p.w_begin_ts)[valid].tolist() == [7, 7]
    assert np.asarray(p.commit_mask).tolist() == [False, True]
    # the earlier duplicate is closed at its own begin ts -> zero lifetime
    assert np.asarray(p.w_end_ts)[0] == 7
    # slots follow program order: write col 0 -> slot 0, col 1 -> slot 1
    assert np.asarray(p.w_slot)[0].tolist() == [0, 1]
    # the txn's own reads see the PREDECESSOR (base), not its duplicates
    assert np.asarray(p.r_dep_txn).flatten().tolist() == [-1, -1]


def test_duplicate_write_set_last_write_wins_end_to_end():
    """Engine-level regression: with a duplicate write-set the later write
    column must become the committed head AND the ring's visible version."""
    from repro.core.engine import BohmEngine
    from repro.core.txn import Workload

    def two_writes(vals, args):
        w = jnp.zeros_like(vals).at[0, 0].set(10).at[1, 0].set(20)
        return w, jnp.zeros((), bool)

    wl = Workload(name="dup", n_read=2, n_write=2, payload_words=1,
                  branches=(two_writes,))
    batch = make_batch(np.array([[5, 5]]), np.array([[5, 5]]),
                       np.zeros(1), np.zeros((1, 1)))
    eng = BohmEngine(8, wl)
    eng.run_batch(batch)
    assert int(eng.snapshot()[5, 0]) == 20
    vals, found = eng.snapshot_read(np.array([5]))
    assert bool(found[0]) and int(vals[0, 0]) == 20


# ---------------------------------------------------------------------------
# Batch footprints: the conflict-aware scheduler's merge-eligibility test.
# ---------------------------------------------------------------------------
def _fp(reads, writes, R=130):
    batch = make_batch(np.asarray(reads), np.asarray(writes),
                       np.zeros(len(reads)), np.zeros((len(reads), 1)))
    return batch, batch_footprint(batch, R)


def _bits_to_set(bits):
    return {w * 64 + r for w in range(len(bits)) for r in range(64)
            if (int(bits[w]) >> r) & 1}


def test_footprint_bitsets_cover_exactly_the_touched_records():
    # R=130 spans three uint64 words; pads (-1) must not set bits
    batch, fp = _fp([[0, 64], [129, -1]], [[64, -1], [-1, -1]])
    assert _bits_to_set(fp.read_bits) == {0, 64, 129}
    assert _bits_to_set(fp.write_bits) == {64}
    assert _bits_to_set(fp.rw_bits) == {0, 64, 129}


def test_footprints_conflict_directions():
    _, a = _fp([[1]], [[2]])
    _, b = _fp([[3]], [[4]])
    assert not footprints_conflict(a, b)
    _, w_r = _fp([[9]], [[5]])       # writes 5 ...
    _, r_w = _fp([[5]], [[6]])       # ... which the other reads
    assert footprints_conflict(w_r, r_w)
    assert footprints_conflict(r_w, w_r)             # symmetric
    _, w_w = _fp([[-1]], [[7]])
    _, w_w2 = _fp([[-1]], [[7]])                     # write-write
    assert footprints_conflict(w_w, w_w2)
    # read-read sharing is NOT a conflict (reads commute)
    _, r1 = _fp([[8]], [[1]])
    _, r2 = _fp([[8]], [[2]])
    assert not footprints_conflict(r1, r2)


def test_footprint_signatures_certify_disjointness():
    """The uint64 block signature: bit j set <=> some touched 64-record
    block w has w % 64 == j. Disjoint signatures certify disjoint
    footprints (never a false negative on conflicts); colliding
    signatures of truly disjoint sets fall back to the word scan and
    stay non-conflicting."""
    from repro.core.plan import signatures_disjoint

    batch, fp = _fp([[0, 64], [129, -1]], [[64, -1], [-1, -1]])
    # r in {0, 64, 129} -> blocks {0, 1, 2}; writes {64} -> block {1}
    assert fp.rw_sig == 0b111
    assert fp.write_sig == 0b10
    # blocks 0 vs 1: signatures certify disjointness
    _, a = _fp([[2]], [[2]])
    _, b = _fp([[66]], [[66]])
    assert signatures_disjoint(a, b)
    assert not footprints_conflict(a, b)
    # records 2 and 3 share block 0: the signature CANNOT certify,
    # but the word scan still proves the footprints disjoint
    _, c = _fp([[3]], [[3]])
    assert not signatures_disjoint(a, c)
    assert not footprints_conflict(a, c)
    # a true conflict is never certified disjoint
    _, d = _fp([[2]], [[-1]])
    assert not signatures_disjoint(a, d)
    assert footprints_conflict(a, d)
    # merged signatures are the OR of the members' signatures
    fm = merge_footprints(a, c)
    assert fm.rw_sig == a.rw_sig | c.rw_sig
    assert fm.write_sig == a.write_sig | c.write_sig


def test_footprint_signature_randomized_agreement():
    """signatures_disjoint => not footprints_conflict on random batches
    (the fast path may only ever skip work, never flip a verdict)."""
    from repro.core.plan import signatures_disjoint

    rng = np.random.default_rng(42)
    fps = []
    for _ in range(24):
        reads = rng.integers(-1, 130, (4, 3))
        writes = np.where(rng.random((4, 3)) < 0.5, reads, -1)
        fps.append(_fp(reads, writes)[1])
    for a in fps:
        for b in fps:
            slow = bool(np.any(a.write_bits & b.rw_bits)
                        or np.any(b.write_bits & a.rw_bits))
            assert footprints_conflict(a, b) == slow
            if signatures_disjoint(a, b):
                assert not slow


def test_merge_batches_preserves_order_and_timestamps():
    """cc_plan over a merged epoch assigns every txn the same global
    begin/end ts as the two per-batch plans at consecutive ts bases —
    the merge-eligibility condition's provably-identical claim."""
    b1, f1 = _fp([[3, 4]], [[3, -1]])
    b2, f2 = _fp([[10, 11]], [[10, 11]])
    assert not footprints_conflict(f1, f2)
    merged = merge_batches(b1, b2)
    assert merged.size == 2
    fm = merge_footprints(f1, f2)
    assert (fm.rw_bits == (f1.rw_bits | f2.rw_bits)).all()
    pm = cc_plan(merged, jnp.int32(5))
    p1 = cc_plan(b1, jnp.int32(5))
    p2 = cc_plan(b2, jnp.int32(6))

    def rows(p):
        v = np.asarray(p.w_valid).astype(bool)
        out = np.stack([np.asarray(p.w_rec)[v], np.asarray(p.w_begin_ts)[v],
                        np.asarray(p.w_end_ts)[v],
                        np.asarray(p.commit_mask)[v]], axis=1)
        return out[np.lexsort(out.T[::-1])]

    both = np.concatenate([rows(p1), rows(p2)])
    np.testing.assert_array_equal(rows(pm),
                                  both[np.lexsort(both.T[::-1])])
    # reads of the second batch resolve exactly as they did standalone
    # (disjoint footprints: nothing in b1 can become their producer)
    np.testing.assert_array_equal(np.asarray(pm.r_dep_txn)[1],
                                  np.asarray(p2.r_dep_txn)[0])


def test_merge_batches_rejects_width_mismatch():
    a = make_batch(np.zeros((1, 2)), np.zeros((1, 2)), np.zeros(1),
                   np.zeros((1, 1)))
    b = make_batch(np.zeros((1, 3)), np.zeros((1, 3)), np.zeros(1),
                   np.zeros((1, 1)))
    with pytest.raises(ValueError):
        merge_batches(a, b)


# ---------------------------------------------------------------------------
# Record-partitioned CC equivalence. The in-process variant needs >1 device;
# the property sweep runs in a subprocess that forces 4 host devices (the
# repo convention — the main test process must keep seeing 1 device).
# ---------------------------------------------------------------------------
_SHARDED_PROPERTY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.engine import BohmEngine
    from repro.core.plan import cc_plan, cc_plan_sharded, merge_sharded_plan
    from repro.core.txn import Workload, make_batch

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

    def version_rows(p):
        v = np.asarray(p.w_valid).astype(bool)
        rows = np.stack([np.asarray(p.w_rec)[v], np.asarray(p.w_txn)[v],
                         np.asarray(p.w_end_local)[v],
                         np.asarray(p.commit_mask)[v].astype(np.int32),
                         np.asarray(p.w_begin_ts)[v],
                         np.asarray(p.w_end_ts)[v]], axis=1)
        return rows[np.lexsort(rows.T[::-1])]

    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def ro(vals, args):
        return vals, jnp.zeros((), bool)

    wl = Workload("inc", OPS, OPS, 2, (rmw, ro))
    for seed in range(6):
        batch = rand_batch(seed)
        p1 = cc_plan(batch, jnp.int32(1))
        ps = merge_sharded_plan(
            cc_plan_sharded(batch, jnp.int32(1), mesh), batch)
        # identical read resolution (producer txn per read)
        np.testing.assert_array_equal(np.asarray(p1.r_dep_txn),
                                      np.asarray(ps.r_dep_txn))
        # identical write resolution: same (rec, txn, end, commit, ts) set
        np.testing.assert_array_equal(version_rows(p1), version_rows(ps))

    # end-to-end: sharded engine == unsharded engine, incl. snapshot ring
    for seed in range(3):
        e_u = BohmEngine(R, wl)
        e_s = BohmEngine(R, wl, mesh=mesh)
        for i in range(2):
            batch = rand_batch(100 + seed * 10 + i)
            r_u, _ = e_u.run_batch(batch)
            r_s, _ = e_s.run_batch(batch)
            np.testing.assert_array_equal(np.asarray(r_u),
                                          np.asarray(r_s))
        np.testing.assert_array_equal(np.asarray(e_u.snapshot()),
                                      np.asarray(e_s.snapshot()))
        v_u, f_u = e_u.snapshot_read(np.arange(R))
        v_s, f_s = e_s.snapshot_read(np.arange(R))
        np.testing.assert_array_equal(np.asarray(v_u), np.asarray(v_s))
        np.testing.assert_array_equal(np.asarray(f_u), np.asarray(f_s))
    print("SHARDED_PROPERTY_OK")
""")


def test_sharded_plan_property_sweep():
    import os
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED_PROPERTY_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(root), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_PROPERTY_OK" in out.stdout


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device for the cc mesh axis")
def test_sharded_plan_matches_unsharded():
    from repro.core.plan import cc_plan_sharded, merge_sharded_plan
    from repro.runtime import cc_mesh
    mesh = cc_mesh()
    rng = np.random.default_rng(0)
    writes = rng.integers(0, 16, (8, 3))
    reads = rng.integers(0, 16, (8, 3))
    batch = make_batch(reads, writes, np.zeros(8), np.zeros((8, 1)))
    p1 = cc_plan(batch, jnp.int32(0))
    ps = merge_sharded_plan(
        cc_plan_sharded(batch, jnp.int32(0), mesh), batch)
    # same read dependencies (the observable contract)
    np.testing.assert_array_equal(np.asarray(p1.r_dep_txn),
                                  np.asarray(ps.r_dep_txn))
