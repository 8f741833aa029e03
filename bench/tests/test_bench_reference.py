"""The plain reference agrees with the engine served through
``TxnService`` at 4,096 records of 250 words, a pinned scan included,
and its control does not."""
import numpy as np
import pytest

from bench import check, harness, loadgen
from bench.reference.ycsb_rmw import Replay, values
from bench.tests.tiny import small_pools, tiny_cell

SEED = 2**31 + 5


def _serve(name):
    """Drive the cell's service by hand: a few batches, a pin, more
    batches, a scan at the pin; returns (outcome, pools, init)."""
    import jax
    cell = tiny_cell(name)
    cfg = cell.config
    svc = harness.build_service(cfg, cell.mix, jax.devices()[:1])
    svc.engine.reset_store(harness.initial_records(SEED, cfg.records,
                                                   cfg.payload_words))
    client = harness.Client(svc, cell, SEED)
    client.keep_ticket[:] = True
    client.keep_scan[:] = True
    scan_mix = loadgen.Mix(**{**cell.mix.__dict__, "scan_ops": 10,
                              "scan_theta": 0.9})
    scans = loadgen.scan_pool(SEED, scan_mix, cfg.records, cfg.batch_txns)
    client.scan_pool = scans
    client.scan_batches = [harness.txn_batch(b, 0) for b in scans]
    for _ in range(3):
        client._submit(None)
    client._repin()
    for _ in range(4):
        client._submit(None)
    client._scan(None)
    client._scan(None)
    client.drain(None, float("inf"))
    out = client.outcome
    out.head = np.asarray(svc.engine.store.base)
    init = np.asarray(harness.initial_records(SEED, cfg.records,
                                              cfg.payload_words))
    return out, client.pool, scans, init


@pytest.fixture(scope="module")
def served():
    with pytest.MonkeyPatch.context() as mp:
        small_pools(mp)
        return _serve("ycsb-1kb.z0.9-2rmw8r")


def test_reference_agrees_with_the_served_engine(served):
    out, pool, scans, init = served
    readings = check.compare(Replay(init), out, pool, scans)
    assert readings["compared_tickets"] == 7
    assert readings["compared_scans"] == 2
    for name in check.LIMITS:
        assert readings[name] == 0, name
    assert check.verdict(readings)


def test_control_answers_fail_the_comparison(served):
    out, pool, scans, init = served
    readings = check.compare(Replay(init), out, pool, scans, control=True)
    # the control breaks serializability within a batch and pins one
    # batch early: both must show, and fail the verdict
    assert readings["compared_tickets"] == 7
    assert readings["compared_scans"] == 2
    assert readings["ticket_mismatch_reads"] > 0
    assert readings["snapshot_mismatch_reads"] > 0
    assert readings["head_mismatch_records"] == 0
    assert not check.verdict(readings)


def test_snapshot_reads_came_through_the_pin(served):
    out, pool, scans, init = served
    # the scan ran after 7 batches but was pinned after 3: the values it
    # read are the state after 3, which differs from the head
    assert {s.pin for s in out.scans} == {3}
    assert all(s.found.all() for s in out.scans)
    rp = Replay(init)
    for i in out.history:
        rp.apply(pool[i].write_set)
    later = values(init, rp.counts, np.maximum(scans[0].read_set, 0))
    assert np.any(later != out.scans[0].vals)


def test_an_altered_answer_is_caught(served):
    out, pool, scans, init = served
    bad = check.Outcome(list(out.history), dict(out.ticket_reads),
                        list(out.scans), out.head.copy())
    bad.ticket_reads[5] = bad.ticket_reads[5].copy()
    bad.ticket_reads[5][17, 3, 200] += 1
    bad.head[123, 249] ^= 1
    readings = check.compare(Replay(init), bad, pool, scans)
    assert readings["ticket_mismatch_reads"] == 1
    assert readings["head_mismatch_records"] == 1
    assert not check.verdict(readings)


def test_serial_reads_by_hand():
    init = np.arange(12, dtype=np.int32).reshape(4, 3)
    rp = Replay(init)
    reads = np.array([[0, 1], [1, 2], [1, -1]], np.int32)
    writes = np.array([[0, 1], [1, -1], [-1, -1]], np.int32)
    got = rp.serial_reads(reads, writes)
    # txn 0 reads the initial records 0 and 1 and writes both (+1)
    # txn 1 reads record 1 after txn 0's write, and record 2
    # txn 2 reads record 1 after two writes
    assert got[:, :, 0].tolist() == [[0, 3], [4, 6], [5, 0]]
    assert got[2, 1].tolist() == [0, 0, 0]
    assert rp.head()[:, 0].tolist() == [1, 5, 6, 9]
    assert np.array_equal(rp.head()[:, 1:], init[:, 1:])


def test_word_zero_wraps_like_int32():
    init = np.full((1, 2), np.iinfo(np.int32).max, np.int32)
    rp = Replay(init)
    rp.apply(np.array([[0]], np.int32))
    assert rp.head()[0, 0] == np.iinfo(np.int32).min
