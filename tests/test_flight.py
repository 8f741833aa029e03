"""Flight recorder (repro.obs.flight): the zero-sync contract (recorder
off OR on adds ZERO fences and leaves results byte-identical), the
telescoping latency breakdown, randomized conflict-witness soundness,
async-lane Chrome-trace invariants and streaming quantile accuracy."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.plan import (batch_footprint, conflict_witness,
                             footprints_conflict)
from repro.core.txn import Workload, make_batch
from repro.obs import (NULL_FLIGHT, FlightRecorder, LogHistogram,
                       PhaseTracer, stitch_chrome_trace,
                       validate_chrome_trace)
from repro.service import TxnService

T, OPS, R = 16, 3, 64


def _inc_workload():
    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def read_only(vals, args):
        return vals, jnp.zeros((), bool)

    return Workload(name="inc", n_read=OPS, n_write=OPS, payload_words=2,
                    branches=(rmw, read_only))


def _random_batch(seed: int, lo: int = 0, hi: int = R, t: int = T):
    rng = np.random.default_rng(seed)
    reads = rng.integers(lo, hi, (t, OPS))
    wmask = rng.random((t, OPS)) < 0.6
    writes = np.where(wmask, reads, -1)
    types = rng.integers(0, 2, t)
    args = rng.integers(1, 5, (t, 1))
    return make_batch(reads, writes, types, args)


def _run_stream(flight, n=6, **svc_kw):
    """One conflict-aware OOO stream; returns (service, read values)."""
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng, max_inflight=2, admission_window=4,
                     max_inflight_execs=2, flight=flight, **svc_kw)
    tickets = svc.submit_many([_random_batch(s) for s in range(n)])
    tickets.append(svc.submit(_random_batch(99, hi=8, t=4),
                              latency_class="interactive"))
    reads = [np.asarray(svc.wait(t).read_vals) for t in tickets]
    svc.drain()
    return svc, reads


# ------------------------------------------------------- zero-sync contract
def test_flight_adds_zero_fences_and_results_identical(monkeypatch):
    """The recorder — OFF or ON — introduces no jax fences (stamps ride
    joins the scheduler already performs) and leaves every read result
    byte-identical."""
    _, want = _run_stream(None)                       # no recorder at all

    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    fences = {}
    for name, flight in [("off", FlightRecorder(enabled=False)),
                         ("on", FlightRecorder(enabled=True))]:
        calls["n"] = 0
        monkeypatch.setattr(jax, "block_until_ready", counting)
        svc, got = _run_stream(flight)
        monkeypatch.setattr(jax, "block_until_ready", real)
        fences[name] = calls["n"]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert svc.flight is flight
    # identical fence count whether the recorder is off or on: the
    # whole point of host-side stamping at existing transitions
    assert fences["on"] == fences["off"]


def test_null_flight_records_nothing():
    svc, _ = _run_stream(FlightRecorder(enabled=False))
    assert not svc.flight.records() and not svc.flight.inflight()
    assert svc.flight.completed == 0
    assert NULL_FLIGHT.records() == []    # the shared default, untouched


# -------------------------------------------------- breakdown + SLO gauges
def test_breakdown_telescopes_and_health_slo():
    flight = FlightRecorder(enabled=True)
    svc, _ = _run_stream(flight)
    recs = flight.records()
    assert len(recs) == 7 and flight.completed == 7
    for f in recs:
        assert f.complete
        bd = f.breakdown()
        parts = sum(bd[p] for p in
                    ("queue", "formation", "exec", "commit_defer"))
        assert parts == pytest.approx(bd["total"], abs=1e-9)
        assert all(v >= 0 for v in bd.values())
        assert bd["total"] == f.t_visible - f.t_submit

    health = svc.health()
    slo = health["flight_slo"]
    assert set(slo) == {"interactive", "bulk"}
    assert slo["interactive"]["count"] == 1
    assert slo["bulk"]["count"] == 6
    for g in slo.values():
        assert 0 < g["p50_ms"] <= g["p99_ms"]
    assert health["flight_completed"] == 7
    assert health["flight_inflight"] == 0


def test_conflict_attribution_populates_heatmap():
    """A deliberately conflict-heavy stream (every batch hits the same
    8-record range) must produce blocked events with real witnesses."""
    flight = FlightRecorder(enabled=True)
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng, max_inflight=2, admission_window=4,
                     max_inflight_execs=2, flight=flight)
    for t in svc.submit_many([_random_batch(s, hi=8) for s in range(6)]):
        svc.wait(t)
    svc.drain()
    assert flight.block_kinds.get("epoch-conflict", 0) > 0
    top = flight.blocking_top()
    assert top and all(n >= 1 for _, n in top)
    assert [n for _, n in top] == sorted(
        (n for _, n in top), reverse=True)
    # every heatmap record is a real record id in range
    assert all(0 <= rec < R for rec, _ in top)


# ------------------------------------------------- conflict witness (prop)
def test_conflict_witness_randomized_soundness():
    """witness(a, b) is a record written by one side and touched by the
    other; None exactly when the footprints commute."""
    rng = np.random.default_rng(7)
    n_r = 320
    fps = []
    for _ in range(24):
        t = int(rng.integers(1, 6))
        reads = rng.integers(0, n_r, (t, 4))
        writes = np.where(rng.random((t, 4)) < 0.5, reads, -1)
        batch = make_batch(reads, writes, np.zeros(t), np.zeros((t, 1)))
        fps.append(batch_footprint(batch, n_r))

    def touched(fp, rec):
        return bool(int(fp.rw_bits[rec >> 6]) >> (rec & 63) & 1)

    def written(fp, rec):
        return bool(int(fp.write_bits[rec >> 6]) >> (rec & 63) & 1)

    checked_conflicts = 0
    for i, a in enumerate(fps):
        for b in fps[i + 1:]:
            w = conflict_witness(a, b)
            if footprints_conflict(a, b):
                assert w is not None
                assert ((written(a, w) and touched(b, w))
                        or (written(b, w) and touched(a, w)))
                checked_conflicts += 1
            else:
                assert w is None
    assert checked_conflicts > 10    # the workload actually conflicts


# ----------------------------------------------------- async-lane export
def test_async_lanes_validate_and_stitch():
    flight = FlightRecorder(enabled=True)
    flight.on_submit(0, 0, 16)
    flight.on_submit(1, 1, 16)
    flight.on_dispatch([0, 1], epoch=0, epoch_txns=32, epoch_batches=2)
    flight.on_blocked(1, "epoch-conflict", blocker=0, witness=42)
    flight.on_exec([0, 1], chain_depth=2)
    flight.on_commit([0, 1])
    flight.on_visible(0)
    flight.on_visible(1)

    events = flight.to_async_events(t0=flight.earliest_ts())
    counts = validate_chrome_trace({"traceEvents": events})
    assert counts["async_lanes"] == 2
    # ticket span + 4 phase spans per lane
    assert counts["async_spans"] == 2 * 5
    blocked = [e for e in events if e["name"] == "blocked"]
    assert len(blocked) == 1 and blocked[0]["args"]["witness"] == 42

    tracer = PhaseTracer(enabled=True)
    with tracer.span("plan", txns=32):
        pass
    trace = stitch_chrome_trace(tracer, flight)
    counts = validate_chrome_trace(trace)
    assert counts["async_lanes"] == 2 and counts["spans"] == 1
    assert trace["otherData"]["flight_tickets"] == 2
    assert json.loads(json.dumps(trace)) == trace    # JSON-serializable


def test_async_lane_validator_rejects_malformed():
    ok = {"name": "ticket", "ph": "b", "ts": 0, "pid": 0, "tid": 0,
          "cat": "flight", "id": "7"}
    # 'e' without an open 'b' in the same lane
    bad_e = dict(ok, ph="e", id="8")
    with pytest.raises(ValueError, match="without open"):
        validate_chrome_trace({"traceEvents": [ok, bad_e]})
    # async event without an id
    no_id = {k: v for k, v in ok.items() if k != "id"}
    with pytest.raises(ValueError, match="missing 'id'"):
        validate_chrome_trace({"traceEvents": [no_id]})
    # dangling 'b' (lane never closed)
    with pytest.raises(ValueError, match="never closed"):
        validate_chrome_trace({"traceEvents": [ok]})


def test_flight_capacity_bounded():
    flight = FlightRecorder(capacity=4, enabled=True)
    for tk in range(10):
        flight.on_submit(tk, 1, 1)
        flight.on_dispatch([tk], epoch=tk, epoch_txns=1, epoch_batches=1)
        flight.on_exec([tk])
        flight.on_commit([tk])
        flight.on_visible(tk)
    assert len(flight.records()) == 4
    assert flight.dropped == 6
    assert flight.completed == 10          # counters keep the true total
    assert [f.ticket for f in flight.records()] == [6, 7, 8, 9]


# ------------------------------------------------------ quantile digests
def test_log_histogram_tracks_numpy_percentiles():
    rng = np.random.default_rng(3)
    xs = rng.lognormal(mean=-7.0, sigma=1.2, size=4000)   # latency-like
    h = LogHistogram()
    h.extend(xs)
    assert h.count == 4000
    for q in (50.0, 90.0, 99.0):
        got, want = h.quantile(q), float(np.percentile(xs, q))
        assert got == pytest.approx(want, rel=2 * h.rel_error)
    assert h.quantile(0.0) == pytest.approx(xs.min())
    assert h.quantile(100.0) == pytest.approx(xs.max())
    assert h.mean == pytest.approx(xs.mean(), rel=1e-9)

    h2 = LogHistogram()
    h2.extend(xs[:1000])
    h3 = LogHistogram()
    h3.extend(xs[1000:])
    h2.merge(h3)
    assert h2.quantile(99.0) == pytest.approx(h.quantile(99.0))
    # round-trips through its dict form
    back = LogHistogram.from_dict(h.to_dict())
    assert back.quantile(50.0) == h.quantile(50.0)
