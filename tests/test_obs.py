"""Observability plane (repro.obs): registry semantics, unfenced tracing
(no host sync whether it records, annotates or neither), the spans'
names and epoch arguments on the profiler's annotations, Chrome-trace
export invariants, EWMA regression (the ft.monitor extraction),
provenance stamping, health gauges — and the non-perturbation
properties: instrumentation must leave engine/service results
byte-identical, and the pipelined vs barriered schedules must agree on
every data counter."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.txn import Workload, make_batch
from repro.obs import (Ewma, EwmaAnomaly, MetricsRegistry, NULL_SPAN,
                       PhaseTracer, run_metadata, validate_chrome_trace)
from repro.service import TxnService

T, OPS, R = 16, 3, 32


def _inc_workload():
    def rmw(vals, args):
        return vals.at[..., 0].add(args[0]), jnp.zeros((), bool)

    def read_only(vals, args):
        return vals, jnp.zeros((), bool)

    return Workload(name="inc", n_read=OPS, n_write=OPS, payload_words=2,
                    branches=(rmw, read_only))


def _random_batch(seed: int, lo: int = 0, hi: int = R):
    rng = np.random.default_rng(seed)
    reads = rng.integers(lo, hi, (T, OPS))
    wmask = rng.random((T, OPS)) < 0.6
    writes = np.where(wmask, reads, -1)
    types = rng.integers(0, 2, T)
    args = rng.integers(1, 5, (T, 1))
    return make_batch(reads, writes, types, args)


# ---------------------------------------------------------------- registry
def test_registry_device_counters_and_snapshot():
    reg = MetricsRegistry()
    reg.declare("a/vec", jnp.zeros(4, jnp.int32))
    reg.accumulate("a/vec", jnp.arange(4, dtype=jnp.int32))
    reg.accumulate("a/vec", jnp.ones(4, jnp.int32))
    reg.accumulate("a/scalar", jnp.int32(3))     # auto-declared
    reg.accumulate("a/scalar", jnp.int32(4))
    snap = reg.snapshot()
    np.testing.assert_array_equal(snap["a/vec"], [1, 2, 3, 4])
    assert snap["a/scalar"] == 7                 # 0-d -> python int
    assert isinstance(snap["a/scalar"], int)
    # peek hands back the device array without transfer semantics change
    assert int(reg.peek("a/scalar")) == 7
    reg.reset("a/scalar")
    assert reg.value("a/scalar") == 0
    np.testing.assert_array_equal(reg.value("a/vec"), [1, 2, 3, 4])
    reg.reset()
    np.testing.assert_array_equal(reg.value("a/vec"), [0, 0, 0, 0])
    # re-declare resets (reset_store lifecycle)
    reg.accumulate("a/vec", jnp.ones(4, jnp.int32))
    reg.declare("a/vec", jnp.zeros(4, jnp.int32))
    np.testing.assert_array_equal(reg.value("a/vec"), [0, 0, 0, 0])


def test_registry_host_counters_and_gauges():
    reg = MetricsRegistry()
    reg.inc("h/x")
    reg.inc("h/x", 4)
    reg.set("h/y", "label")
    reg.register_gauge("g/z", lambda: 42)
    snap = reg.snapshot()
    assert snap["h/x"] == 5 and snap["h/y"] == "label" and snap["g/z"] == 42
    assert "g/z" not in reg.snapshot(include_gauges=False)
    assert reg.value("g/z") == 42
    assert set(reg.names()) == {"h/x", "h/y", "g/z"}


def test_metrics_view_dict_semantics():
    reg = MetricsRegistry()
    view = reg.view("svc/")
    for k in ("a", "b", "c"):
        view[k] = 0
    view["a"] += 2
    view.update(b=5)
    view["c"] = max(view["c"], 3)
    assert dict(view) == {"a": 2, "b": 5, "c": 3}
    assert list(view) == ["a", "b", "c"]         # insertion order
    assert len(view) == 3
    with pytest.raises(KeyError):
        view["missing"]
    # namespacing: a second view is isolated, registry sees full names
    other = reg.view("other/")
    other["a"] = 99
    assert view["a"] == 2
    assert reg.snapshot()["svc/a"] == 2
    assert reg.snapshot()["other/a"] == 99
    del view["c"]
    assert "c" not in view


# ----------------------------------------------------------------- tracing
def test_tracer_disabled_is_null_span_and_records_nothing():
    tr = PhaseTracer(enabled=False)
    sp = tr.span("plan_phase", txns=8)
    assert sp is NULL_SPAN
    with sp as s:
        s.note(k=1)
    tr.instant("decision", x=1)
    assert tr.events() == []
    assert tr.to_chrome_trace()["traceEvents"] == []


def test_tracer_disabled_never_blocks(monkeypatch):
    """The zero-sync property: a run_batch stream performs ZERO
    block_until_ready fences with the default tracer, and so does a
    tracer that records AND annotates — through the engine and through
    the service's dispatch path, whose results stay byte-identical to an
    untraced service's."""
    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    assert not eng.tracer.enabled and eng.tracer.annotate
    batches = [_random_batch(s) for s in range(3)]
    monkeypatch.setattr(jax, "block_until_ready", counting)
    for b in batches:
        eng.run_batch(b)
    eng.gc_sweep()
    assert calls["n"] == 0
    # tracing on (ring + annotations) adds no fence either
    eng2 = BohmEngine(R, _inc_workload(), ring_slots=8,
                      tracer=PhaseTracer(enabled=True, annotate=True))
    for b in batches:
        eng2.run_batch(b)
    eng2.run_readonly_batch(batches[0], 1)
    eng2.gc_sweep()
    assert calls["n"] == 0
    assert validate_chrome_trace(eng2.tracer.to_chrome_trace())["spans"] > 0

    def served(tracer):
        e = BohmEngine(R, _inc_workload(), ring_slots=8, tracer=tracer)
        svc = TxnService(e, max_inflight=2, admission_window=2)
        tickets = [svc.submit(b) for b in batches * 2]
        calls["n"] = 0
        svc._pump(flush=True)                # the dispatch path alone
        dispatched = calls["n"]
        reads = [np.asarray(svc.wait(t).read_vals) for t in tickets]
        svc.drain()
        return dispatched, reads, np.asarray(e.store.base)

    n_on, reads_on, base_on = served(PhaseTracer(enabled=True,
                                                 annotate=True))
    n_off, reads_off, base_off = served(PhaseTracer(enabled=False))
    assert n_on == n_off
    for a, b in zip(reads_on, reads_off):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(base_on, base_off)


def test_tracer_span_export_and_validation(tmp_path):
    tr = PhaseTracer(enabled=True)
    with tr.span("outer", txns=4) as sp:
        with tr.span("inner"):
            pass
        tr.instant("decision", kind="merge")
        sp.note(result=7)
    trace = tr.to_chrome_trace()
    counts = validate_chrome_trace(trace)
    assert counts == {"spans": 2, "instants": 1, "events": 5,
                      "async_spans": 0, "async_lanes": 0, "counters": 0}
    ev = trace["traceEvents"]
    names = [(e["ph"], e["name"]) for e in ev]
    assert names == [("B", "outer"), ("B", "inner"), ("E", "inner"),
                     ("i", "decision"), ("E", "outer")]
    outer_end = ev[-1]
    assert outer_end["args"]["result"] == 7      # note() landed
    assert "dur_ms" in outer_end["args"]
    assert ev[3]["s"] == "t"                     # thread-scoped instant
    path = tmp_path / "trace.json"
    tr.export(path)
    assert validate_chrome_trace(json.loads(path.read_text())) == counts
    durs = tr.span_durations()
    assert set(durs) == {"outer", "inner"}
    assert durs["outer"][0] >= durs["inner"][0] >= 0


def test_tracer_ring_overflow_export_stays_valid():
    tr = PhaseTracer(enabled=True, capacity=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 2 * 20 - 8
    counts = validate_chrome_trace(tr.to_chrome_trace())
    assert counts["spans"] == 4                  # 8 events = 4 whole pairs
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


class _StubAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each span's
    name and arguments and whether it was entered and left."""
    log: list = []

    def __init__(self, name, **kwargs):
        self.entry = [name, kwargs, "made"]
        _StubAnnotation.log.append(self.entry)

    def __enter__(self):
        self.entry[2] = "open"
        return self

    def __exit__(self, *exc):
        self.entry[2] = "closed"
        return False


@pytest.fixture
def annotations(monkeypatch):
    """Every span name and argument dict the tracers built after this
    fixture annotated."""
    _StubAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _StubAnnotation)
    return _StubAnnotation.log


def test_tracer_annotates_without_recording(annotations):
    """``annotate`` no longer depends on ``enabled``: an annotating
    tracer with the ring off hands every span to the profiler and
    records nothing; with both off nothing is annotated."""
    tr = PhaseTracer(enabled=False, annotate=True)
    with tr.span("service/plan", epoch=3, txns=8) as sp:
        sp.note(k=1)
    tr.instant("admission/hop", x=1)
    assert annotations == [["service/plan", {"epoch": 3, "txns": 8},
                            "closed"]]
    assert tr.events() == [] and tr.dropped == 0
    rec = PhaseTracer(enabled=True, annotate=True)
    with rec.span("engine/gc_sweep", watermark=5):
        pass
    assert annotations[-1] == ["engine/gc_sweep", {"watermark": 5},
                               "closed"]
    assert [e[:2] for e in rec.events()] == [("B", "engine/gc_sweep"),
                                             ("E", "engine/gc_sweep")]
    with PhaseTracer(enabled=False, annotate=False).span("x") as sp:
        assert sp is NULL_SPAN
    assert len(annotations) == 2


def test_service_spans_carry_their_epoch(annotations):
    """The default tracer annotates the service's host path: admission,
    epoch formation, the three dispatches and both joins, and every span
    of an epoch carries that epoch's ``dispatch_log`` index."""
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng, max_inflight=1, admission_window=2)
    tickets = svc.submit_many([_random_batch(s) for s in range(6)])
    for t in tickets:
        svc.wait(t)
    svc.drain()
    assert all(state == "closed" for _, _, state in annotations)
    names = {name for name, _, _ in annotations}
    assert {"service/admit", "service/form_epoch", "service/plan",
            "service/exec", "service/commit", "service/backpressure",
            "service/wait"} <= names
    assert not names & {"plan_phase", "exec_phase", "commit_phase"}
    by_name = {}
    for name, args, _ in annotations:
        by_name.setdefault(name, []).append(args)
    n_epochs = len(svc.dispatch_log)
    for name in ("service/form_epoch", "service/plan", "service/exec",
                 "service/commit"):
        assert [a["epoch"] for a in by_name[name]] == list(range(n_epochs))
    for args in by_name["service/plan"]:
        epoch = svc.dispatch_log[args["epoch"]]
        assert args["epoch_batches"] == len(epoch)
        assert args["txns"] == T * len(epoch)
    assert all(0 <= a["epoch"] < n_epochs
               for a in by_name["service/backpressure"])
    assert [a["ticket"] for a in by_name["service/admit"]] == tickets
    assert [a["ticket"] for a in by_name["service/wait"]] == tickets


def test_engine_spans_on_the_profiler(annotations):
    eng = BohmEngine(R, _inc_workload(), ring_slots=2, adaptive_k=True)
    for s in range(3):
        eng.run_batch(_random_batch(s))
    eng.run_readonly_batch(_random_batch(7), eng.current_ts())
    eng.gc_sweep()
    names = [name for name, _, _ in annotations]
    assert names[:3] == ["engine/plan", "engine/exec", "engine/commit"]
    assert {"engine/readonly", "engine/gc_sweep",
            "engine/reassign_k"} <= set(names)
    assert not set(names) & {"read/resolve", "gc_sweep", "reassign_k"}


def test_validate_chrome_trace_rejects_malformed():
    def ev(ph, name, ts, **kw):
        return dict({"name": name, "ph": ph, "ts": ts, "pid": 1,
                     "tid": 1}, **kw)

    with pytest.raises(ValueError, match="not a list"):
        validate_chrome_trace({})
    with pytest.raises(ValueError, match="missing 'ts'"):
        validate_chrome_trace({"traceEvents": [
            {"name": "a", "ph": "B", "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError, match="ts"):
        validate_chrome_trace({"traceEvents": [
            ev("B", "a", 5), ev("E", "a", 3)]})
    with pytest.raises(ValueError, match="E without open B"):
        validate_chrome_trace({"traceEvents": [ev("E", "a", 1)]})
    with pytest.raises(ValueError, match="closes B"):
        validate_chrome_trace({"traceEvents": [
            ev("B", "a", 1), ev("B", "b", 2), ev("E", "a", 3)]})
    with pytest.raises(ValueError, match="never closed"):
        validate_chrome_trace({"traceEvents": [ev("B", "a", 1)]})
    with pytest.raises(ValueError, match="unknown ph"):
        validate_chrome_trace({"traceEvents": [ev("X", "a", 1)]})


# ------------------------------------------------------- engine integration
def test_instrumented_engine_results_byte_identical():
    """Registry + enabled tracing must not perturb execution: read
    values, head store, and ring state match an uninstrumented engine."""
    batches = [_random_batch(s) for s in range(4)]
    plain = BohmEngine(R, _inc_workload(), ring_slots=8)
    traced = BohmEngine(R, _inc_workload(), ring_slots=8,
                        tracer=PhaseTracer(enabled=True))
    snap_p = snap_t = None
    for i, b in enumerate(batches):
        rp, _ = plain.run_batch(b)
        rt, _ = traced.run_batch(b)
        np.testing.assert_array_equal(np.asarray(rp), np.asarray(rt))
        if i == 1:
            snap_p = plain.begin_snapshot()
            snap_t = traced.begin_snapshot()
    np.testing.assert_array_equal(np.asarray(plain.store.base),
                                  np.asarray(traced.store.base))
    sp, fp, _ = plain.run_readonly_batch(batches[0], snap_p.ts)
    st, ft, _ = traced.run_readonly_batch(batches[0], snap_t.ts)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(st))
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(ft))
    assert validate_chrome_trace(traced.tracer.to_chrome_trace())["spans"] > 0


def test_engine_legacy_stats_surfaces_on_registry():
    eng = BohmEngine(R, _inc_workload(), ring_slots=2)
    for s in range(4):
        eng.run_batch(_random_batch(s))
    snap = eng.metrics.snapshot()
    assert snap["engine/commits"] == 4
    assert snap["engine/txns_committed"] == 4 * T
    ov = eng.overflow_stats()
    assert ov["total_overwrites"] == snap["engine/ring_overwrote_live"]
    sp = eng.spill_stats()
    assert sp["spill_admitted"] == snap["engine/spill_admitted"]
    # reset_store re-declares: counters go back to zero
    eng.reset_store(eng.store.base * 0)
    snap = eng.metrics.snapshot()
    assert snap["engine/ring_overwrote_live"] == 0


def test_service_and_scheduler_stats_namespaces():
    from repro.serving.scheduler import BohmScheduler
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng, max_inflight=2, admission_window=2)
    assert list(svc.stats) == ["submitted", "planned_ahead_max",
                               "backpressure_joins", "merged_batches",
                               "overlapped_execs", "hopped_batches",
                               "class_promotions", "chain_depth_max",
                               "admission_window_occupancy"]
    svc.submit(_random_batch(0))
    svc.drain()
    assert svc.stats["submitted"] == 1
    assert eng.metrics.snapshot()["service/submitted"] == 1
    sched = BohmScheduler(slots=2, num_pages=8, page_size=4,
                          max_pages_per_seq=4, registry=eng.metrics)
    assert dict(sched.stats) == {"admitted": 0, "completed": 0,
                                 "prefix_hits": 0, "pages_recycled": 0}
    assert eng.metrics.snapshot()["serving/admitted"] == 0


def test_pipelined_and_barriered_agree_on_data_counters():
    """Same stream through the pipelined and barriered schedules: every
    DATA counter (what happened to the data) matches. Decision counters
    (merges, overlaps, backpressure) legitimately differ."""
    data_keys = ["engine/txns_committed", "engine/aborts",
                 "engine/commits", "engine/waves",
                 "engine/ring_overwrote_live", "engine/ring_overwrote_dead",
                 "engine/spill_admitted", "engine/spill_dropped",
                 "engine/spill_overwrote_pinned",
                 "engine/paged_alloc_failed"]
    batches = [_random_batch(s) for s in range(6)]

    def run(pipelined, window):
        eng = BohmEngine(R, _inc_workload(), ring_slots=2)
        svc = TxnService(eng, max_inflight=2, pipelined=pipelined,
                         admission_window=window)
        for t in svc.submit_many(batches):
            svc.wait(t)
        svc.drain()
        snap = eng.metrics.snapshot()
        return {k: snap[k] for k in data_keys}

    barriered = run(False, 1)
    assert barriered["engine/txns_committed"] == 6 * T
    assert run(True, 1) == barriered
    # merged epochs change epoch shape (commits/waves) but not the data
    merged = run(True, 4)
    for k in ("engine/txns_committed", "engine/aborts",
              "engine/ring_overwrote_live", "engine/ring_overwrote_dead",
              "engine/spill_admitted", "engine/spill_dropped",
              "engine/spill_overwrote_pinned"):
        assert merged[k] == barriered[k], k


# --------------------------------------------------------------- ewma / ft
def test_ewma_seed_and_update():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.update(10.0) == 10.0                # first sample seeds
    assert e.update(20.0) == 15.0                # 0.5*10 + 0.5*20
    assert e.update(5.0) == 10.0
    assert e.n == 3
    with pytest.raises(ValueError):
        Ewma(alpha=0.0)
    with pytest.raises(ValueError):
        Ewma(alpha=1.5)


def test_ewma_anomaly_threshold_semantics():
    det = EwmaAnomaly(alpha=0.5, threshold=2.0)
    assert det.record(1.0) is False              # seeds, never anomalous
    assert det.baseline == 1.0
    assert det.record(3.0) is True               # 3 > 2 * 1
    assert det.baseline == 1.0                   # flagged: no update
    assert det.record(1.8) is False              # 1.8 <= 2 * 1
    assert det.baseline == pytest.approx(1.4)
    assert (det.n, det.n_anomalies) == (3, 1)
    with pytest.raises(ValueError):
        EwmaAnomaly(threshold=0.0)


def test_straggler_detector_regression():
    """ft.monitor must preserve its semantics through the obs.ewma
    extraction: same alpha/threshold arithmetic, same flag indices."""
    from repro.ft.monitor import StragglerDetector
    det = StragglerDetector(alpha=0.5, threshold=2.0)
    for _ in range(10):
        det.record(1.0)
    assert det.ewma == pytest.approx(1.0)
    assert det.record(5.0) is True               # 5 > 2x baseline
    assert det.flagged == [11]
    assert det.ewma == pytest.approx(1.0)        # flagged step excluded
    assert det.record(1.5) is False
    assert det.ewma == pytest.approx(1.25)
    assert det.n == 12
    assert (det.alpha, det.threshold) == (0.5, 2.0)


# ---------------------------------------------------------- meta / health
def test_run_metadata_keys():
    meta = run_metadata(extra={"bench": "obs"})
    for key in ("jax_version", "backend", "device_count",
                "python_version", "platform", "git_sha", "timestamp"):
        assert key in meta, key
    assert meta["device_count"] >= 1
    assert meta["bench"] == "obs"
    assert meta["jax_version"] == jax.__version__


def test_write_json_stamps_meta(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "RESULTS_DIR", tmp_path)
    common.write_json("probe", [{"a": 1}])
    data = json.loads((tmp_path / "probe.json").read_text())
    assert data["rows"] == [{"a": 1}]
    assert "jax_version" in data["meta"]
    # summarize reads both formats
    from benchmarks import summarize
    monkeypatch.setattr(summarize, "RESULTS", tmp_path)
    assert summarize.bench_rows("probe") == [{"a": 1}]
    (tmp_path / "bare.json").write_text(json.dumps([{"b": 2}]))
    assert summarize.bench_rows("bare") == [{"b": 2}]
    assert summarize.bench_meta("probe") is not None
    assert summarize.bench_meta("bare") is None


@pytest.mark.parametrize("cfg", [
    {},                                          # dense rings + spill
    {"spill_slots": 0},                          # bare rings
    {"paged": True, "spill_slots": 0},           # paged slab
    {"adaptive_k": True},                        # adaptive-K + spill
])
def test_engine_health_gauges(cfg):
    eng = BohmEngine(R, _inc_workload(), ring_slots=2, **cfg)
    for s in range(4):
        eng.run_batch(_random_batch(s))
    snap = eng.begin_snapshot()
    eng.run_batch(_random_batch(9))
    h = eng.health()
    assert h["ts_counter"] == 5 * T
    assert h["watermark_lag"] >= 0
    assert h["active_pins"] == 1
    assert h["oldest_pin_ts"] == snap.ts
    assert h["oldest_pin_lag_ts"] == 5 * T - snap.ts
    assert h["oldest_pin_age_s"] >= 0.0
    assert h["live_versions"] > 0
    assert 0.0 <= h["ring_fill_p50"] <= h["ring_fill_max"] <= 1.0
    assert h["pressure_max"] >= 0.0
    assert len(h["k_eff_slots_by_shard"]) == 1
    if cfg.get("paged"):
        assert h["slab_fill_by_shard"][0] > 0.0
        assert h["pages_mapped_by_shard"][0] > 0
    if cfg.get("spill_slots") != 0:
        assert "spill_fill_by_shard" in h
    eng.release_snapshot(snap)
    assert eng.health()["active_pins"] == 0


def test_service_health_queue_depths():
    eng = BohmEngine(R, _inc_workload(), ring_slots=8)
    svc = TxnService(eng, max_inflight=2, admission_window=4)
    svc.submit(_random_batch(0))                 # held: window not full
    h = svc.health()
    assert h["admission_queue_depth"] == 1
    assert h["admission_window"] == 4
    svc.drain()
    h = svc.health()
    assert h["admission_queue_depth"] == 0
    assert h["inflight_epochs"] == 0
    assert h["unclaimed_results"] == 0
    assert h["admission_window_occupancy_max"] >= 1
