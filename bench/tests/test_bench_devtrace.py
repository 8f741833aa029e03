"""The trace reader and the per-layer readers on a trace built by hand,
and the byte count of the commit's roofline share worked out by hand."""

import pytest

from bench import counting, devtrace, harness
from bench.devtrace import Device, Span, Trace

MS = 1e-3


def _trace(chips=1):
    """Two epochs in a 10 ms window on each chip:

    plan 0-1, exec 1-3, commit 3-6 | idle 6-7 | plan 7-7.5, exec 7.5-8,
    commit 8-9.5 | idle 9.5-10; one collective of 0.25 ms inside each
    commit on chip 0, and a resolve kernel run inside the second commit
    (the readers do not care where an op runs)."""
    mods, ops = [], []
    for a, b, name in [(0, 1, "jit_plan_phase(7)"), (1, 3, "jit_exec_phase"),
                       (3, 6, "jit_commit_phase"),
                       (7, 7.5, "jit_plan_phase"), (7.5, 8, "jit_exec_phase"),
                       (8, 9.5, "jit_commit_phase.1"),
                       (9.6, 9.7, "jit_plan_phase_sharded")]:
        mods.append(Span(name, a * MS, b * MS))
    for a, b, name in [
            (0, 1, "%fusion.1 = s32[8]{0} fusion()"), (1, 3, "%while.2"),
            (3, 5.75, "%fusion.3"), (5.75, 6, "%all-gather-start.1"),
            (7, 8, "%fusion.4"),
            (8, 9, "%_mvcc_resolve.5 = (s32[256,10240]) custom-call()"),
            (8.5, 9.5, "%fusion.6"),           # overlaps the kernel
            (9.6, 9.7, "%fusion.7")]:
        ops.append(Span(name, a * MS, b * MS))
    devices = [Device("/device:TPU:0", mods, ops)]
    for c in range(1, chips):
        devices.append(Device(f"/device:TPU:{c}", list(mods),
                              [o for o in ops if "all-" not in o.name]))
    host = [Span("bench/window", 0, 10 * MS),
            Span("bench/submit", 5.9 * MS, 7.2 * MS),
            Span("bench/wait", 9.4 * MS, 10 * MS),
            Span("bench/copy", 9.5 * MS, 9.9 * MS)]
    return Trace(devices, host)


def test_union_and_gaps():
    spans = [Span("a", 0, 2), Span("b", 1, 3), Span("c", 5, 6),
             Span("d", 5.5, 5.7), Span("e", 9, 12)]
    busy = devtrace.union(spans, 0, 10)
    assert busy == [(0, 3), (5, 6), (9, 10)]
    assert devtrace.covered(busy) == 5
    assert devtrace.gaps(busy, 0, 10) == [(3, 5), (6, 9)]
    assert devtrace.gaps([], 0, 4) == [(0, 4)]


@pytest.mark.parametrize("name,hit", [
    ("jit_plan_phase", True), ("jit_plan_phase(123)", True),
    ("jit_plan_phase.4", True), ("jit_plan_phase_sharded", False),
    ("jit_replan_phase", False), ("plan_phase", False)])
def test_module_names(name, hit):
    assert bool(devtrace.module_pattern("plan_phase").match(name)) is hit


def test_private_function_module_name():
    assert devtrace.module_pattern("_readonly_resolve").match(
        "jit__readonly_resolve(3)")


def test_idle_gaps_go_to_the_innermost_host_span():
    tr = _trace()
    lo, hi = tr.window()
    dev = tr.devices[0]
    idle = devtrace.gaps(devtrace.union(dev.ops, lo, hi), lo, hi)
    assert idle == pytest.approx([(6 * MS, 7 * MS), (9.5 * MS, 9.6 * MS),
                                  (9.7 * MS, 10 * MS)])
    by_span = devtrace.attribute_gaps(idle, tr.host)
    # 6-7 ms: inside submit; 9.5-9.6: copy (nested in wait, starts
    # later); 9.7-10: midpoint 9.85 is still inside copy
    assert by_span == pytest.approx({"bench/submit": 1 * MS,
                                     "bench/copy": 0.4 * MS})
    lone = devtrace.attribute_gaps([(20 * MS, 21 * MS)], tr.host)
    assert lone == pytest.approx({devtrace.NO_SPAN: 1 * MS})


def test_layer_readers_on_a_hand_trace():
    view = harness.LayerView(trace=_trace(), counters={
        "engine/waves": 160, "engine/commits": 2}, commit_bytes=1e6,
        hbm_bytes_per_s=1e12, chips=1)
    read = lambda m: harness.load_reader(m)(view)  # noqa: E731
    assert read("plan_ms_per_epoch") == pytest.approx(0.75)
    assert read("exec_ms_per_epoch") == pytest.approx(1.25)
    assert read("commit_ms_per_epoch") == pytest.approx(2.25)
    assert read("waves_per_epoch") == pytest.approx(80)
    # 1 MB at 1 TB/s is 1 us against 4.5 ms of commit
    assert read("commit_roofline") == pytest.approx(100 * 1e-6 / 4.5e-3)
    # busy 0-6, 7-9.5, 9.6-9.7 of 10 ms
    assert read("device_idle_share") == pytest.approx(14.0)
    assert read("readonly_ms_per_batch") is None     # no scan program
    assert read("resolve_roofline") is None


def test_layer_readers_average_over_chips():
    view = harness.LayerView(trace=_trace(chips=4), counters={},
                             commit_bytes=4e6, hbm_bytes_per_s=1e12,
                             chips=4)
    read = lambda m: harness.load_reader(m)(view)  # noqa: E731
    assert read("commit_ms_per_epoch") == pytest.approx(2.25)
    assert read("commit_roofline") == pytest.approx(100 * 1e-6 / 4.5e-3)
    assert read("waves_per_epoch") is None


def test_resolve_readers_on_a_hand_trace():
    """Two scan batches, 0.5 ms and 0.1 ms of ``_readonly_resolve``, in
    the idle gaps of the hand trace."""
    tr = _trace()
    tr.devices[0].modules.extend([
        Span("jit__readonly_resolve(3)", 6 * MS, 6.5 * MS),
        Span("jit__readonly_resolve.1", 9.5 * MS, 9.6 * MS)])
    view = harness.LayerView(trace=tr, counters={}, commit_bytes=1e6,
                             hbm_bytes_per_s=1e12, chips=1,
                             resolve_bytes_per_batch=3e5)
    read = lambda m: harness.load_reader(m)(view)  # noqa: E731
    assert read("readonly_ms_per_batch") == pytest.approx(0.3)
    # 2 x 0.3 MB at 1 TB/s is 0.6 us against 0.6 ms
    assert read("resolve_roofline") == pytest.approx(0.1)


def test_commit_bytes_by_hand():
    # one epoch: 3 versions written to 2 records, K = 4, D = 2
    # per record: header 2 x 4 x 4 B read and written (64 B) + head row
    # of 2 words and its timestamp (12 B) = 76 B
    # per version: 8 B payload read + (2 + 2) x 4 B written = 24 B
    assert counting.commit_bytes(3, 2, 4, 2) == 2 * 76 + 3 * 24


FUNCTIONS = {"plan_phase": ["batch", "ts_base"],
             "exec_phase": ["plan", "batch", "store"],
             "commit_phase": ["plan", "batch", "store", "w_data", "watermark",
                              "ts_window", "pin_ts"],
             "exec_commit_phase": ["plan", "batch", "store", "watermark",
                                   "pin_ts"],
             "_readonly_resolve": ["versions", "read_set", "ts"]}


@pytest.mark.parametrize("operands,fn", [
    ({"batch_read_set", "batch_write_set", "fusion"}, "plan_phase"),
    ({"batch_read_set", "plan_w_slot", "store_base", "while"}, "exec_phase"),
    ({"plan_w_rec", "store_versions_rings_payload", "w_data", "min"},
     "commit_phase"),
    ({"versions_rings_begin", "read_set", "_mvcc_resolve"},
     "_readonly_resolve"),
    ({"x", "y", "fusion"}, None),                       # no parameter read
])
def test_identify_by_parameter_names(operands, fn):
    assert devtrace.identify(operands, FUNCTIONS) == fn


def test_identify_refuses_a_tie():
    assert devtrace.identify({"batch_x"}, {"f": ["batch", "a"],
                                           "g": ["batch", "b"]}) is None


def test_unknown_programs_are_named_after_their_parameters():
    mods = [Span("jit__unknown(11)", 0, 1), Span("jit__unknown(22)", 1, 3),
            Span("jit_add(5)", 3, 4), Span("jit__unknown(11)", 4, 5)]
    ops = [Span("%copy.1 = s32[8]{0} copy(s32[8]{0} %batch_write_set.1)",
                0.1, 0.5),
           Span("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %plan_w_slot.3, "
                "s32[8,4]{1,0} %store_base.1)", 1.5, 2.5),
           Span("%add.1 = s32[] add(s32[] %x.1, s32[] %y.2)", 3.1, 3.2),
           Span("%sort.4 = s32[8]{0} sort(s32[8]{0} %fusion.1)", 4.2, 4.4)]
    devs = [Device("/device:TPU:0", mods, ops),
            Device("/device:TPU:1", list(mods), list(ops))]
    devtrace.name_programs(devs, FUNCTIONS)
    for d in devs:
        assert [m.name for m in d.modules] == [
            "jit_plan_phase(11)", "jit_exec_phase(22)", "jit_add(5)",
            "jit_plan_phase(11)"]
        assert [o.program for o in d.ops] == ["plan_phase", "exec_phase",
                                              "add", "plan_phase"]
    totals = devtrace.op_totals(devs[0], 0, 5)
    assert totals == pytest.approx({"plan_phase:copy.1": 0.4,
                                    "exec_phase:fusion.2": 1.0,
                                    "add:add.1": 0.1,
                                    "plan_phase:sort.4": 0.2})


def test_engine_phases_are_named_by_their_parameters():
    """The engine's own functions, as the harness reads them, name each
    phase's programs apart."""
    table = harness.program_functions()
    for fn in ("plan_phase", "exec_phase", "commit_phase",
               "_readonly_resolve"):
        assert devtrace.identify(table[fn], table) == fn


def test_resolve_bytes_by_hand():
    # 3 reads, K = 4, D = 2: per read a header of 4 begin + 4 end words
    # (32 B), the visible payload read (8 B), the payload and found
    # flag written (12 B)
    assert counting.resolve_bytes(3, 4, 2) == 3 * 52
    assert counting.resolve_bytes(0, 4, 250) == 0
