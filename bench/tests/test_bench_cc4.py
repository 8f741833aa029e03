"""The four-chip cell ``ycsb-1kb-cc4.z0.9-2rmw8r``: its tiny twin runs
correct through the harness on four virtual CPU devices (and its control
does not), and its two per-layer readers, ``collective_ms_per_epoch`` and
``shard_write_skew``, on a four-device trace and counters built by
hand."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.devtrace import Device, Span, Trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "ycsb-1kb-cc4.z0.9-2rmw8r"
MS = 1e-3

TINY = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
from bench import harness, loadgen
from bench.tests import tiny

harness.SAMPLE_EVERY = 1
for name, value in tiny.POOLS.items():
    setattr(loadgen, name, value)
cell = tiny.tiny_cell(sys.argv[2])
out = []
for control in (False, True):
    res = harness.run_cell(cell, 2**31 + 15, 1.0, False, time.perf_counter(),
                           require_tpu=False, compile_cache=False,
                           control=control)
    out.append({k: res[k] for k in ("correct", "failed", "attempted",
                                    "metrics", "checks", "device")})
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def tiny_runs():
    p = subprocess.run([sys.executable, "-c", TINY, str(ROOT), CELL],
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "compiles inside the window: 0" in p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_is_four_chips_of_the_published_store():
    cell = harness.load_cell(CELL)
    assert cell.config.chips == 4
    assert cell.config.records == 1_000_000
    assert cell.config.payload_words * 4 == 1000
    assert cell.config.reference == "ycsb_rmw"
    assert cell.mix == harness.load_cell("ycsb-1kb.z0.9-2rmw8r").mix
    assert {m["name"] for m in cell.per_layer} >= {
        "collective_ms_per_epoch", "shard_write_skew", "commit_ms_per_epoch",
        "commit_roofline", "device_idle_share"}


def test_tiny_cell_runs_correct_on_four_devices(tiny_runs):
    sound = tiny_runs[0]
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["device"]["count"] == 4
    assert set(sound["metrics"]) == {
        m["name"] for m in harness.load_cell(CELL).end_to_end}


def test_tiny_cell_control_is_not_correct(tiny_runs):
    control = tiny_runs[1]
    assert not control["correct"]
    assert control["checks"]["ticket_mismatch_reads"]["value"] > 0


# -- the readers on a trace built by hand ---------------------------------
def _trace():
    """A 10 ms window on four chips, two epochs (plan, exec, commit) each.
    Chip 0's collectives: an all-reduce's start and done halves (0.2 +
    0.3 ms), an all-gather (0.5 ms) overlapped by a nested event of the
    same op (counted once), a collective-permute (0.1 ms) and one outside
    the window; a fusion that only reads an all-gather's result is not a
    collective. Chips 1-3 each hold one 0.4 ms all-to-all."""
    mods = [Span(name, a * MS, b * MS) for a, b, name in [
        (0, 1, "jit_plan_phase"), (1, 3, "jit_exec_phase"),
        (3, 6, "jit_commit_phase"), (6, 7, "jit_plan_phase"),
        (7, 8, "jit_exec_phase"), (8, 9.5, "jit_commit_phase.1")]]
    ops0 = [Span(name, a * MS, b * MS) for a, b, name in [
        (0.2, 0.4, "%all-reduce-start.3 = s32[4]{0} all-reduce-start("
                   "s32[4]{0} %p)"),
        (0.4, 0.7, "%all-reduce-done.3 = s32[4]{0} all-reduce-done("
                   "s32[4]{0} %all-reduce-start.3)"),
        (3.0, 3.5, "%all-gather.7 = s32[40960]{0} all-gather(s32[10240]{0}"
                   " %w)"),
        (3.1, 3.3, "%all-gather.7"),
        (3.5, 4.0, "%fusion.9 = s32[40960]{0} fusion(s32[40960]{0} "
                   "%all-gather.7), kind=kLoop"),
        (8.0, 8.1, "%collective-permute.2 = s32[8]{0} "
                   "collective-permute(s32[8]{0} %x)"),
        (11.0, 12.0, "%all-reduce.9 = s32[] all-reduce(s32[] %y)")]]
    other = [Span("%all-to-all.4 = s32[4,8]{1,0} all-to-all(s32[4,8]{1,0}"
                  " %z)", 4 * MS, 4.4 * MS)]
    devices = [Device("/device:TPU:0", mods, ops0)] + [
        Device(f"/device:TPU:{c}", list(mods), list(other))
        for c in range(1, 4)]
    return Trace(devices, [Span("bench/window", 0, 10 * MS)])


def _view(trace=None, counters=None):
    return harness.LayerView(trace=trace, counters=counters or {},
                             commit_bytes=0.0, hbm_bytes_per_s=1.0, chips=4)


def test_collective_reader_on_four_chips():
    value = harness.load_reader("collective_ms_per_epoch")(_view(_trace()))
    # chip 0: 0.2 + 0.3 + 0.5 + 0.1 = 1.1 ms; chips 1-3: 0.4 ms each;
    # mean 0.575 ms over two epochs
    assert value == pytest.approx((1.1 + 3 * 0.4) / 4 / 2)


def test_collective_reader_without_an_epoch():
    tr = _trace()
    for d in tr.devices:
        d.modules = [m for m in d.modules if "commit" not in m.name]
    assert harness.load_reader("collective_ms_per_epoch")(_view(tr)) is None


@pytest.mark.parametrize("op,hit", [
    ("%all-reduce.1 = s32[] all-reduce(s32[] %a)", True),
    ("%all-reduce-start.2", True), ("%all-reduce-done.2", True),
    ("%all-gather-start.5 = (s32[8]{0}, s32[32]{0}) all-gather-start("
     "s32[8]{0} %b)", True),
    ("%reduce-scatter.1 = s32[8]{0} reduce-scatter(s32[32]{0} %c)", True),
    ("%all-to-all.3", True), ("%collective-permute-done.4", True),
    ("%ar = s32[4]{0} all-reduce(s32[4]{0} %d), replica_groups={}", True),
    ("%fusion.9 = s32[8]{0} fusion(s32[8]{0} %all-reduce.1)", False),
    ("%while.6 = (s32[]) while((s32[]) %t)", False),
    ("%copy.22 = s32[4]{0} copy(s32[4]{0} %e)", False),
    # as a TPU v5e trace of the cell prints them
    ("%all-gather.42 = s32[40960]{0:T(1024)} all-gather(s32[10240]"
     "{0:T(1024)S(1)} %copy-done.118), channel_id=29, "
     "replica_groups=[1,4]<=[4], dimensions={0}", True),
    ("%all-reduce.4 = (s32[1024,10]{1,0:T(8,128)S(1)}, s32[1024,10]"
     "{1,0:T(8,128)S(1)}) all-reduce(s32[1024,10]{1,0:T(8,128)S(1)} "
     "%add_any), channel_id=5", True),
    ("%fusion.8 = s32[40960]{0:T(1024)S(1)} fusion(s32[40960]"
     "{0:T(1024)S(1)} %all-gather.41, s32[40960]{0:T(1024)S(1)} "
     "%copy-done.67), kind=kCustom", False),
    ("%copy-start.85 = (s32[40960]{0:T(1024)S(1)}, s32[40960]{0:T(1024)}, "
     "u32[]{:S(2)}) copy-start(s32[40960]{0:T(1024)} %all-gather.42)",
     False)])
def test_collective_names(op, hit):
    path = ROOT / "bench" / "metrics" / "collective_ms_per_epoch.py"
    spec = importlib.util.spec_from_file_location("coll", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.is_collective(op) is hit


@pytest.mark.parametrize("writes,busiest,skew", [
    (4000, 1000, 1.0), (4000, 1100, 1.1), (2048, 2048, 4.0)])
def test_shard_write_skew(writes, busiest, skew):
    value = harness.load_reader("shard_write_skew")(_view(counters={
        "engine/shard_writes": writes, "engine/shard_writes_max": busiest}))
    assert value == pytest.approx(skew)


def test_shard_write_skew_without_the_counters():
    assert harness.load_reader("shard_write_skew")(_view()) is None
