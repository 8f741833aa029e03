"""The harness end to end on the CPU, at a tiny size: a sound run is
correct, and each fault planted under the timed path makes it not
correct. Also: the command refuses a machine without a TPU, and
``BENCHMARK.json`` keeps to the benchmark's contract."""
import json
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import check, harness
from bench.tests.tiny import small_pools, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 99
CELLS = ["ycsb-1kb.z0.9-2rmw8r", "micro-8b.uniform-10rmw",
         "ycsb-1kb.z0.9-2rmw8r.pinned-scans"]     # the one-chip cells


def _run(cell, patch=None, monkeypatch=None, seconds=1.0, control=False):
    monkeypatch.setattr(harness, "SAMPLE_EVERY", 1)
    small_pools(monkeypatch)
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            require_tpu=False, patch=patch,
                            compile_cache=False, control=control)


# -- faults planted under the timed path -----------------------------------
def _commit_returns_state_unchanged(svc):
    eng = svc.engine
    orig = eng._commit

    def commit(plan, batch, store, *args, **kwargs):
        _, metrics = orig(plan, batch, store, *args, **kwargs)
        return store, metrics
    eng._commit = commit


def _answer_altered_where_produced(svc):
    eng = svc.engine
    orig = eng._exec

    def exec_(plan, batch, store):
        w, reads, metrics = orig(plan, batch, store)
        return w, reads.at[3, 1, 1].add(1), metrics
    eng._exec = exec_


def _half_the_batch_left_out(svc):
    import dataclasses
    eng = svc.engine
    orig = eng._plan

    def plan(batch, ts_base):
        t = batch.size // 2
        ws = batch.write_set.copy()
        ws[t:] = -1
        return orig(dataclasses.replace(batch, write_set=ws), ts_base)
    eng._plan = plan


def _snapshot_read_altered(svc):
    eng = svc.engine
    orig = eng._readonly

    def readonly(versions, read_set, ts):
        vals, found, metrics = orig(versions, read_set, ts)
        return vals.at[0, 0, 7].add(1), found, metrics
    eng._readonly = readonly


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    res = _run(tiny_cell(name), monkeypatch=monkeypatch)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   harness.load_cell(name).end_to_end}
    assert list(res)[-1] == "checks"
    for k, lim in check.LIMITS.items():
        assert res["checks"][k] == {"value": 0, "limit": lim}


@pytest.mark.parametrize("fault", [
    _commit_returns_state_unchanged, _answer_altered_where_produced,
    _half_the_batch_left_out])
@pytest.mark.parametrize("name", ["ycsb-1kb.z0.9-2rmw8r",
                                  "micro-8b.uniform-10rmw"])
def test_fault_makes_run_not_correct(name, fault, monkeypatch):
    res = _run(tiny_cell(name), patch=fault, monkeypatch=monkeypatch)
    assert not res["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_control_run_is_not_correct(name, monkeypatch):
    """The control's answers, through the same comparison as the
    program's, fail it: each cell's reads with serializability broken,
    and the snapshot reads of the cell with a reader at the pin one batch
    early."""
    res = _run(tiny_cell(name), monkeypatch=monkeypatch, control=True)
    assert not res["correct"]
    assert res["checks"]["ticket_mismatch_reads"]["value"] > 0
    if harness.load_cell(name).mix.has_scans:
        assert res["checks"]["snapshot_mismatch_reads"]["value"] > 0
    assert res["checks"]["head_mismatch_records"]["value"] == 0


def test_altered_snapshot_read_makes_run_not_correct(monkeypatch):
    res = _run(tiny_cell("ycsb-1kb.z0.9-2rmw8r.pinned-scans"),
               patch=_snapshot_read_altered, monkeypatch=monkeypatch)
    assert res["checks"]["snapshot_mismatch_reads"]["value"] > 0
    assert not res["correct"]


FOUR_CHIPS = r"""
import dataclasses, json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
import jax.numpy as jnp
from bench import harness, loadgen
from bench.tests import tiny
from repro.core import plan as plan_mod

harness.SAMPLE_EVERY = 1
for name, value in tiny.POOLS.items():
    setattr(loadgen, name, value)
# cell 1's configuration and traffic, at the tiny size, with its records
# partitioned over four chips
one = tiny.tiny_cell("ycsb-1kb.z0.9-2rmw8r")
cell = dataclasses.replace(
    one, name="ycsb-1kb-cc4.z0.9-2rmw8r",
    config=dataclasses.replace(one.config, name="ycsb-1kb-cc4", chips=4))


def run(patch=None):
    return harness.run_cell(cell, 2**31 + 3, 1.0, False, time.perf_counter(),
                            require_tpu=False, patch=patch,
                            compile_cache=False)["correct"]


def exchange_left_out(svc):
    # each chip's plan reaches the merge alone: the other shards' write
    # slots and read dependencies never arrive
    orig = plan_mod.merge_sharded_plan

    def merge(plan, batch):
        n = plan.w_rec.shape[0]

        def own(x):
            keep = (jnp.arange(n) == 0).reshape((n,) + (1,) * (x.ndim - 1))
            return jnp.where(keep, x, -1)
        return orig(dataclasses.replace(
            plan, w_slot=own(plan.w_slot), r_dep_slot=own(plan.r_dep_slot),
            r_dep_txn=own(plan.r_dep_txn)), batch)
    plan_mod.merge_sharded_plan = merge


print(json.dumps([run(), run(exchange_left_out)]))
"""


def test_four_chip_cell_and_the_exchange_left_out():
    """Cell 1's deployment partitioned over four chips (not yet a cell),
    on four virtual CPU devices, in a process of its own (this one keeps
    one device): sound, it is correct; with the exchange between chips
    left out of the CC plan, it is not."""
    p = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(ROOT)],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == [True, False]


# -- the command ---------------------------------------------------------
def test_command_refuses_a_machine_without_a_tpu():
    env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(ROOT)}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


# -- the contract --------------------------------------------------------
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keeps_to_the_contract():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"])
        assert c["file"].startswith("bench/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert {"source", "assumed", "guarantees"} <= set(data)
        assert len(data["source"]) <= 200
    used = set()
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        harness.load_cell(w["name"])
    assert used == set(configs)
    cells = {w["name"] for w in spec["workloads"]}
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert set(m.get("workloads", cells)) <= cells
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        # every cell the metric lists reports the end-to-end metric it
        # moves
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        assert sum(cell in m.get("workloads", cells)
                   for m in spec["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])
    assert len(json.dumps(spec)) < 64 * 1024
