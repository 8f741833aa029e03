"""The record-partitioned store on a four-device mesh, built where it
lives: after ``BohmEngine(mesh=cc_mesh(4))`` and after ``reset_store``
every version leaf is split over ``cc`` with one ``[1, R/4, ...]`` shard a
device and the head is replicated; the set-up program's output on each
device is one shard plus the head; the four-shard engine's head and
reads equal the one-shard engine's on the same stream, and its store the
four logical shards' on one device; the partition's write counters equal
a count of the stream's owned writes; and the four-device plan names its
shard and merge stages on the trace.

Everything runs in one subprocess with four virtual CPU devices (this
process keeps one); the tests read what it printed."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import contextlib, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["TEST_DIR"])
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

setup_programs = []
_jit = jax.jit


def spy(fn, *args, **kw):
    compiled = _jit(fn, *args, **kw)
    if "out_shardings" in kw:
        setup_programs.append(compiled)
    return compiled


jax.jit = spy
from repro.core.engine import BohmEngine
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.runtime import cc_mesh
from repro.service import TxnService
import test_program_scopes as scopes

R, D, T, N = 1024, 8, 64, 6
wl = make_ycsb(payload_words=D, ops=10)
mesh = cc_mesh(4)
out = {}


def layout(store):
    leaves = {}
    for path, x in jax.tree_util.tree_leaves_with_path(store.versions):
        leaves[jax.tree_util.keystr(path)] = {
            "spec": str(x.sharding.spec), "shape": list(x.shape),
            "shards": sorted({tuple(s.data.shape)
                              for s in x.addressable_shards}),
            "devices": len({s.device for s in x.addressable_shards})}
    head = {name: {"replicated": getattr(store, name).sharding
                   .is_fully_replicated,
                   "devices": len(getattr(store, name).sharding.device_set)}
            for name in ("base", "base_ts", "ts_counter")}
    return {"leaves": leaves, "head": head}


eng = BohmEngine(R, wl, mesh=mesh)
out["init"] = layout(eng.store)
rng = np.random.default_rng(7)
base = rng.integers(-2**31, 2**31, (R, D), dtype=np.int64).astype(np.int32)
eng.reset_store(jnp.asarray(base))
out["reset"] = layout(eng.store)

# the compiled set-up program: bytes each device holds of its outputs
versions = sum(x.nbytes for x in jax.tree.leaves(eng.store.versions))
head = sum(getattr(eng.store, n).nbytes
           for n in ("base", "base_ts", "ts_counter"))
args = jax.device_put((jnp.asarray(base), None), NamedSharding(mesh, P()))
mem = setup_programs[-1].lower(*args).compile().memory_analysis()
out["setup_memory"] = None if mem is None else {
    "output": mem.output_size_in_bytes, "one_shard": versions // 4,
    "head": head, "whole_versions": versions}

# one stream through the served path, on four shards over the mesh, four
# logical shards on one device, and one shard
batches = [gen_ycsb_batch(rng, T, R, theta=0.9, mix="2rmw8r")
           for _ in range(N)]
engines = {"mesh": BohmEngine(R, wl, mesh=mesh),
           "logical": BohmEngine(R, wl, n_shards=4),
           "one": BohmEngine(R, wl)}
reads, heads = {}, {}
for name, e in engines.items():
    e.reset_store(jnp.asarray(base))
    svc = TxnService(e, admission_window=4, pipelined=True)
    tickets = [svc.submit(b) for b in batches]
    reads[name] = [np.asarray(svc.wait(t).read_vals) for t in tickets]
    svc.drain()
    heads[name] = np.asarray(e.snapshot())
out["equal"] = {
    "head": bool(np.array_equal(heads["mesh"], heads["one"])),
    "reads": all(np.array_equal(a, b)
                 for a, b in zip(reads["mesh"], reads["one"])),
    "logical_shards_store": all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(engines["mesh"].store.versions),
                        jax.tree.leaves(engines["logical"].store.versions))),
}

# the partition's counters, one batch an epoch, against a numpy count
counted = {}
for name, kw in (("four", {"mesh": mesh}), ("one", {})):
    e = BohmEngine(R, wl, **kw)
    e.reset_store(jnp.asarray(base))
    for b in batches:
        e.run_batch(b)
    snap = e.metrics.snapshot()
    counted[name] = [int(snap["engine/shard_writes"]),
                     int(snap["engine/shard_writes_max"])]
owned = [np.bincount(b.write_set[b.write_set >= 0] % 4, minlength=4)
         for b in batches]
out["counters"] = {"engine": counted,
                   "numpy": [int(sum(o.sum() for o in owned)),
                             int(sum(o.max() for o in owned))]}

# the four-device plan's stage names, and that they change only metadata
plan = scopes._hlo(scopes._engine(mesh=mesh), "plan_phase")
names = scopes._op_names(plan)
out["plan_scopes"] = {s: scopes._has_scope(names, s)
                      for s in ("plan/shard", "plan/merge")}
jax.clear_caches()
jax.named_scope = lambda name: contextlib.nullcontext()
out["plan_scopes_only_metadata"] = (
    scopes.strip_metadata(plan)
    == scopes.strip_metadata(scopes._hlo(scopes._engine(mesh=mesh),
                                         "plan_phase")))
print(json.dumps(out))
"""

STAGES = ("init", "reset")


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TEST_DIR=os.path.dirname(os.path.abspath(__file__)))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("stage", STAGES)
def test_version_leaves_split_over_cc(four, stage):
    leaves = four[stage]["leaves"]
    assert {"rings", "spill", "k_eff"} <= {k.split(".")[1].split("[")[0]
                                           for k in leaves}
    for name, leaf in leaves.items():
        assert leaf["spec"] == "PartitionSpec('cc',)", name
        assert leaf["shape"][0] == 4 and leaf["devices"] == 4, name
        assert leaf["shards"] == [[1] + leaf["shape"][1:]], name
        # R/4 records a shard; the spill pool has a bucket per 4 of them
        per_shard = 1024 // 4 // (4 if name.startswith(".spill") else 1)
        assert leaf["shape"][1] == per_shard, name


@pytest.mark.parametrize("stage", STAGES)
def test_head_replicated(four, stage):
    for name, head in four[stage]["head"].items():
        assert head == {"replicated": True, "devices": 4}, name


def test_setup_program_holds_one_shard_per_device(four):
    mem = four["setup_memory"]
    if mem is None:
        pytest.skip("memory_analysis() is not available on this backend")
    want = mem["one_shard"] + mem["head"]
    # the output tuple's own index table may add a few words
    assert want <= mem["output"] <= want + 1024
    assert mem["output"] < mem["whole_versions"]


@pytest.mark.parametrize("what", ["head", "reads", "logical_shards_store"])
def test_four_shards_match(four, what):
    assert four["equal"][what]


@pytest.mark.parametrize("engine", ["four", "one"])
def test_shard_write_counters(four, engine):
    total, busiest = four["counters"]["numpy"]
    got = four["counters"]["engine"][engine]
    assert got == ([total, busiest] if engine == "four" else [total, total])


@pytest.mark.parametrize("scope", ["plan/shard", "plan/merge"])
def test_plan_scopes_on_four_devices(four, scope):
    assert four["plan_scopes"][scope]


def test_plan_scopes_change_only_metadata(four):
    assert four["plan_scopes_only_metadata"]
