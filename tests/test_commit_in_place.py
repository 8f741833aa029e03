"""The commit writes the store in place.

``BohmEngine`` donates the store to its commit program (``jit_commit_phase``)
and every store update indexes its leaf in the stored shape: the head
and ``base_ts`` by record, the rings' headers by (record, slot), the spill
pool's by (bucket, slot), and the payload rows through
``repro.kernels.ops.update_rows`` (a Pallas kernel where the device keeps
records minor, XLA's scatter where payload words are minor, as here on
the CPU). The evidence is the compiled program: its store leaves alias
its outputs, its temporaries stay below one ring payload, and no copy, pad
or concatenate rebuilds a whole payload or the head.

Donation also means the commit's input store is gone after the call, so
the values the engine hands out are copies, and nothing about what the
commit computes changes: a donated engine and an undonated replay of the
same batches agree on every store leaf and every commit metric.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.execute import commit
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.kernels import row_update
from repro.obs import LifecycleAuditor

R, D, T = 16384, 250, 256
LAYOUTS = {
    "one_shard": {},
    "four_logical_shards": {"n_shards": 4},
}


def _batch(seed: int, t: int = T, r: int = R):
    return gen_ycsb_batch(np.random.default_rng(seed), t, r, theta=0.9,
                          mix="2rmw8r")


def _commit_program(eng: BohmEngine):
    """The engine's commit program, compiled at the shapes the service
    dispatches it with."""
    b = _batch(0)
    ts = jnp.asarray(1, jnp.int32)
    plan = eng._plan(b, ts)
    w_data, _, _ = eng._exec(plan, b, eng.store)
    window = (ts, jnp.asarray(1 + T, jnp.int32))
    return eng._commit.lower(plan, b, eng.store, w_data, ts, window,
                             eng.pin_array()).compile()


def _rebuilds(hlo: str, sizes) -> list:
    """Copies, pads and concatenates anywhere in the module whose result
    has one of ``sizes`` elements."""
    hits = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* (copy|pad|concatenate)\(",
                         hlo):
        n = int(np.prod([int(v) for v in m.group(1).split(",") if v]))
        if n in sizes:
            hits.append(m.group(0))
    return hits


def _check_in_place(eng: BohmEngine) -> None:
    compiled = _commit_program(eng)
    store = eng.store
    versions = store.versions
    def local(x):                 # what one device holds of a leaf
        return x.addressable_shards[0].data

    # every leaf the commit writes (``k_eff`` passes through unchanged)
    written = [local(x) for x in jax.tree.leaves(store)
               if x is not versions.k_eff]
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= sum(x.nbytes for x in written)
    payload = local(versions.rings.payload)
    assert ma.temp_size_in_bytes < payload.nbytes
    whole = {payload.size, local(versions.spill.payload).size,
             local(store.base).size}
    assert not _rebuilds(compiled.as_text(), whole)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_commit_program_updates_store_in_place(layout):
    eng = BohmEngine(R, make_ycsb(payload_words=D, ops=10), ring_slots=4,
                     **LAYOUTS[layout])
    _check_in_place(eng)


_MESH_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["TEST_DIR"])
from repro.core.engine import BohmEngine
from repro.core.workloads import make_ycsb
from repro.runtime import cc_mesh
import test_commit_in_place as t

# each device holds a quarter of the records: the same per-device store
# as the one-device tests
t._check_in_place(BohmEngine(4 * t.R, make_ycsb(payload_words=t.D, ops=10),
                             ring_slots=4, mesh=cc_mesh(4)))
print("MESH_IN_PLACE_OK")
"""


def test_commit_program_updates_store_in_place_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TEST_DIR=os.path.dirname(os.path.abspath(__file__)))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH_IN_PLACE_OK" in out.stdout, out.stderr[-3000:]


# -- donated commits compute what undonated ones do -------------------------
SMALL_R, SMALL_T = 256, 32
REPLAY = {
    "one_shard": {},
    "four_logical_shards": {"n_shards": 4},
    "paged": {"paged": True, "page_slots": 2},
}


def _leaves_equal(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), what)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["row_kernel", "xla_scatter"])
@pytest.mark.parametrize("audit", [False, True], ids=["plain", "audit"])
@pytest.mark.parametrize("layout", sorted(REPLAY))
def test_donated_commit_matches_undonated_replay(layout, audit, kernel,
                                                 monkeypatch):
    """One engine runs its batches through the donated commit program;
    each batch is replayed through the un-jitted ``commit`` on a store of
    its own, with the payload rows written by XLA's scatter. Pins held
    across batches make the rings overflow into the spill pool and the
    pool's pinned victims get overwritten. With ``row_kernel`` the engine
    writes its rows through the Pallas kernel (interpreted), as on a
    device that keeps records minor."""
    if kernel:
        monkeypatch.setattr(row_update, "stored_order",
                            lambda shape, dtype: (2, 1, 0))
    eng = BohmEngine(SMALL_R, make_ycsb(payload_words=8, ops=10),
                     ring_slots=2, spill_slots=2, spill_buckets=4,
                     auditor=LifecycleAuditor() if audit else None,
                     **REPLAY[layout])
    ref_store = jax.tree.map(jnp.copy, eng.store)
    totals = {"spill_admitted": 0, "spill_overwrote": 0}
    for step in range(6):
        if step in (1, 3):
            eng.begin_snapshot()
        b = _batch(step, SMALL_T, SMALL_R)
        wm = jnp.asarray(eng.watermark(), jnp.int32)
        pins = eng.pin_array()
        plan = eng._plan(b, eng.store.ts_counter)
        w_data, _, _ = eng._exec(plan, b, eng.store)
        w_ref, _, _ = eng._exec(plan, b, ref_store)
        eng.store, metrics = eng._commit(plan, b, eng.store, w_data, wm,
                                         None, pins)
        eng.claim_ts_window(b.size)
        with monkeypatch.context() as m:
            m.setattr(row_update, "stored_order",
                      lambda shape, dtype: (0, 1, 2))
            ref_store, ref_metrics = commit(plan, b, ref_store, w_ref, wm,
                                            pin_ts=pins, with_audit=audit)
        _leaves_equal(eng.store, ref_store, f"store after batch {step}")
        assert sorted(metrics) == sorted(ref_metrics)
        for k in metrics:
            np.testing.assert_array_equal(np.asarray(metrics[k]),
                                          np.asarray(ref_metrics[k]), k)
        for k in totals:
            totals[k] += int(metrics[k])
    # the replay covered spill admissions and overwritten spill victims
    assert totals["spill_admitted"] > 0 and totals["spill_overwrote"] > 0


# -- values handed out outlive later commits --------------------------------
def test_handed_out_values_outlive_later_commits():
    wl = make_ycsb(payload_words=8, ops=10)
    eng = BohmEngine(SMALL_R, wl, ring_slots=2)
    base = jnp.asarray(np.random.default_rng(3).integers(
        0, 1000, (SMALL_R, 8)), jnp.int32)
    base_host = np.asarray(base)
    eng.reset_store(base)
    eng.run_batch(_batch(1, SMALL_T, SMALL_R))
    snap = eng.snapshot()
    snap_host = np.asarray(snap)
    for s in range(2, 5):
        eng.run_batch(_batch(s, SMALL_T, SMALL_R))
    jax.block_until_ready(eng.store.base)
    # neither the caller's initial state nor the snapshot was donated
    np.testing.assert_array_equal(np.asarray(base), base_host)
    np.testing.assert_array_equal(np.asarray(snap), snap_host)
    assert not np.array_equal(np.asarray(eng.snapshot()), snap_host)
