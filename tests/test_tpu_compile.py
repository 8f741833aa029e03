"""Compile the snapshot-read path and the commit for a described TPU v5e,
no chip needed.

Interpret mode (every other test file) cannot see what Mosaic refuses:
unaligned blocks, unsupported boolean reshapes, more fast memory than a
kernel may use. These tests lower the resolve kernels natively
(``interpret=False``) against one chip of a described ``v5e:2x2``
topology and check that a Mosaic kernel (``tpu_custom_call``) is in the
compiled program: the two kernels at the read counts the engine sends,
and the engine's whole read-only resolve step over a 1,000,000-record
store — dense ring + spill, and paged slab + spill. The commit program
is checked for what the chip's layouts make of it: the row kernel in
it, and no relayout of a whole ring or spill payload.

The topology is described inside a module fixture (never at import):
only one process may hold the TPU library, so the test workers must all
collect the same tests and only the worker running this file loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import mvcc_resolve as kernels
from repro.store import init_sharded_store, resolve_sharded

R = 1_000_000       # the paper's §5 table (YCSB_HIGH_2RMW8R)
D = 8               # int32 payload words


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


# reads per call: one, a 1024-txn x 10-op scan, and a count that is not
# a multiple of the 1024-read block
@pytest.mark.parametrize("b,k,d", [(1, 4, D), (10240, 4, D),
                                   (12345, 8, 2)])
def test_resolve_compiles_for_v5e(one_chip, b, k, d):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                            sharding=one_chip)
    fn = jax.jit(lambda be, en, da, ts: kernels.mvcc_resolve(
        be, en, da, ts, interpret=False))
    _assert_mosaic(fn.lower(s(b, k), s(b, k), s(b, k, d), s(b)))


@pytest.mark.parametrize("b,k,d", [(1, 8, D), (10240, 8, D),
                                   (12345, 8, 2)])
def test_resolve_masked_compiles_for_v5e(one_chip, b, k, d):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                            sharding=one_chip)
    fn = jax.jit(lambda be, en, re, wa, da, ts: kernels.mvcc_resolve_masked(
        be, en, re, wa, da, ts, interpret=False))
    _assert_mosaic(fn.lower(s(b, k), s(b, k), s(b, k), s(b), s(b, k, d),
                            s(b)))


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_store_resolve_compiles_for_v5e(one_chip, monkeypatch, paged):
    """The engine's read path at 1M records — per-read window gather
    (through the page table when paged), ``mvcc_resolve``, then the
    spill fall-through through ``mvcc_resolve_masked``."""
    # the engine leaves interpret mode to the backend, which here is
    # the CPU: steer it to the chip's native lowering for this compile
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    # the engine's default store: K = 4 ring slots, R/4 spill buckets of
    # 8 slots, one page of 4 slots per record when paged (shapes only)
    store = jax.eval_shape(lambda: init_sharded_store(
        jnp.zeros((R, D), jnp.int32), num_slots=4, spill_buckets=R // 4,
        spill_slots=8, paged=paged))
    reads = jax.ShapeDtypeStruct((10240,), jnp.int32, sharding=one_chip)
    _assert_mosaic(jax.jit(resolve_sharded).lower(
        _spec(store, one_chip), reads, reads))


# the commit's store shapes: the engine's default ring (K = 4) and a spill
# pool of 8-slot buckets at the 1 KB record of ``ycsb-1kb`` and the 8-byte
# record of ``micro-8b``, with 2^16 records (the layouts, and so the
# program's ops, are those of the full-size stores). R/8 buckets, not the
# engine's R/4: at 8 bytes an R/4 pool's payload has as many elements as
# the ring's [R, 4] headers, whose small relayouts remain.
@pytest.mark.parametrize("d", [250, 2])
def test_commit_writes_store_in_place_on_v5e(one_chip, monkeypatch, d):
    """The engine's commit program, compiled for the chip: the store is
    donated and aliased to the outputs, the payload rows go through the
    row kernel in the stored record-minor layout, and no copy, relayout,
    pad or concatenate rebuilds a whole ring or spill payload."""
    from repro.core.engine import commit_phase, exec_phase, plan_phase
    from repro.core.execute import init_store
    from repro.core.workloads import gen_ycsb_batch, make_ycsb
    from repro.kernels import row_update
    from repro.runtime import jit_named

    def stored_order(shape, dtype):
        spec = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        fmt = jax.jit(lambda a: a).lower(spec).compile().input_formats
        return tuple(fmt[0][0].layout.major_to_minor)

    monkeypatch.setattr(row_update, "default_interpret", lambda: False)
    monkeypatch.setattr(row_update, "stored_order", stored_order)
    r, t = 1 << 16, 1024
    store = jax.eval_shape(lambda: init_store(
        r, d, ring_slots=4, spill_buckets=r // 8, spill_slots=8, k_init=4))
    batch = gen_ycsb_batch(np.random.default_rng(0), t, r, theta=0.9,
                           mix="2rmw8r")
    plan = jax.eval_shape(lambda b: plan_phase(b, jnp.int32(1), mesh=None,
                                               cc_axis="cc"), batch)
    w_data = jax.eval_shape(
        lambda p, b, s: exec_phase(p, b, s,
                                   workload=make_ycsb(payload_words=d,
                                                      ops=10)),
        plan, batch, store)[0]
    ts = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    pins = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    commit = jit_named(commit_phase, donate_argnums=(2,), mesh=None,
                       cc_axis="cc")
    compiled = commit.lower(
        _spec(plan, one_chip), _spec(batch, one_chip),
        _spec(store, one_chip), _spec(w_data, one_chip), ts, (ts, ts),
        pins).compile()
    text = compiled.as_text()
    assert text.splitlines()[0].startswith("HloModule jit_commit_phase,")
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(store)
        if x is not store.versions.k_eff)
    payloads = [store.versions.rings.payload, store.versions.spill.payload]
    sizes = {int(np.prod(x.shape)) for x in payloads}
    # a header leaf can have a payload's element count (the 8-byte record)
    others = {tuple(x.shape) for x in jax.tree.leaves(store)
              if all(x is not p for p in payloads)}
    # no relayout (``copy``, ``transpose``, ``reshape``), ``pad`` or
    # ``concatenate`` of a whole payload; the in-place passes are fusions
    # and the kernels custom calls, and ``copy-start``/``copy-done`` pairs
    # move an array between memories without changing its layout
    entry = text[text.index("\nENTRY"):]
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", entry):
        dims = tuple(int(v) for v in m.group(1).split(",") if v)
        if int(np.prod(dims)) in sizes and dims not in others:
            assert m.group(2) in (
                "bitcast", "parameter", "fusion", "get-tuple-element",
                "custom-call", "copy-start", "copy-done"), m.group(0)
