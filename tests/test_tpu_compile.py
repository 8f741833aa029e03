"""Compile the snapshot-read path for a described TPU v5e, no chip needed.

Interpret mode (every other test file) cannot see what Mosaic refuses:
unaligned blocks, unsupported boolean reshapes, more fast memory than a
kernel may use. These tests lower the resolve kernels natively
(``interpret=False``) against one chip of a described ``v5e:2x2``
topology and check that a Mosaic kernel (``tpu_custom_call``) is in the
compiled program: the two kernels at the read counts the engine sends,
and the engine's whole read-only resolve step over a 1,000,000-record
store — dense ring + spill, and paged slab + spill.

The topology is described inside a module fixture (never at import):
only one process may hold the TPU library, so the test workers must all
collect the same tests and only the worker running this file loads it.
"""
import os

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
