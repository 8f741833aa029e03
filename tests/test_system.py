"""End-to-end behaviour of the full system: the Bohm engine under a mixed
workload stream, model-layer <-> kernel consistency, and the public API
surface used by the examples."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, reduced_config
from repro.core.engine import BohmEngine, serial_oracle
from repro.core.execute import init_store
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.kernels import ops


def test_engine_sustained_stream():
    """20 batches of mixed contention stay serializable and GC-stable."""
    wl = make_ycsb()
    R = 4096
    eng = BohmEngine(R, wl)
    rng = np.random.default_rng(0)
    base = init_store(R, wl.payload_words).base
    for i in range(20):
        theta = 0.0 if i % 2 == 0 else 0.95
        mix = "10rmw" if i % 3 == 0 else "2rmw8r"
        batch = gen_ycsb_batch(rng, 128, R, theta=theta, mix=mix)
        reads, metrics = eng.run_batch(batch)
        base, sr = serial_oracle(base, batch, wl)
        np.testing.assert_array_equal(np.asarray(eng.snapshot()),
                                      np.asarray(base))
        assert int(metrics["waves"]) >= 1
    # timestamps advanced monotonically across batches
    assert int(eng.store.ts_counter) == 1 + 20 * 128


def test_model_decode_consistent_with_kernel():
    """The model's dense decode attention agrees with the Pallas decode
    kernel on the same cache contents."""
    from repro.models.layers import attention_decode
    rng = np.random.default_rng(0)
    b, kvh, g, dh, t = 2, 2, 3, 32, 64
    h = kvh * g
    q = jnp.asarray(rng.standard_normal((b, 1, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), jnp.float32)
    kl = jnp.array([40, 64], jnp.int32)
    dense = attention_decode(q, k, v, kl)
    kern = ops.decode_attention(q.reshape(b, 1, kvh, g, dh)[:, 0],
                                k, v, kl, block_t=32)
    np.testing.assert_allclose(
        np.asarray(dense.reshape(b, kvh, g, dh)), np.asarray(kern),
        rtol=1e-5, atol=1e-5)


def test_registry_covers_all_assigned_archs():
    assert len(ALL_ARCHS) == 10
    for name in ALL_ARCHS:
        cfg = get_config(name)
        red = reduced_config(name)
        assert red.family == cfg.family
        assert red.num_layers <= 2 and red.d_model <= 64


def test_long_500k_skip_policy():
    from repro.launch.specs import cell_supported
    runs = [a for a in ALL_ARCHS
            if cell_supported(get_config(a), "long_500k")[0]]
    assert sorted(runs) == ["hymba-1.5b", "mamba2-370m"]


def test_pipelined_batch_stream():
    """run_stream (paper §4.1.4: CC of b+1 overlaps exec of b) produces
    the same state as synchronous per-batch execution."""
    from repro.configs.bohm_workloads import YCSB_HIGH_2RMW8R, build
    import dataclasses
    cfg = dataclasses.replace(YCSB_HIGH_2RMW8R, num_records=2048,
                              batch_size=128)
    eng1, gen1 = build(cfg, seed=5)
    eng2, gen2 = build(cfg, seed=5)
    batches = [gen1() for _ in range(4)]
    m = eng1.run_stream(iter(batches))
    for b in batches:
        eng2.run_batch(b)
    np.testing.assert_array_equal(np.asarray(eng1.snapshot()),
                                  np.asarray(eng2.snapshot()))
    assert int(m["waves"]) >= 1


def test_paper_workload_configs():
    from repro.configs.bohm_workloads import ALL_WORKLOADS, build
    import dataclasses
    assert len(ALL_WORKLOADS) == 7
    for name, wcfg in ALL_WORKLOADS.items():
        small = dataclasses.replace(wcfg, num_records=256, batch_size=32)
        eng, gen = build(small, seed=1)
        _, metrics = eng.run_batch(gen())
        assert int(metrics["waves"]) >= 1, name


def test_sequence_parallel_constraint():
    from repro.parallel.constraints import activation_mesh, \
        constrain_residual
    from repro.runtime import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.ones((4, 8, 16))
    with activation_mesh(mesh, sequence_parallel=True):
        y = jax.jit(constrain_residual)(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
