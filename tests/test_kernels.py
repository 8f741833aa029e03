"""Per-kernel allclose sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

INF = np.iinfo(np.int32).max


def _version_store(rng, b, k, d, dtype):
    begin = np.sort(rng.integers(0, 100, (b, k)).astype(np.int32), axis=1)
    end = np.concatenate([begin[:, 1:], np.full((b, 1), INF, np.int32)],
                         axis=1)
    data = rng.integers(-1000, 1000, (b, k, d)).astype(dtype)
    return begin, end, data


@pytest.mark.parametrize("b,k,d", [(7, 4, 3), (64, 8, 16), (300, 16, 250),
                                   (1, 1, 1), (129, 2, 129)])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_mvcc_resolve_shapes(b, k, d, dtype):
    rng = np.random.default_rng(b * 1000 + k)
    begin, end, data = _version_store(rng, b, k, d, dtype)
    ts = rng.integers(0, 120, b).astype(np.int32)
    v1, f1 = ops.mvcc_resolve(jnp.asarray(begin), jnp.asarray(end),
                              jnp.asarray(data), jnp.asarray(ts),
                              block_b=64, block_d=64)
    v2, f2 = ops.mvcc_resolve_ref(jnp.asarray(begin), jnp.asarray(end),
                                  jnp.asarray(data), jnp.asarray(ts))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))


def test_mvcc_resolve_semantics():
    """Hand-built chain: version visible iff begin <= ts < end."""
    begin = jnp.array([[1, 5, 9]], jnp.int32)
    end = jnp.array([[5, 9, INF]], jnp.int32)
    data = jnp.arange(3, dtype=jnp.int32).reshape(1, 3, 1) + 10
    for ts, want, found in [(0, 0, False), (1, 10, True), (4, 10, True),
                            (5, 11, True), (8, 11, True), (9, 12, True),
                            (100, 12, True)]:
        v, f = ops.mvcc_resolve(begin, end, data,
                                jnp.array([ts], jnp.int32))
        assert bool(f[0]) == found, ts
        if found:
            assert int(v[0, 0]) == want, ts


@pytest.mark.parametrize("b,kvh,g,dh,t", [
    (1, 1, 1, 64, 64), (3, 2, 4, 64, 257), (2, 5, 3, 128, 1024),
    (4, 8, 1, 128, 96),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_shapes(b, kvh, g, dh, t, dtype):
    rng = np.random.default_rng(b * 37 + t)
    q = jnp.asarray(rng.standard_normal((b, kvh, g, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), dtype)
    kl = jnp.asarray(rng.integers(1, t + 1, b), jnp.int32)
    o1 = ops.decode_attention(q, k, v, kl, block_t=128)
    o2 = ops.decode_attention_ref(q, k, v, kl)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_masking():
    """Tokens beyond kv_len must not influence the output."""
    rng = np.random.default_rng(0)
    b, kvh, g, dh, t = 2, 2, 2, 32, 128
    q = jnp.asarray(rng.standard_normal((b, kvh, g, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), jnp.float32)
    kl = jnp.array([40, 90], jnp.int32)
    o1 = ops.decode_attention(q, k, v, kl, block_t=64)
    # poison the masked region — output must be identical
    k2 = k.at[0, 40:].set(1e9).at[1, 90:].set(1e9)
    v2 = v.at[0, 40:].set(-1e9).at[1, 90:].set(-1e9)
    o2 = ops.decode_attention(q, k2, v2, kl, block_t=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_mvcc_resolve_against_engine_plan():
    """Kernel-resolved reads agree with the CC-phase plan resolution for a
    base-only store (no in-batch writers): begin=base_ts, end=INF."""
    from repro.core.plan import cc_plan
    from repro.core.txn import make_batch
    rng = np.random.default_rng(1)
    R, T = 32, 16
    base_ts = rng.integers(0, 5, R).astype(np.int32)
    base_val = rng.integers(0, 100, (R, 2)).astype(np.int32)
    reads = rng.integers(0, R, (T, 2))
    batch = make_batch(reads, np.full((T, 2), -1), np.zeros(T),
                       np.zeros((T, 1)))
    plan = cc_plan(batch, jnp.int32(10))
    assert int((plan.r_dep_slot >= 0).sum()) == 0   # no in-batch writers
    begin = jnp.asarray(base_ts[reads.reshape(-1)]).reshape(-1, 1)
    end = jnp.full_like(begin, INF)
    data = jnp.asarray(base_val[reads.reshape(-1)])[:, None, :]
    ts = jnp.full((T * 2,), 10, jnp.int32)
    vals, found = ops.mvcc_resolve(begin, end, data, ts)
    assert bool(found.all())
    np.testing.assert_array_equal(
        np.asarray(vals).reshape(T, 2, 2), base_val[reads])


@pytest.mark.parametrize("b,s,kvh,g,dh,bq,bk", [
    (1, 128, 1, 1, 32, 64, 64), (2, 256, 2, 3, 64, 64, 128),
    (1, 512, 4, 2, 128, 256, 256), (2, 128, 2, 1, 64, 128, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, s, kvh, g, dh, bq, bk, dtype):
    rng = np.random.default_rng(s + b)
    q = jnp.asarray(rng.standard_normal((b, s, kvh, g, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, dh)), dtype)
    o1 = ops.flash_attention_causal(q, k, v, block_q=bq, block_k=bk)
    o2 = ops.flash_attention_causal_ref(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


def test_flash_kernel_matches_model_path():
    """Pallas kernel == the model's blockwise jnp attention."""
    from repro.models.layers import flash_attention
    rng = np.random.default_rng(3)
    b, s, kvh, g, dh = 2, 256, 2, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, kvh * g, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, dh)), jnp.float32)
    o_model = flash_attention(q, k, v, causal=True, chunk=64)
    o_kern = ops.flash_attention_causal(
        q.reshape(b, s, kvh, g, dh), k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o_model),
                               np.asarray(o_kern.reshape(b, s, -1, dh)),
                               rtol=1e-4, atol=1e-4)


# -- in-place version-row writes (the commit's ring and spill payloads) ----
def _row_updates(rng, r, k, d, n):
    """Targets distinct among the kept entries, as a commit's are; the
    dropped ones point anywhere."""
    flat = rng.choice(r * k, min(n, r * k), replace=False)
    flat = np.resize(flat, n)
    keep = (rng.random(n) < 0.7) & (np.arange(n) < r * k)
    rec = np.where(keep, flat // k, rng.integers(0, r, n))
    return (jnp.asarray(rec, jnp.int32), jnp.asarray(flat % k, jnp.int32),
            jnp.asarray(keep),
            jnp.asarray(rng.integers(-2**31, 2**31 - 1, (n, d)), jnp.int32))


# the kernel's two record-minor views, and the row-major layout on which
# XLA's own scatter writes in place (the CPU's)
ROW_VIEWS = {"slots_on_sublanes": (2, 1, 0), "words_on_sublanes": (1, 2, 0),
             "row_major": (0, 1, 2)}


@pytest.mark.parametrize("view", sorted(ROW_VIEWS))
@pytest.mark.parametrize("r,k,d,n", [(1000, 4, 5, 300), (512, 8, 3, 200),
                                     (300, 4, 250, 64), (256, 4, 2, 600),
                                     (64, 4, 8, 40)])
@pytest.mark.parametrize("with_old", [True, False])
def test_update_rows(view, r, k, d, n, with_old, monkeypatch):
    from repro.kernels import row_update
    monkeypatch.setattr(row_update, "stored_order",
                        lambda shape, dtype: ROW_VIEWS[view])
    rng = np.random.default_rng(r + k + d + n)
    x = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (r, k, d)), jnp.int32)
    rec, slot, keep, rows = _row_updates(rng, r, k, d, n)
    want, want_old = ref.update_rows_ref(x, rec, slot, keep, rows)
    got, old = jax.jit(lambda x: ops.update_rows(
        x, rec, slot, keep, rows, with_old=with_old), donate_argnums=0)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if with_old:
        np.testing.assert_array_equal(np.asarray(old), np.asarray(want_old))
    else:
        assert old is None


@pytest.mark.parametrize("view", sorted(ROW_VIEWS))
def test_update_rows_per_shard(view, monkeypatch):
    """vmapped over stacked shards, as the sharded commit maps it."""
    from repro.kernels import row_update
    monkeypatch.setattr(row_update, "stored_order",
                        lambda shape, dtype: ROW_VIEWS[view])
    rng = np.random.default_rng(7)
    n_sh, r, k, d, n = 3, 130, 4, 3, 100
    x = jnp.asarray(rng.integers(0, 1000, (n_sh, r, k, d)), jnp.int32)
    upd = [_row_updates(rng, r, k, d, n) for _ in range(n_sh)]
    rec, slot, keep, rows = (jnp.stack(a) for a in zip(*upd))
    got, old = jax.vmap(ops.update_rows)(x, rec, slot, keep, rows)
    want, want_old = jax.vmap(ref.update_rows_ref)(x, rec, slot, keep, rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(old), np.asarray(want_old))
