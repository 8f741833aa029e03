"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = jnp.iinfo(jnp.int32).min


def mvcc_resolve_ref(begin: jax.Array, end: jax.Array, data: jax.Array,
                     ts: jax.Array):
    """Visibility: the version with max begin among {begin <= ts < end}."""
    vis = (begin <= ts[:, None]) & (ts[:, None] < end)        # [B, K]
    score = jnp.where(vis, begin, NEG_INF)
    best = jnp.max(score, axis=1)
    found = best > NEG_INF
    idx = jnp.argmax(score, axis=1)
    vals = jnp.take_along_axis(
        data, idx[:, None, None].repeat(data.shape[-1], -1), axis=1)[:, 0]
    vals = jnp.where(found[:, None], vals, 0)
    return vals, found


def mvcc_resolve_masked_ref(begin: jax.Array, end: jax.Array,
                            rec: jax.Array, want: jax.Array,
                            data: jax.Array, ts: jax.Array):
    """Masked variant over shared (spill-bucket) windows: slot (i, k) is
    a candidate for read i only when rec[i, k] == want[i]."""
    vis = (begin <= ts[:, None]) & (ts[:, None] < end) \
        & (rec == want[:, None])
    score = jnp.where(vis, begin, NEG_INF)
    best = jnp.max(score, axis=1)
    found = best > NEG_INF
    idx = jnp.argmax(score, axis=1)
    vals = jnp.take_along_axis(
        data, idx[:, None, None].repeat(data.shape[-1], -1), axis=1)[:, 0]
    vals = jnp.where(found[:, None], vals, 0)
    return vals, found


def mvcc_resolve_paged_ref(page_rows: jax.Array, begin: jax.Array,
                           end: jax.Array, data: jax.Array,
                           ts: jax.Array):
    """Paged variant: read i's candidate window is the union of its
    mapped pages' slots — page_rows [B, MaxP] indexes the slab
    begin/end [P, S] / data [P, S, D]; -1 = unmapped (no candidates)."""
    b, maxp = page_rows.shape
    s = begin.shape[-1]
    safe = jnp.maximum(page_rows, 0)
    mapped = (page_rows >= 0)[..., None]                      # [B, MaxP, 1]
    w_begin = jnp.where(mapped, begin[safe], jnp.iinfo(jnp.int32).max)
    w_end = jnp.where(mapped, end[safe], jnp.iinfo(jnp.int32).max)
    w_data = jnp.where(mapped[..., None], data[safe], 0)
    return mvcc_resolve_ref(w_begin.reshape(b, maxp * s),
                            w_end.reshape(b, maxp * s),
                            w_data.reshape(b, maxp * s, -1), ts)


def update_rows_ref(x: jax.Array, rec: jax.Array, slot: jax.Array,
                    keep: jax.Array, rows: jax.Array):
    """``x`` [R, K, D] with row (rec, slot) set to ``rows`` where ``keep``,
    and the rows it held before (0 where not kept)."""
    r, k = jnp.where(keep, rec, 0), jnp.where(keep, slot, 0)
    old = jnp.where(keep[:, None], x[r, k], 0)
    put = jnp.where(keep, rec, x.shape[0])
    return x.at[put, slot].set(rows, mode="drop"), old


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: jax.Array) -> jax.Array:
    """q [B,KvH,G,Dh]; k,v [B,T,KvH,Dh]; kv_len [B] or scalar."""
    b, kvh, g, dh = q.shape
    t = k.shape[1]
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = kv_len[None].repeat(b)
    s = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32) * dh ** -0.5,
                   k.astype(jnp.float32))
    mask = jnp.arange(t)[None, :] < kv_len[:, None]           # [B, T]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_causal_ref(q: jax.Array, k: jax.Array,
                               v: jax.Array) -> jax.Array:
    """q [B,S,KvH,G,Dh]; k,v [B,S,KvH,Dh] — full-softmax causal oracle."""
    b, s, kvh, g, dh = q.shape
    sc = jnp.einsum("bqkgd,btkd->bkgqt", q.astype(jnp.float32) * dh ** -0.5,
                    k.astype(jnp.float32))
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
