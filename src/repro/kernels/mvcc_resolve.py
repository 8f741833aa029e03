"""Pallas TPU kernel: MVCC version-visibility resolution + payload select.

This is the paper's §4.1.3 read path ("find the version with
t_begin <= ts and ts < t_end") adapted to the TPU memory hierarchy: the
linked-list prev-pointer traversal becomes a K-wide interval test over a
per-record version window held in VMEM, fused with the payload select so
the kernel reads each version window once. The wrapper's pad and
transpose to the lane-major layout below run in XLA before the kernel;
unless XLA fuses them into the caller's gather they add one more pass
over the windows. They carry the ``resolve/layout`` scope in the
program's op metadata and the kernel ``resolve/kernel`` (the callers'
window gathers ``resolve/gather``), so a device trace times each.

Callers pre-gather the candidate windows per read (XLA's gather is the
efficient primitive for the HBM-resident [R, K] rings, and the paged
slab's page-table walk is the same gather — ``gather_windows_paged``):

    begin [B, K] i32   version begin timestamps (garbage slots: INT32_MAX)
    end   [B, K] i32   version end timestamps   (open versions: INT32_MAX)
    data  [B, K, D]    payloads
    ts    [B]    i32   reader timestamps

Returns (vals [B, D], found [B] bool).

Kernel layout: READS RUN ALONG LANES. The wrapper transposes the windows
to begin/end [K, B], data [K, D, B] and ts [1, B], so every block is
lane-dense (B tiles in multiples of 128 lanes, the K slots and D payload
words sit on sublanes). The visibility mask is a max over the K sublane
rows, and the payload select is a static loop over K that adds one
[D, Bb] tile per slot — no boolean reshapes, no 1-D blocks, nothing that
leaves most of each vreg's 128 lanes empty when K and D are small (the
paper's records are 2-8 int32 words). ``found`` leaves the kernel as an
int32 [1, B] row and becomes bool in the wrapper. The kernel is
memory-bound by design; its roofline is the window traffic.

``mvcc_resolve_masked`` is the second level of the hierarchical read
path (primary ring -> spill pool, see repro/store/spill.py): spill
buckets are SHARED across records, so each candidate slot carries an
owner record id and the visibility test gains a ``rec == want`` term —
fused into the same interval test rather than materialising a masked
copy of the window. Both kernels share one body and one tiling scheme.

Interpret mode is chosen by the backend alone (``default_interpret``):
native Mosaic lowering on a TPU, the Pallas interpreter elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = jnp.iinfo(jnp.int32).min
LANES = 128


def default_interpret() -> bool:
    """Pallas lowers these kernels natively only on TPU; every other
    backend (the CPU substrate, notably) runs the kernel body in
    interpret mode."""
    return jax.default_backend() != "tpu"


def _select(vis, begin, data_ref, out_ref, found_ref):
    """Shared body: the newest visible version's payload (zeros and
    found=0 when none is visible). ``vis``/``begin`` are [K, Bb]."""
    score = jnp.where(vis, begin, NEG_INF)
    best = jnp.max(score, axis=0, keepdims=True)            # [1, Bb]
    k, bb = score.shape
    dd = out_ref.shape[0]
    best_d = jnp.broadcast_to(best, (dd, bb))
    hit = best_d > NEG_INF
    acc = jnp.zeros((dd, bb), out_ref.dtype)
    for s in range(k):                    # exactly one slot in a
        #                                   consistent store
        sel = hit & (jnp.broadcast_to(score[s:s + 1], (dd, bb)) == best_d)
        acc = acc + jnp.where(sel, data_ref[s], 0)
    out_ref[...] = acc
    # identical at every D tile, so every D step may (re)write it
    found_ref[...] = (best > NEG_INF).astype(jnp.int32)


def _resolve_kernel(ts_ref, begin_ref, end_ref, data_ref, out_ref,
                    found_ref):
    ts = ts_ref[...]                                        # [1, Bb]
    begin = begin_ref[...]                                  # [K, Bb]
    vis = (begin <= ts) & (ts < end_ref[...])
    _select(vis, begin, data_ref, out_ref, found_ref)


def _resolve_masked_kernel(ts_ref, want_ref, begin_ref, end_ref, rec_ref,
                           data_ref, out_ref, found_ref):
    ts = ts_ref[...]                                        # [1, Bb]
    begin = begin_ref[...]                                  # [K, Bb]
    vis = ((begin <= ts) & (ts < end_ref[...])
           & (rec_ref[...] == want_ref[...]))
    _select(vis, begin, data_ref, out_ref, found_ref)


def _tiles(b: int, d: int, block_b: int, block_d: int):
    """(Bb, Bp, Dd, Dp): lane tile over reads (a multiple of 128) and the
    padded read count; sublane tile over payload words (all of D when it
    fits one tile, else a multiple of 8) and the padded width."""
    bp = -(-max(b, 1) // LANES) * LANES
    bb = min(-(-block_b // LANES) * LANES, bp)
    bp = -(-bp // bb) * bb
    dd = d if d <= block_d else -(-block_d // 8) * 8
    dp = -(-d // dd) * dd
    return bb, bp, dd, dp


def _resolve_call(kernel, per_read, windows, data, *, block_b, block_d,
                  interpret):
    """Transpose to the lane-dense layout, pad, run ``kernel`` over the
    (B, D) grid, transpose back. ``per_read`` are [B] vectors (ts first),
    ``windows`` [B, K] arrays; pads are dropped from the result."""
    b, k = windows[0].shape
    d = data.shape[-1]
    bb, bp, dd, dp = _tiles(b, d, block_b, block_d)
    pad_b = bp - b
    # the relayout on both sides of the kernel is ``resolve/layout`` in
    # the program's op metadata, the kernel ``resolve/kernel``
    with jax.named_scope("resolve/layout"):
        rows = [jnp.pad(x, (0, pad_b))[None, :]
                for x in per_read]                                # [1, Bp]
        wins = [jnp.pad(w, ((0, pad_b), (0, 0))).T
                for w in windows]                                 # [K, Bp]
        data_t = jnp.pad(data, ((0, pad_b), (0, 0), (0, dp - d))
                         ).transpose(1, 2, 0)                     # [K,Dp,Bp]
    with jax.named_scope("resolve/kernel"):
        vals, found = pl.pallas_call(
            kernel,
            grid=(bp // bb, dp // dd),
            in_specs=[pl.BlockSpec((1, bb), lambda i, j: (0, i))]
            * len(rows)
            + [pl.BlockSpec((k, bb), lambda i, j: (0, i))] * len(wins)
            + [pl.BlockSpec((k, dd, bb), lambda i, j: (0, j, i))],
            out_specs=[pl.BlockSpec((dd, bb), lambda i, j: (j, i)),
                       pl.BlockSpec((1, bb), lambda i, j: (0, i))],
            out_shape=[jax.ShapeDtypeStruct((dp, bp), data.dtype),
                       jax.ShapeDtypeStruct((1, bp), jnp.int32)],
            interpret=interpret,
        )(*rows, *wins, data_t)
    with jax.named_scope("resolve/layout"):
        return vals[:d, :b].T, found[0, :b] != 0


@functools.partial(jax.jit, static_argnames=("block_b", "block_d",
                                             "interpret"))
def _mvcc_resolve(begin, end, data, ts, *, block_b, block_d, interpret):
    return _resolve_call(_resolve_kernel, [ts], [begin, end], data,
                         block_b=block_b, block_d=block_d,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "block_d",
                                             "interpret"))
def _mvcc_resolve_masked(begin, end, rec, want, data, ts, *, block_b,
                         block_d, interpret):
    # pad reads carry want = 0 against rec = 0 windows, but their
    # begin = end = 0 windows are never visible, so pads find nothing
    return _resolve_call(_resolve_masked_kernel, [ts, want],
                         [begin, end, rec], data, block_b=block_b,
                         block_d=block_d, interpret=interpret)


def mvcc_resolve(begin: jax.Array, end: jax.Array, data: jax.Array,
                 ts: jax.Array, *, block_b: int = 1024, block_d: int = 128,
                 interpret: Optional[bool] = None):
    """Resolve B reads over their [B, K] candidate windows. ``interpret``
    None (the engine never passes it) follows ``default_interpret``; it
    is decided here, outside the jit, so it is part of the cache key."""
    if interpret is None:
        interpret = default_interpret()
    return _mvcc_resolve(begin, end, data, ts, block_b=block_b,
                         block_d=block_d, interpret=bool(interpret))


def mvcc_resolve_masked(begin: jax.Array, end: jax.Array, rec: jax.Array,
                        want: jax.Array, data: jax.Array, ts: jax.Array,
                        *, block_b: int = 1024, block_d: int = 128,
                        interpret: Optional[bool] = None):
    """Visibility resolution over SHARED candidate windows: slot (i, k) is
    considered for read i only when ``rec[i, k] == want[i]`` (the spill
    pool's bucket layout — several records share one bucket)."""
    if interpret is None:
        interpret = default_interpret()
    return _mvcc_resolve_masked(begin, end, rec, want, data, ts,
                                block_b=block_b, block_d=block_d,
                                interpret=bool(interpret))
