"""Pallas TPU kernel: write version rows into a record-minor store in place.

The version store's payloads are ``[R, K, D]`` arrays (``K`` ring slots or
spill slots per record or bucket, ``D`` payload words). On a TPU v5e their
default layout puts the RECORD axis minor (``{1,2,3,0:T(4,128)}`` for the
stacked ``[n, R, 4, D]`` ring payload): tiles of ``K x 128`` records, one
plane per payload word, no padding. XLA's scatter and gather lower only
on a ``[R * K, D]`` view with ``D`` minor, so a commit that writes rows
with ``.at[rec, slot].set`` makes the compiler relayout the whole array
into that view and back, twice each way, on every epoch — whatever the
program's own indexing, and with the store donated or not.

This kernel works in the stored layout instead. Its view of ``x`` is the
transpose ``[D, K, R]``, which is that layout read row-major (a bitcast,
no copy), and it visits only the 128-record blocks an update lands in:

    grid step i      block ``[D, K, 128]`` of records
                     ``[128 * gid[i], 128 * gid[i] + 128)``, in VMEM
    update u in i    slot k, lane l: the block's column (k, l) gets the
                     update's row; its old row is kept first when asked

The updates arrive grouped by block (scalar-prefetched block ids, group
offsets and packed ``k * 128 + l`` targets); their rows arrive lane-major
as ``[D, 1, N]`` and are moved to lane ``l`` by a lane rotation, so one
update costs a few vector ops per payload word and no transpose. Rows and
old rows stream through one 128-update VMEM window each. The block is
read and written once per grid step (``x`` is aliased to the output), so
an epoch's traffic is its touched blocks, not the store.

Interpret mode is chosen by the backend alone (``default_interpret``), as
for the resolve kernels: native Mosaic on a TPU, the Pallas interpreter on
the CPU substrate.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mvcc_resolve import default_interpret

LANES = 128
_NO_BLOCK = jnp.iinfo(jnp.int32).max


def _kernel(gid_ref, gstart_ref, ngrp_ref, target_ref,      # scalar prefetch
            x_in, rows_hbm, x_out, old_hbm,                 # operands
            row_win, old_win, win, sem, *, slot_axis: int, with_old: bool):
    i = pl.program_id(0)
    shape = x_out.shape                      # [D, K, BL] or [K, D, BL]
    BL = shape[2]

    @pl.when(i == 0)
    def _():
        win[0] = -1

    # a block's first step copies it through; steps past the last group
    # repeat the last block id, so nothing is fetched or written again
    @pl.when((i == 0) | (gid_ref[i] != gid_ref[jnp.maximum(i - 1, 0)]))
    def _():
        x_out[...] = x_in[...]

    def window_dma(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def lanes_of(w):
        return pl.ds(pl.multiple_of(w * LANES, LANES), LANES)

    @pl.when(i < ngrp_ref[0])
    def _():
        kk = jax.lax.broadcasted_iota(jnp.int32, shape, slot_axis)
        ll = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        wl = jax.lax.broadcasted_iota(jnp.int32, row_win.shape, 2)

        def update(u, carry):
            w = u // LANES
            c = u - w * LANES

            @pl.when(w != win[0])
            def _():
                if with_old:
                    @pl.when(win[0] >= 0)
                    def _():
                        window_dma(old_win,
                                   old_hbm.at[:, :, lanes_of(win[0])])
                window_dma(rows_hbm.at[:, :, lanes_of(w)], row_win)
                win[0] = w

            t = target_ref[u]
            k = t // LANES
            lane = t - k * LANES
            blk = x_out[...]
            if with_old:
                # the old row: slot k's plane, lane ``lane`` rotated to c
                old = jnp.sum(jnp.where(kk == k, blk, 0), axis=slot_axis,
                              keepdims=True)
                if BL < LANES:
                    old = jnp.pad(old, ((0, 0), (0, 0), (0, LANES - BL)))
                old_win[...] = jnp.where(
                    wl == c, pltpu.roll(old, (c - lane) % LANES, 2),
                    old_win[...])
            row = pltpu.roll(row_win[...], (lane - c) % LANES, 2)
            row = jnp.broadcast_to(row, shape[:2] + (LANES,))[:, :, :BL]
            x_out[...] = jnp.where((kk == k) & (ll == lane), row, blk)
            return carry

        jax.lax.fori_loop(gstart_ref[i], gstart_ref[i + 1], update, 0)

    if with_old:
        @pl.when((i == pl.num_programs(0) - 1) & (win[0] >= 0))
        def _():
            window_dma(old_win, old_hbm.at[:, :, lanes_of(win[0])])


@functools.lru_cache(maxsize=None)
def stored_order(shape: Tuple[int, ...], dtype) -> Tuple[int, ...]:
    """Major-to-minor order of the default device layout of an array of
    ``shape`` on the default backend (asked of the compiler once per
    shape)."""
    spec = jax.ShapeDtypeStruct(shape, dtype)
    fmt = jax.jit(lambda a: a).lower(spec).compile().input_formats[0][0]
    return tuple(fmt.layout.major_to_minor)


def _record_minor_view(shape: Tuple[int, ...],
                       dtype) -> Optional[Tuple[int, ...]]:
    """The transpose of an ``[R, K, D]`` array that reads its default
    layout row-major, when that layout keeps the record axis minor:
    ``[D, K, R]`` (slots on sublanes, the v5e's order for wide payloads)
    or ``[K, D, R]`` (payload words on sublanes, its order for narrow
    ones). None for a layout with the payload words minor (the CPU's
    row-major one), where XLA's scatter already writes rows in place."""
    order = stored_order(shape, dtype)
    return order if order in ((2, 1, 0), (1, 2, 0)) else None


def update_rows(x: jax.Array, rec: jax.Array, slot: jax.Array,
                keep: jax.Array, rows: jax.Array, *, with_old: bool = True
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``x.at[rec, slot].set(rows)`` over the entries where ``keep``, in
    place, and (``with_old``) the rows those entries overwrite.

    ``x`` [R, K, D]; ``rec``, ``slot``, ``keep`` [N]; ``rows`` [N, D]. The
    kept (rec, slot) targets must be distinct (one version per slot), as
    every commit's are. Returns ``(x', old)`` with ``old`` [N, D] the
    previous row of each kept entry and 0 elsewhere (None without
    ``with_old``). Donate ``x`` to the caller's jit to write it in place.
    """
    R, K, D = x.shape
    N = rec.shape[0]
    perm = _record_minor_view(tuple(x.shape), x.dtype)
    if perm is None:
        # rows are contiguous in this layout: XLA scatters them in place
        # (dropped entries carry their slot's old row, which orders the
        # gather before the write)
        put = jnp.where(keep, rec, R)
        if not with_old:
            return x.at[put, slot].set(rows, mode="drop"), None
        old = x[jnp.where(keep, rec, R - 1), jnp.where(keep, slot, K - 1)]
        new_x = x.at[put, slot].set(jnp.where(keep[:, None], rows, old),
                                    mode="drop")
        return new_x, jnp.where(keep[:, None], old, 0)
    slot_axis = 1 if perm == (2, 1, 0) else 0
    row_dims = (D, 1) if slot_axis == 1 else (1, D)
    BL = min(LANES, R)
    n_steps = min(N, -(-R // BL))
    n_pad = -(-N // LANES) * LANES
    # kept entries first, grouped by 128-record block (stable: in order)
    block = jnp.where(keep, rec // BL, _NO_BLOCK)
    order = jnp.argsort(block, stable=True)
    block_s = block[order]
    n_keep = jnp.sum(keep).astype(jnp.int32)
    idx = jnp.arange(N, dtype=jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), block_s[:-1]])
    first = (block_s != prev) & (idx < n_keep)
    n_grp = jnp.sum(first).astype(jnp.int32)
    start = jnp.nonzero(first, size=n_steps, fill_value=N)[0]
    start = start.astype(jnp.int32)
    real = idx[:n_steps] < n_grp
    gid = block_s[jnp.minimum(start, N - 1)]
    gid = jnp.where(real, gid, gid[jnp.maximum(n_grp - 1, 0)])
    gid = jnp.where(n_grp > 0, gid, 0)
    gstart = jnp.concatenate([jnp.where(real, start, n_keep), n_keep[None]])
    target = slot[order] * LANES + rec[order] % BL
    target = jnp.pad(target, (0, n_pad - N))
    rows_t = jnp.pad(rows[order], ((0, n_pad - N), (0, 0))).T
    rows_t = rows_t.reshape(row_dims + (n_pad,))

    view = tuple(x.shape[a] for a in perm)
    blk = pl.BlockSpec(view[:2] + (BL,), lambda i, gid, *_: (0, 0, gid[i]))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    xv, old_t = pl.pallas_call(
        functools.partial(_kernel, slot_axis=slot_axis, with_old=with_old),
        out_shape=(jax.ShapeDtypeStruct(view, x.dtype),
                   jax.ShapeDtypeStruct(row_dims + (n_pad,), x.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n_steps,),
            in_specs=[blk, hbm], out_specs=(blk, hbm),
            scratch_shapes=[pltpu.VMEM(row_dims + (LANES,), x.dtype),
                            pltpu.VMEM(row_dims + (LANES,), x.dtype),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=default_interpret(),
    )(gid, gstart, n_grp[None], target, jnp.transpose(x, perm), rows_t)
    new_x = jnp.transpose(xv, tuple(perm.index(a) for a in range(3)))
    if not with_old:
        return new_x, None
    old = jnp.zeros((N, D), x.dtype).at[order].set(
        old_t.reshape(D, n_pad)[:, :N].T)
    return new_x, jnp.where(keep[:, None], old, 0)
