"""Jitted public wrappers around the Pallas kernels.

On TPU the kernels lower natively; on any other backend they run in
``interpret=True`` mode (the kernel body executes in Python on the CPU),
which is what the per-kernel tests in tests/test_kernels.py check
against the jnp oracles in ref.py. The backend alone picks the mode
(``default_interpret``); tests/test_tpu_compile.py compiles the native
lowering for a described TPU v5e.
"""
from __future__ import annotations

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import \
    flash_attention_causal as _flash
from repro.kernels.mvcc_resolve import default_interpret as _interpret
from repro.kernels.mvcc_resolve import mvcc_resolve as _resolve
from repro.kernels.mvcc_resolve import \
    mvcc_resolve_masked as _resolve_masked
from repro.kernels.row_update import update_rows as _update_rows


def mvcc_resolve(begin, end, data, ts, **kw):
    # interpret mode is picked from the backend inside the kernel module
    return _resolve(begin, end, data, ts, **kw)


def mvcc_resolve_masked(begin, end, rec, want, data, ts, **kw):
    # the spill-pool fall-through: shared bucket windows filtered by
    # owner record id inside the visibility test
    return _resolve_masked(begin, end, rec, want, data, ts, **kw)


def update_rows(x, rec, slot, keep, rows, **kw):
    # the commit's in-place write of version rows into a record-minor
    # ring or spill payload (interpret mode picked inside the module)
    return _update_rows(x, rec, slot, keep, rows, **kw)


def decode_attention(q, k, v, kv_len, **kw):
    kw.setdefault("interpret", _interpret())
    return _decode(q, k, v, kv_len, **kw)


def flash_attention_causal(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _flash(q, k, v, **kw)


mvcc_resolve_ref = ref.mvcc_resolve_ref
mvcc_resolve_masked_ref = ref.mvcc_resolve_masked_ref
decode_attention_ref = ref.decode_attention_ref
flash_attention_causal_ref = ref.flash_attention_causal_ref
