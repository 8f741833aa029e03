"""Kernel micro-benchmarks: Pallas vs jnp oracle.

The Pallas execution mode is auto-selected from ``jax.default_backend()``
(interpret everywhere but TPU) and can be forced either way with
``run(interpret=...)`` — the choice and the backend are recorded per row.
Interpret-mode wall-clock measures the Python kernel body (NOT TPU
performance) — the purpose is a correctness + plumbing check in the
benchmark harness; TPU-side roofline expectations live in EXPERIMENTS.md.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn, write_csv
from repro.kernels import ops
from repro.kernels.mvcc_resolve import default_interpret
from repro.runtime import setup_compile_cache

INF = np.iinfo(np.int32).max


def run(interpret: Optional[bool] = None) -> list:
    rng = np.random.default_rng(0)
    rows = []
    backend = jax.default_backend()
    interp = default_interpret() if interpret is None else interpret

    b, k, d = 4096, 8, 64
    begin = np.sort(rng.integers(0, 100, (b, k)).astype(np.int32), axis=1)
    end = np.concatenate([begin[:, 1:], np.full((b, 1), INF, np.int32)],
                         axis=1)
    data = rng.integers(0, 100, (b, k, d)).astype(np.int32)
    ts = rng.integers(0, 120, b).astype(np.int32)
    a = [jnp.asarray(x) for x in (begin, end, data, ts)]
    t_ref = time_fn(ops.mvcc_resolve_ref, *a)
    t_pal = time_fn(ops.mvcc_resolve, *a, interpret=interp)
    v1, f1 = ops.mvcc_resolve(*a, interpret=interp)
    v2, f2 = ops.mvcc_resolve_ref(*a)
    ok = bool((np.asarray(v1) == np.asarray(v2)).all())
    rows.append({"kernel": "mvcc_resolve", "shape": f"b{b}_k{k}_d{d}",
                 "backend": backend, "interpret": interp,
                 "ref_us": round(t_ref * 1e6), "pallas_us":
                 round(t_pal * 1e6), "allclose": ok})

    b, kvh, g, dh, t = 8, 4, 4, 128, 2048
    q = jnp.asarray(rng.standard_normal((b, kvh, g, dh)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((b, t, kvh, dh)), jnp.float32)
    kl = jnp.asarray(rng.integers(1, t, b), jnp.int32)
    t_ref = time_fn(ops.decode_attention_ref, q, kk, vv, kl)
    t_pal = time_fn(ops.decode_attention, q, kk, vv, kl, interpret=interp)
    o1 = ops.decode_attention(q, kk, vv, kl, interpret=interp)
    o2 = ops.decode_attention_ref(q, kk, vv, kl)
    ok = bool(np.allclose(np.asarray(o1), np.asarray(o2), atol=1e-4))
    rows.append({"kernel": "decode_attention",
                 "shape": f"b{b}_kv{kvh}_g{g}_dh{dh}_t{t}",
                 "backend": backend, "interpret": interp,
                 "ref_us": round(t_ref * 1e6),
                 "pallas_us": round(t_pal * 1e6), "allclose": ok})
    write_csv("kernels", rows)
    return rows


if __name__ == "__main__":
    setup_compile_cache()
    run()
