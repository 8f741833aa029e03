"""Serving-engine benchmark: continuous batching throughput with and
without prefix sharing (the Bohm MVCC read-annotation path)."""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import write_csv
from repro.configs import get_config
from repro.models import init_params
from repro.runtime import setup_compile_cache
from repro.serving.engine import ServeEngine


def _cfg():
    return dataclasses.replace(
        get_config("smollm-360m"), name="smollm-nano",
        num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
        head_dim=32, d_ff=512, vocab_size=2048)


def _run_once(share_prefix: bool, n_requests: int = 12):
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, slots=6, page_size=16, num_pages=256,
                      max_pages_per_seq=32)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 2000, 32).astype(np.int32)
    for rid in range(n_requests):
        if share_prefix:
            prompt = shared
        else:
            prompt = rng.integers(1, 2000, 32).astype(np.int32)
        eng.submit(rid, prompt, max_new_tokens=12)
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    return {
        "mode": "shared_prefix" if share_prefix else "unique_prompts",
        "requests": n_requests, "tokens": toks,
        "wall_s": round(dt, 3), "tok_s": round(toks / dt, 1),
        "prefix_hits": eng.sched.stats["prefix_hits"],
        "pages_recycled": eng.sched.stats["pages_recycled"],
    }


def run() -> list:
    rows = [_run_once(False), _run_once(True)]
    write_csv("serving", rows)
    return rows


if __name__ == "__main__":
    setup_compile_cache()
    run()
