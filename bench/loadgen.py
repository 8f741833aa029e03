"""The one traffic generator. A traffic mix is a JSON file under
``bench/traffic/`` whose parameters this module reads; nothing here is
specific to one mix.

Keys are drawn by inverse-CDF sampling over the zipf table (rank 1 is
record 0, as in the paper's YCSB generator), so drawing a batch costs a
``searchsorted`` per key instead of ``rng.choice(p=...)`` over the whole
table. Every transaction touches ``ops`` distinct records: a column that
repeats an earlier record of its row is drawn again from the same
distribution until none repeats.

A run draws a pool of ``POOL_BATCHES`` update batches (and, for a mix
with readers, ``SCAN_POOL_BATCHES`` scan batches) from ``--seed`` in
set-up and submits them in pool order, cycling, so the generator costs
nothing inside the measured window and every seed gives the same amount
of work per batch.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

# stream ids for np.random.default_rng([seed, stream])
UPDATE_STREAM, SCAN_STREAM, SAMPLE_STREAM = 1, 2, 3
POOL_BATCHES = 256          # distinct update batches drawn per run
SCAN_POOL_BATCHES = 64      # distinct scan batches drawn per run


@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic mix: a closed loop of update batches, optionally with a
    synchronous reader that scans a pinned snapshot after each submit."""
    name: str
    mix: str                  # "10rmw" | "2rmw8r"
    ops: int                  # records per transaction
    theta: float              # zipf skew of the update keys (0: uniform)
    outstanding: int          # update batches in flight (closed loop)
    scan_ops: int = 0         # records per scan transaction (0: no reader)
    scan_theta: float = 0.0
    pin_hold_s: float = 0.0   # the reader's snapshot is re-pinned this often

    @property
    def has_scans(self) -> bool:
        return self.scan_ops > 0


def load_mix(path: Path) -> Mix:
    data = json.loads(Path(path).read_text())
    fields = {f.name for f in dataclasses.fields(Mix)}
    unknown = set(data) - fields - {"why"}
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    return Mix(**{k: v for k, v in data.items() if k in fields})


def zipf_cdf(n: int, theta: float) -> Optional[np.ndarray]:
    """Cumulative zipf(theta) over ranks 1..n (None for uniform)."""
    if theta <= 0.0:
        return None
    w = np.power(np.arange(1, n + 1, dtype=np.float64), -theta)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def draw_records(rng: np.random.Generator, cdf: Optional[np.ndarray],
                 n: int, shape) -> np.ndarray:
    """Records drawn independently: uniform, or zipf by inverse CDF."""
    if cdf is None:
        return rng.integers(0, n, size=shape, dtype=np.int64)
    u = rng.random(size=shape)
    return np.minimum(np.searchsorted(cdf, u, side="right"), n - 1)


def distinct_rows(rng: np.random.Generator, cdf: Optional[np.ndarray],
                  n: int, n_txns: int, ops: int) -> np.ndarray:
    """[n_txns, ops] records, distinct within each row."""
    if ops > n:
        raise ValueError("a transaction cannot touch more records than exist")
    out = draw_records(rng, cdf, n, (n_txns, ops))
    for col in range(1, ops):
        while True:
            dup = (out[:, col:col + 1] == out[:, :col]).any(axis=1)
            if not dup.any():
                break
            out[dup, col] = draw_records(rng, cdf, n, int(dup.sum()))
    return out


@dataclasses.dataclass(frozen=True)
class HostBatch:
    """A batch as the client sends it: int32 host arrays, -1 padded."""
    read_set: np.ndarray      # [T, ops]
    write_set: np.ndarray     # [T, ops]

    @property
    def n_writes(self) -> int:
        return int((self.write_set >= 0).sum())

    @property
    def n_written_records(self) -> int:
        w = self.write_set[self.write_set >= 0]
        return int(np.unique(w).size)


def update_batch(rng: np.random.Generator, mix: Mix, cdf, n_records: int,
                 n_txns: int) -> HostBatch:
    recs = distinct_rows(rng, cdf, n_records, n_txns, mix.ops)
    if mix.mix == "10rmw":
        writes = recs.copy()
    elif mix.mix == "2rmw8r":
        writes = np.full_like(recs, -1)
        writes[:, :2] = recs[:, :2]
    else:
        raise ValueError(f"unknown update mix {mix.mix!r}")
    return HostBatch(recs.astype(np.int32), writes.astype(np.int32))


def scan_batch(rng: np.random.Generator, mix: Mix, cdf, n_records: int,
               n_txns: int) -> HostBatch:
    recs = distinct_rows(rng, cdf, n_records, n_txns, mix.scan_ops)
    return HostBatch(recs.astype(np.int32),
                     np.full_like(recs, -1, dtype=np.int32))


def update_pool(seed: int, mix: Mix, n_records: int,
                n_txns: int) -> List[HostBatch]:
    rng = np.random.default_rng([seed, UPDATE_STREAM])
    cdf = zipf_cdf(n_records, mix.theta)
    return [update_batch(rng, mix, cdf, n_records, n_txns)
            for _ in range(POOL_BATCHES)]


def scan_pool(seed: int, mix: Mix, n_records: int,
              n_txns: int) -> List[HostBatch]:
    if not mix.has_scans:
        return []
    rng = np.random.default_rng([seed, SCAN_STREAM])
    cdf = zipf_cdf(n_records, mix.scan_theta)
    return [scan_batch(rng, mix, cdf, n_records, n_txns)
            for _ in range(SCAN_POOL_BATCHES)]


def sample_mask(seed: int, stream: int, every: int,
                size: int = 1 << 16) -> np.ndarray:
    """[size] bool: which batches of ``stream`` (by submission index) are
    kept for the comparison, about one in ``every``, drawn from the seed
    in set-up so that the window only indexes it."""
    rng = np.random.default_rng([seed, SAMPLE_STREAM, stream])
    return rng.integers(0, every, size=size) == 0
