"""Plain reference for YCSB-style read-modify-write transactions.

Semantics, as the configurations state them: a transaction reads every
record of its read set; for each written record (always one it read) it
writes back the value it read with word 0 increased by 1 (int32,
wrapping), every other word unchanged. Update batches are serializable
in submission order, transaction by transaction within a batch. A
snapshot pinned after the k-th submitted batch reads exactly the state
after those k batches.

Because every write adds 1 to word 0 of what it read, a record's value
after n committed writes is its initial value with n added to word 0.
The reference therefore keeps one write count per record instead of a
second copy of the store, and works out a value when it is asked for.
Transactions whose reads are compared are replayed one by one, in order;
the others only add to the counts, which is the same whatever their
order.

Nothing here imports the program. The initial records come from the
benchmark's own generator, not from the program's store.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def values(init: np.ndarray, counts: np.ndarray,
           records: np.ndarray) -> np.ndarray:
    """[..., D] value of ``records`` (>= 0) given per-record write counts."""
    out = init[records].copy()
    out[..., 0] = (out[..., 0].astype(np.int64)
                   + counts[records]).astype(np.int32)
    return out


class Replay:
    """The serial history, fed batch by batch in submission order."""

    def __init__(self, init: np.ndarray):
        self.init = init
        self.counts = np.zeros(init.shape[0], np.int64)
        self.batches = 0
        self._pins: Dict[int, np.ndarray] = {}

    def keep_pin(self, k: int) -> None:
        """Remember the state after ``k`` batches (call before batch k)."""
        if k != self.batches:
            raise ValueError("a pin is kept when the replay reaches it")
        self._pins[k] = self.counts.copy()

    def apply(self, write_set: np.ndarray) -> None:
        """A batch whose reads are not compared: only its writes count."""
        w = write_set[write_set >= 0]
        self.counts += np.bincount(w, minlength=self.counts.size)
        self.batches += 1

    def serial_reads(self, read_set: np.ndarray,
                     write_set: np.ndarray) -> np.ndarray:
        """Replay a batch transaction by transaction and return what each
        read returned: [T, ops, D], zero where the read set is padded."""
        T, ops = read_set.shape
        out = np.zeros((T, ops, self.init.shape[1]), np.int32)
        for t in range(T):
            valid = read_set[t] >= 0
            out[t, valid] = values(self.init, self.counts,
                                   read_set[t, valid])
            w = write_set[t][write_set[t] >= 0]
            self.counts[w] += 1
        self.batches += 1
        return out

    def control_reads(self, read_set: np.ndarray) -> np.ndarray:
        """The control: what the batch's reads return with serializability
        broken, every transaction reading the state the batch started
        from, as if the batch ran with no order among its transactions.
        Feeds nothing: replay the batch itself afterwards."""
        valid = read_set >= 0
        return np.where(valid[..., None],
                        values(self.init, self.counts,
                               np.maximum(read_set, 0)), 0).astype(np.int32)

    def snapshot_reads(self, k: int, read_set: np.ndarray) -> np.ndarray:
        """[T, ops, D] reads at the snapshot pinned after ``k`` batches."""
        valid = read_set >= 0
        return np.where(valid[..., None],
                        values(self.init, self._pins[k],
                               np.maximum(read_set, 0)), 0).astype(np.int32)

    def head(self) -> np.ndarray:
        """[R, D] state after every batch fed so far."""
        return values(self.init, self.counts, np.arange(self.init.shape[0]))
