"""The bytes a step has to move, worked out from its shapes.

These count the work, not the implementation: the least traffic to HBM
that any store with this layout needs for the step, so that a roofline
share stays honest after a PR changes how the step is done. All words
are 4-byte int32.
"""
from __future__ import annotations

WORD = 4


def commit_bytes(n_writes: int, n_records: int, ring_slots: int,
                 payload_words: int) -> int:
    """Least bytes one epoch's commit moves.

    ``n_writes`` versions are written by the epoch's transactions, to
    ``n_records`` distinct records. Per written record: its ring header
    (``ring_slots`` begin and end timestamps) read and written back, and
    its head row (payload plus commit timestamp) written. Per version:
    its payload read from the execution's output and written into the
    ring with its begin and end timestamps.
    """
    header = 2 * ring_slots * WORD
    per_record = 2 * header + (payload_words + 1) * WORD
    per_version = payload_words * WORD + (payload_words + 2) * WORD
    return n_records * per_record + n_writes * per_version



def resolve_bytes(n_reads: int, ring_slots: int, payload_words: int) -> int:
    """Least bytes a batch of ``n_reads`` snapshot reads moves.

    Per read: its record's ring header (``ring_slots`` begin and end
    timestamps) read, to find the version visible at the snapshot; that
    version's payload read; the payload and its found flag written. The
    spill level and the other versions' payloads are not counted: a read
    whose version is in the ring needs neither.
    """
    header = 2 * ring_slots * WORD
    return n_reads * (header + 2 * payload_words * WORD + WORD)
