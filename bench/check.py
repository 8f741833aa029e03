"""The comparison that decides ``correct``.

After the window the harness hands over what the timed path produced:
the head store, the read values of the update batches drawn for the
comparison, and the reads of the scan batches drawn for it (with their
``found`` flags). The plain reference replays the whole submitted
history from the initial records, and each number below counts the
answers that differ from it. Every limit is 0: the comparison is exact.

With ``control`` set, the control's answers stand in for the program's:
the reference with one stated guarantee broken (``Replay.control_reads``
in ``bench/reference``: a batch's transactions all read the state it
started from; and each snapshot read at the pin one batch early), put
through the same comparison. A control run has to come out not correct.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

LIMITS = {
    "head_mismatch_records": 0,
    "ticket_mismatch_reads": 0,
    "snapshot_mismatch_reads": 0,
}


@dataclasses.dataclass
class ScanSample:
    pin: int                  # batches submitted before the snapshot
    pool_index: int
    vals: np.ndarray          # [T, ops, D] as the program returned them
    found: np.ndarray         # [T, ops]


@dataclasses.dataclass
class Outcome:
    """Everything the timed path produced that the comparison reads."""
    history: List[int]                      # update pool index per ticket
    ticket_reads: Dict[int, np.ndarray]     # ticket -> [T, ops, D]
    scans: List[ScanSample]
    head: np.ndarray                        # [R, D]


def _differ(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.any(a != b, axis=-1)


def compare(rp, outcome: Outcome, pool: Sequence, scan_pool: Sequence,
            control: bool = False) -> Dict[str, int]:
    """Readings of every number in ``LIMITS``, of the program's answers
    or, with ``control``, of the control's. ``rp`` is a fresh replay of
    the configuration's reference (a ``bench/reference`` module's
    ``Replay`` over the initial records)."""
    pins = {s.pin for s in outcome.scans}
    keep = pins | ({p - 1 for p in pins if p > 0} if control else set())
    by_pin: Dict[int, List[ScanSample]] = {}
    for s in outcome.scans:
        by_pin.setdefault(s.pin, []).append(s)

    got = dict.fromkeys(LIMITS, 0)
    compared = {"tickets": 0, "scans": 0}
    for pos, idx in enumerate(outcome.history + [None]):
        if pos in keep:
            rp.keep_pin(pos)
        if idx is None:
            break
        b = pool[idx]
        if pos in outcome.ticket_reads:
            reads = rp.control_reads(b.read_set) if control \
                else outcome.ticket_reads[pos]
            want = rp.serial_reads(b.read_set, b.write_set)
            valid = b.read_set >= 0
            got["ticket_mismatch_reads"] += int(
                (_differ(reads, want) & valid).sum())
            compared["tickets"] += 1
        else:
            rp.apply(b.write_set)

    for pin, samples in by_pin.items():
        for s in samples:
            rs = scan_pool[s.pool_index].read_set
            want = rp.snapshot_reads(pin, rs)
            valid = rs >= 0
            if control:
                vals, found = rp.snapshot_reads(max(pin - 1, 0), rs), valid
            else:
                vals, found = s.vals, s.found
            got["snapshot_mismatch_reads"] += int(
                (_differ(vals, want) & valid & found).sum())
            compared["scans"] += 1

    # the control's head is the reference's: the order of a batch's
    # transactions does not change what they add up to
    if not control:
        got["head_mismatch_records"] = int(
            _differ(outcome.head, rp.head()).sum())
    got.update({f"compared_{k}": v for k, v in compared.items()})
    return got


def verdict(readings: Dict[str, int]) -> bool:
    """True when every number is within its limit and something was
    compared."""
    return (readings["compared_tickets"] > 0
            and all(readings[k] <= lim for k, lim in LIMITS.items()))
