"""Snapshot resolve's share of its HBM roofline, in %: the least bytes
the scan batches' reads had to move (``bench/counting.py:
resolve_bytes``) over the chips' HBM bandwidth, against the device time
of the ``_readonly_resolve`` program (window gathers, relayouts and both
``mvcc_resolve`` kernels) summed over the chips.

The share is of the whole program, not of the kernels alone: XLA places
the kernels' operands in VMEM (memory space ``S(1)`` in the ops' HLO
text) and the relayout that feeds them writes them there, so the
kernels' own time is not bound by HBM."""


def read(run):
    batches = run.module_count("_readonly_resolve")
    seconds = run.module_seconds("_readonly_resolve")
    if not batches or seconds <= 0 or run.resolve_bytes_per_batch <= 0:
        return None
    return 100.0 * batches * run.resolve_bytes_per_batch / (
        run.hbm_bytes_per_s * seconds)
