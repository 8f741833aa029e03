"""Device: the share of the traced window, in %, in which no operation
ran on a chip, averaged over the chips (trace, union of "XLA Ops")."""


def read(run):
    lo, hi = run.window
    if hi <= lo:
        return None
    busy = run.busy_seconds()
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
