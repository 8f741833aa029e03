"""Execution wavefront: waves per epoch, from the engine's counters
``engine/waves`` and ``engine/commits`` over the window."""


def read(run):
    commits = run.counters.get("engine/commits", 0)
    if not commits:
        return None
    return run.counters.get("engine/waves", 0) / commits
