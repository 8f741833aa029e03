"""Version commit and GC: how unevenly the record partition loads the
chips, as the busiest chip's committed versions over an even share:
chips x ``engine/shard_writes_max`` / ``engine/shard_writes`` over the
window (the engine's counters: each epoch adds the versions committed
and the largest number any one shard committed). 1.0 is an even split;
a program without the counters gives nothing."""


def read(run):
    writes = run.counters.get("engine/shard_writes", 0)
    if not writes:
        return None
    return run.chips * run.counters.get("engine/shard_writes_max", 0) / writes
