"""Device, four chips: device milliseconds of collective operations per
epoch, averaged over the chips (trace, "XLA Ops"; per run of
``commit_phase`` on chip 0).

A collective is an all-reduce, all-gather, reduce-scatter, all-to-all or
collective-permute, whole or as its asynchronous ``-start`` and ``-done``
halves: an op whose instruction name (before ``.<n>``) or opcode is one
of those. A chip's collective time is the union of those ops' intervals
in the window, so nested or overlapping events count once. An op that
only reads a collective's result (``fusion(... %all-gather.41 ...)``) is
not one.

The names a TPU v5e trace of ``ycsb-1kb-cc4.z0.9-2rmw8r`` printed, on
every chip, each whole (no ``-start``/``-done`` halves): in
``jit_plan_phase``, ``%all-reduce.3`` and ``%all-reduce.4`` (the merge of
the shards' plans); in ``jit_commit_phase``, ``%all-gather.37`` to
``%all-gather.43`` (the merged plan's per-shard ``[4 * Nw]`` arrays, which
the head scatter and every shard's commit read whole) and
``%all-reduce.8``, ``%all-reduce.20``, ``%all-reduce.21`` (the commit's
metrics)."""
import re

from bench import devtrace

KINDS = r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start|-done)?"
NAME = re.compile(rf"%?{KINDS}(?:\.\d+)?")
OPCODE = re.compile(rf"\s{KINDS}\(")


def is_collective(op: str) -> bool:
    instr, _, rest = op.partition(" = ")
    return bool(NAME.fullmatch(instr.strip()) or OPCODE.search(" " + rest))


def read(run):
    epochs = run.module_count("commit_phase")
    if not epochs:
        return None
    lo, hi = run.window
    per_chip = [devtrace.covered(devtrace.union(
        (s for s in d.ops if is_collective(s.name)), lo, hi))
        for d in run.trace.devices]
    return 1e3 * sum(per_chip) / len(per_chip) / epochs
