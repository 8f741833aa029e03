"""Execution wavefront: device milliseconds of the ``exec_phase`` program
per epoch, averaged over the chips (trace, "XLA Modules")."""


def read(run):
    epochs = run.module_count("commit_phase")
    if not epochs:
        return None
    return 1e3 * run.module_seconds("exec_phase") / run.chips / epochs
