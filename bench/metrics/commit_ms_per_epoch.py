"""Version commit and GC: device milliseconds of the ``commit_phase``
program per epoch, averaged over the chips (trace, "XLA Modules")."""


def read(run):
    epochs = run.module_count("commit_phase")
    if not epochs:
        return None
    return 1e3 * run.module_seconds("commit_phase") / run.chips / epochs
