"""CC plan: device milliseconds of the ``plan_phase`` program per epoch,
averaged over the chips (trace, "XLA Modules")."""


def read(run):
    epochs = run.module_count("commit_phase")
    if not epochs:
        return None
    return 1e3 * run.module_seconds("plan_phase") / run.chips / epochs
