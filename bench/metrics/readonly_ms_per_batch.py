"""Snapshot resolve: device milliseconds of the ``_readonly_resolve``
program (gather, both resolve kernels, transposes) per scan batch,
averaged over the chips (trace, "XLA Modules")."""


def read(run):
    batches = run.module_count("_readonly_resolve")
    if not batches:
        return None
    return 1e3 * run.module_seconds("_readonly_resolve") / run.chips / batches
