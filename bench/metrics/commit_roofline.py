"""Version commit's share of its HBM roofline, in %: the least bytes the
window's epochs had to move (``bench/counting.py: commit_bytes``, from
each epoch's writes) over the chips' HBM bandwidth, against the device
time of the ``commit_phase`` program summed over the chips."""


def read(run):
    seconds = run.module_seconds("commit_phase")
    if seconds <= 0 or run.commit_bytes <= 0:
        return None
    return 100.0 * run.commit_bytes / (run.hbm_bytes_per_s * seconds)
