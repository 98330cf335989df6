"""Share of a training window in which a collective (all-reduce,
collective-permute and the like) runs on a device and no other op does,
from the profiler trace (``bench/trace.py``), in percent; the worst device
counts.  One chip runs no collective, and gives nothing to read."""


def read(run: dict):
    if (run.get("driver") != "train" or not run.get("trace")
            or run["chips"] < 2):
        return None
    return 100.0 * max(run["trace"]["collective_exposed_share"].values())
