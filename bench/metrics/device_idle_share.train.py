"""Share of a training window in which no op runs on a device, from the
profiler trace (``bench/trace.py``), in percent; the idlest device counts."""


def read(run: dict):
    if run.get("driver") != "train" or not run.get("trace"):
        return None
    return 100.0 * max(run["trace"]["idle_share"].values())
