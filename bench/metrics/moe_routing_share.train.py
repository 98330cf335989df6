"""Share of a training window spent routing tokens to experts and back: the
layer scopes ``moe.router`` (the norm and top-k routing), ``moe.dispatch``
(sorting, ranking and gathering into capacity slots) and ``moe.combine``
(the weighted gather back, scatter-add and residual add), from the profiler
trace reduced by the program's layer scopes (``bench/scopes.py``), in
percent, mean over devices.  The experts' own einsums are not routing.  A
run without a trace, or a step without any of these scopes (a dense model),
gives nothing to read."""

ROUTING = ("moe.router", "moe.dispatch", "moe.combine")


def read(run: dict):
    scopes = run.get("scopes") or {}
    found = [scopes[s] for s in ROUTING if s in scopes]
    if run.get("driver") != "train" or not found:
        return None
    return 100.0 * sum(found)
