"""Share of a training window in which the attention kernel's layer scope
(``attn.core``: scores, mask, softmax and the weighted sum of values, in the
forward, its rematerialisation and the backward) runs on a device, from the
profiler trace reduced by the program's layer scopes (``bench/scopes.py``),
in percent, mean over devices.  A run without a trace, or a step without
the scope, gives nothing to read."""


def read(run: dict):
    share = (run.get("scopes") or {}).get("attn.core")
    if run.get("driver") != "train" or share is None:
        return None
    return 100.0 * share
