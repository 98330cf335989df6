"""The attention kernel's share of its roofline in training: the least time
in which the chip could run the attention kernel's calls of the traced
window, over the device time of the ``attn.core`` layer scope that holds
them (``bench/scopes.py``), in percent, per chip.

The calls are counted from the trace (``kernel_runs``): each backward pass
of a layer runs each backward kernel once, and the forward runs per
backward pass are one, or two where the layer is rematerialised.  Each
call's operations and bytes a token come from ``attention_kernel_cost`` of
the reference module that the configuration names; its least time is the
larger of operations over the bf16 peak and bytes over the HBM bandwidth
(``bench/peaks.py``).  At the cells' 2048-long sequences every call does
hundreds of operations a byte, above the v5e's 240, so compute bounds them.
The scope's time also holds the layout moves around the kernel.  A run
without a trace, without kernel runs under the scope, or whose family
counts no kernel cost, gives nothing to read."""
from bench.peaks import peak

SCOPE = "attn.core"


def read(run: dict):
    share = (run.get("scopes") or {}).get(SCOPE)
    runs = (run.get("kernel_runs") or {}).get(SCOPE)
    cost = run.get("attention_kernel")
    if (run.get("driver") != "train" or not share or not cost or not runs
            or not runs["backward"]):
        return None
    p = peak(run["device_kind"])

    def least_s(call):
        return max(call["flops"] / p["bf16_flops"],
                   call["bytes"] / p["hbm_bytes_per_s"])

    passes = max(runs["backward"].values())
    forwards = sum(runs["forward"].values()) / passes
    tokens = run["attempted"] * run["tokens_per_step"]
    need_s = tokens * (forwards * least_s(cost["forward"])
                       + least_s(cost["backward"])) / run["chips"]
    return 100.0 * need_s / (share * run["scopes_window_s"])
