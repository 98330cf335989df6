"""Model FLOP/s utilization of the train step: model FLOPs per token (the
``train_flops_per_token`` of the reference module that the configuration
names, under ``bench/references/``) times the traced window's tokens per
second, over the chips' summed bf16 peak (``bench/peaks.py``), in percent."""
from bench.peaks import peak


def read(run: dict):
    if run.get("driver") != "train":
        return None
    total_peak = run["chips"] * peak(run["device_kind"])["bf16_flops"]
    return 100.0 * run["tokens_per_s"] * run["flops_per_token"] / total_peak
