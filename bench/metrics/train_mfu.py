"""The whole training step's share of the chip's bf16 peak: model FLOPs of
the window's steps (``bench/flops.py``) over their summed step seconds
times the published peak of the device kind (``bench/peaks.json``)."""

from bench.flops import train_step_flops
from bench.harness import load_peaks


def read(run):
    s = run.values.get("step_seconds")
    if not s:
        return None
    t = run.config["training"]
    flops = train_step_flops(run.config, t["global_batch"], t["seq_len"])
    peak = load_peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops * len(s) / (sum(s) * peak)
