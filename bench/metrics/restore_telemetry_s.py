"""Seconds a resume spends on restore telemetry (access-log appends and
the flush of ``access_log.json``): self time of the program's
``repro.read.telemetry`` spans, over the resumes (``bench.restore``)."""

from bench.progspans import per_unit


def read(run):
    return per_unit(run, "repro.read.telemetry", "bench.restore")
