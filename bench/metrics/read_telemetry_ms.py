"""Milliseconds a region read spends on its access-log record (and the
rewrite of ``access_log.json`` every 8th read): self time of the program's
``repro.read.telemetry`` spans, over the reads (``bench.read``)."""

from bench.progspans import per_unit


def read(run):
    s = per_unit(run, "repro.read.telemetry", "bench.read")
    return None if s is None else 1e3 * s
