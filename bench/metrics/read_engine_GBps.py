"""The engine's bandwidth in a region read: summed ``bytes`` of the
program's ``repro.read.engine`` spans over their summed self time."""

from bench.progspans import gbps


def read(run):
    if "bench.read" not in run.spans:
        return None
    return gbps(run, "repro.read.engine")
