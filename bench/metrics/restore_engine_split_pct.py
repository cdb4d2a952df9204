"""Share of a restore's engine bytes that the memmap engine copied on its
thread pool: summed ``split_bytes`` over summed ``bytes`` of the program's
``repro.read.engine`` spans, in percent.  A program whose spans carry no
``split_bytes`` reads nothing."""

from bench.progspans import find


def read(run):
    if "bench.restore" not in run.spans:
        return None
    s = find(run, "repro.read.engine")
    if not s or "split_bytes" not in s.counters or not s.counters.get("bytes"):
        return None
    return 100 * s.counters["split_bytes"] / s.counters["bytes"]
