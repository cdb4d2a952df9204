"""Median region-read latency until the region is a ready device array,
beside the tail that decides."""

from bench.harness import percentile


def read(run):
    s = run.values.get("read_s")
    return percentile([1e3 * x for x in s], 50) if s else None
