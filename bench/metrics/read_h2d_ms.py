"""Milliseconds to place a region read on the device until it is ready
(the ``bench.h2d`` span), over the reads."""


def read(run):
    if "probe_plan_s" not in run.values:
        return None
    s = run.spans.get("bench.h2d")
    return 1e3 * sum(s) / len(s) if s else None
