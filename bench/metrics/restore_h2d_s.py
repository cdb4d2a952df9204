"""Seconds to place a restored state on the device until it is ready
(the ``bench.h2d`` span), over the resumes."""


def read(run):
    if "restore_read_s" not in run.values:
        return None
    s = run.spans.get("bench.h2d")
    return sum(s) / len(s) if s else None
