"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), from the profiler's trace."""


def read(run):
    t = run.trace_summary
    return None if t is None else 100.0 * t.idle_share
