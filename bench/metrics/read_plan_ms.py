"""Milliseconds a region read spends in the spatial index and the planner
(``ReadStats.probe_seconds + plan_seconds``), over the reads."""


def read(run):
    s = run.values.get("probe_plan_s")
    return 1e3 * sum(s) / len(s) if s else None
