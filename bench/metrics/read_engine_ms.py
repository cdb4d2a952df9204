"""Milliseconds a region read spends in the engine
(``ReadStats.seconds`` less probe and plan), over the reads."""


def read(run):
    s = run.values.get("engine_s")
    return 1e3 * sum(s) / len(s) if s else None
