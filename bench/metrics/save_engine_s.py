"""Seconds a save spends in the engine writing its extents (with
``ensure_size`` and any fsync): self time of the program's
``repro.write.engine`` spans, over the saves (``bench.save``)."""

from bench.progspans import per_unit


def read(run):
    return per_unit(run, "repro.write.engine", "bench.save")
