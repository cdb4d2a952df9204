"""Seconds a save spends copying its leaves from the device into host
memory: self time of the program's ``repro.save.d2h`` spans, over the saves
(``bench.save``)."""

from bench.progspans import per_unit


def read(run):
    return per_unit(run, "repro.save.d2h", "bench.save")
