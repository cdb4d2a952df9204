"""Seconds a save spends removing the steps it no longer keeps: self time
of the program's ``repro.save.retain`` spans, over the saves
(``bench.save``)."""

from bench.progspans import per_unit


def read(run):
    return per_unit(run, "repro.save.retain", "bench.save")
