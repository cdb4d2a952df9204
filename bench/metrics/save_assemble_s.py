"""Seconds a save spends assembling chunk buffers from its blocks: self
time of the program's ``repro.write.assemble`` spans, over the saves
(``bench.save``)."""

from bench.progspans import per_unit


def read(run):
    return per_unit(run, "repro.write.assemble", "bench.save")
