"""Seconds a save spends committing its records: the extents' checksums,
the append cursor and the ``index.json`` rewrite of each variable; self
time of the program's ``repro.write.commit`` spans, over the saves
(``bench.save``)."""

from bench.progspans import per_unit


def read(run):
    return per_unit(run, "repro.write.commit", "bench.save")
