"""Milliseconds the training loop waits for its next batch: self time of
the program's ``repro.train.batch`` spans, over the window's steps."""

from bench.progspans import find


def read(run):
    steps = run.values.get("step_seconds")
    s = find(run, "repro.train.batch") if steps else None
    return 1e3 * s.self_s / len(steps) if s else None
