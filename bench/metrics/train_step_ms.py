"""Milliseconds a training step takes: the trainer's ``step_seconds``
(host clock up to the loss being ready) summed over the window's steps,
over the steps."""


def read(run):
    s = run.values.get("step_seconds")
    return 1e3 * sum(s) / len(s) if s else None
