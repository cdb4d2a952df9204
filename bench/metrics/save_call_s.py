"""Seconds each save holds the training loop: the span around
``CheckpointManager.save`` up to its manifest on disk, over the saves."""


def read(run):
    saves = run.spans.get("bench.save")
    return sum(saves) / len(saves) if saves else None
