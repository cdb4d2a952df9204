"""Seconds a restore spends reading into host memory
(``RestoreStats.seconds``), over the resumes."""


def read(run):
    s = run.values.get("restore_read_s")
    return sum(s) / len(s) if s else None
