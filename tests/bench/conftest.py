"""Shared helpers of the benchmark's tests: run a cell in this process."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def run_cell(capsys):
    """``run_cell(workload, seed=..., seconds=...)`` -> (exit code, the
    result line's object or None, standard error), rehearsed in-process."""

    def run(workload, seed=11, seconds=0.3, trace=0, extra=()):
        from bench import run as bench_run
        argv = ["--workload", workload, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", str(trace), "--rehearse", *extra]
        rc = bench_run.main(argv)
        out, err = capsys.readouterr()
        lines = [ln for ln in out.splitlines() if ln.strip()]
        result = json.loads(lines[-1]) if rc == 0 and lines else None
        return rc, result, err

    return run
