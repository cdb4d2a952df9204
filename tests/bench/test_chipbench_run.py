"""Whole runs: a measurement run with no TPU fails and prints no result; a
rehearsal of each cell prints the result line the driver reads."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELLS = [c["name"] for c in BENCH["workloads"]]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "pic-read", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    # a directory holding only BENCHMARK.json and the benchmark's files
    import shutil
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "pic-read", "--seed", "3", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_line(run_cell, cell):
    rc, result, err = run_cell(cell, seed=2**31 + 3)
    assert rc == 0, err
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == 1
    # the numbers compared, each beside its limit, end standard error
    assert err.rstrip().splitlines()[-1].startswith("check ")
