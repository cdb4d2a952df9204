"""The controls come out as not correct, at a size a test run can hold:
the training reference at float8 in the program's place, and the mesh
variable stored in float16 rather than float32."""

import os
import shutil
import tempfile
import time

import pytest

from bench import harness


def _run(cell, seed, control, seconds=0.3):
    bench = harness.load_benchmark()
    c = harness.find_cell(bench, cell)
    os.makedirs(os.path.join(harness.ROOT, ".bench_work"), exist_ok=True)
    workdir = tempfile.mkdtemp(dir=os.path.join(harness.ROOT, ".bench_work"))
    try:
        run = harness.Run(cell=c, config=harness.find_config(c["config"]),
                          mix=harness.find_mix(c["traffic"]), seed=seed,
                          seconds=seconds, trace=False, rehearse=True,
                          workdir=workdir, t_process=time.perf_counter(),
                          control=control)
        harness.find_loop(run.mix["loop"])(run)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return run


@pytest.mark.parametrize("seed", [101, 2**31 + 5, 3**20])
def test_fp8_training_control_fails(seed):
    run = _run("ckpt-save", seed, "fp8", seconds=0.01)
    assert not run.correct
    failed = {c.name for c in run.checks if not c.ok}
    assert failed and failed <= {"loss_gap", "grad_gap", "change_gap",
                                 "change_mean_gap"}, \
        run.checks


@pytest.mark.parametrize("cell,check", [
    ("pic-read", "read_elements_differing"),
])
def test_float16_store_control_fails(cell, check):
    run = _run(cell, 7, "f16_store")
    assert not run.correct
    assert [x.value for x in run.checks if x.name == check][0] > 0
