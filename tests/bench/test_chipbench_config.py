"""A configuration that states what the program does not do is refused
before any run: each key that the program fixes in code is checked."""

import copy
import time

import pytest

from bench import harness


def _changed(path, value):
    cfg = copy.deepcopy(harness.find_config("qwen2.5-3b-4L-adam"))
    node = cfg
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    return cfg


@pytest.mark.parametrize("path,value", [
    (("training", "compute_dtype"), "float32"),
    (("training", "param_dtype"), "bfloat16"),
    (("training", "zipf_a"), 1.2),
    (("rms_norm_eps",), 1e-5),
    (("hidden_act",), "gelu"),
])
def test_config_the_program_does_not_follow_is_refused(tmp_path, path,
                                                       value):
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, "ckpt-save")
    run = harness.Run(cell=cell, config=_changed(path, value),
                      mix=harness.find_mix(cell["traffic"]), seed=5,
                      seconds=0.01, trace=False, rehearse=True,
                      workdir=str(tmp_path), t_process=time.perf_counter())
    with pytest.raises(ValueError):
        harness.find_loop(run.mix["loop"])(run)
