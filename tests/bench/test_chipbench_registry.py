"""Configurations, mixes, metrics and loops are found by name alone; an
unknown or malformed name is refused; a file the harness has never seen is
found once it exists."""

import json
import os

import pytest

from bench import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_resolves(cell):
    assert harness.find_cell(BENCH, cell["name"]) is cell
    assert harness.find_config(cell["config"])["name"] == cell["config"]
    mix = harness.find_mix(cell["traffic"])
    assert callable(harness.find_loop(mix["loop"]))


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.find_metric(metric["name"]))


@pytest.mark.parametrize("find,name", [
    (harness.find_config, "no-such-config"),
    (harness.find_mix, "no-such-mix"),
    (harness.find_metric, "no_such_metric"),
    (harness.find_loop, "train.no_such_loop"),
    (harness.find_loop, "no_such_module.loop"),
    (harness.find_config, "../BENCHMARK"),
    (harness.find_metric, "a/b"),
])
def test_unknown_or_bad_names_refused(find, name):
    with pytest.raises(ValueError):
        find(name)


def test_unknown_workload_refused():
    with pytest.raises(ValueError):
        harness.find_cell(BENCH, "no-such-cell")


def test_files_never_seen_are_found_by_name(tmp_path):
    for sub in ("configs", "mixes", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"name": "new-model", "hidden_size": 8}))
    (tmp_path / "mixes" / "new-mix.json").write_text(
        json.dumps({"loop": "pic.region_reads", "thickness": 2}))
    (tmp_path / "metrics" / "new_kernel_roofline.py").write_text(
        "def read(run):\n    return run.values.get('x', [None])[0]\n")
    cfg = harness.find_config("new-model", bench_dir=str(tmp_path))
    assert cfg["hidden_size"] == 8
    assert harness.find_mix("new-mix", bench_dir=str(tmp_path))[
        "thickness"] == 2
    read = harness.find_metric("new_kernel_roofline",
                               bench_dir=str(tmp_path))

    class Run:
        values = {"x": [42.0]}
    assert read(Run) == 42.0


def test_reader_that_finds_nothing_leaves_the_metric_out():
    run = harness.Run(cell={"name": "pic-read"}, config={}, mix={}, seed=0,
                      seconds=1, trace=True, rehearse=True, workdir="",
                      t_process=0.0)
    for m in BENCH["per_layer"]:
        assert harness.find_metric(m["name"])(run) is None
