"""The program-span reduction on a small recorded trace: self time less
nested children on the same line, sums of counters, the division by the
benchmark's own units, bandwidth from counters, and nothing read where
there is nothing to read."""

import pytest

from bench import harness, progspans

# host line "python": bench.save [0, 100) us holding repro.save [1, 99),
# which holds repro.save.d2h [2, 12) (with a non-program event inside it),
# repro.write.engine [20, 60) and [60, 70), repro.save.retain [80, 90);
# line "worker": repro.read.engine [10, 30), inside repro.save's interval
# but on another thread
TRACE = """
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 98000000
      stats { metadata_id: 1 int64_value: 5000 }
      stats { metadata_id: 3 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 10000000
      stats { metadata_id: 1 int64_value: 1000 }
      stats { metadata_id: 4 str_value: "params/embed" } }
    events { metadata_id: 7 offset_ps: 3000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 40000000
      stats { metadata_id: 1 int64_value: 4000 }
      stats { metadata_id: 2 str_value: "memmap" } }
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 10000000
      stats { metadata_id: 1 int64_value: 1000 }
      stats { metadata_id: 2 str_value: "memmap" } }
    events { metadata_id: 5 offset_ps: 80000000 duration_ps: 10000000
      stats { metadata_id: 5 int64_value: 1 } }
  }
  lines { id: 2 name: "worker" timestamp_ns: 1000
    events { metadata_id: 6 offset_ps: 10000000 duration_ps: 20000000
      stats { metadata_id: 1 int64_value: 2000 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.save" } }
  event_metadata { key: 2 value { id: 2 name: "repro.save" } }
  event_metadata { key: 3 value { id: 3 name: "repro.save.d2h" } }
  event_metadata { key: 4 value { id: 4 name: "repro.write.engine" } }
  event_metadata { key: 5 value { id: 5 name: "repro.save.retain" } }
  event_metadata { key: 6 value { id: 6 name: "repro.read.engine" } }
  event_metadata { key: 7 value { id: 7 name: "np.asarray" } }
  stat_metadata { key: 1 value { id: 1 name: "bytes" } }
  stat_metadata { key: 2 value { id: 2 name: "engine" } }
  stat_metadata { key: 3 value { id: 3 name: "variables" } }
  stat_metadata { key: 4 value { id: 4 name: "var" } }
  stat_metadata { key: 5 value { id: 5 name: "dirs" } }
}
"""


def _run(workdir, trace=True, spans=None):
    run = harness.Run(cell={"name": "ckpt-save"}, config={}, mix={}, seed=0,
                      seconds=1, trace=trace, rehearse=True,
                      workdir=str(workdir), t_process=0.0)
    run.spans = dict(spans or {})
    return run


@pytest.fixture
def traced(tmp_path):
    """A traced run whose window held two saves, with the trace on disk
    where the harness writes it."""
    from jax.profiler import ProfileData
    d = tmp_path / "trace" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TRACE))
    return _run(tmp_path, spans={"bench.save": [1e-4, 1e-4],
                                 "bench.read": [1e-4] * 4})


def test_self_time_less_children_on_the_same_line(traced):
    sums = progspans.spans(traced)
    assert set(sums) == {"repro.save", "repro.save.d2h",
                         "repro.write.engine", "repro.save.retain",
                         "repro.read.engine"}
    save = sums["repro.save"]
    assert save.count == 1
    assert save.total_s == pytest.approx(98e-6)
    # d2h 10 + engine 40 + 10 + retain 10; the worker's span and the
    # non-program event do not count
    assert save.self_s == pytest.approx(28e-6)
    assert sums["repro.save.d2h"].self_s == pytest.approx(10e-6)
    assert sums["repro.read.engine"].self_s == pytest.approx(20e-6)


def test_counters_are_summed_by_name(traced):
    sums = progspans.spans(traced)
    engine = sums["repro.write.engine"]
    assert engine.count == 2
    assert engine.counters == {"bytes": 5000}
    assert sums["repro.save"].counters == {"bytes": 5000, "variables": 7}
    assert sums["repro.save.retain"].counters == {"dirs": 1}


def test_per_unit_divides_by_the_benchmark_spans(traced):
    assert progspans.per_unit(traced, "repro.write.engine",
                              "bench.save") == pytest.approx(25e-6)
    assert harness.find_metric("save_engine_s")(traced) == \
        pytest.approx(25e-6)
    assert harness.find_metric("save_d2h_s")(traced) == pytest.approx(5e-6)
    assert harness.find_metric("save_retain_s")(traced) == \
        pytest.approx(5e-6)


def test_bandwidth_from_counters(traced):
    # 5000 bytes in 50 us; 2000 bytes in 20 us
    assert progspans.gbps(traced, "repro.write.engine") == \
        pytest.approx(0.1)
    assert harness.find_metric("read_engine_GBps")(traced) == \
        pytest.approx(0.1)


@pytest.mark.parametrize("metric", [
    "save_d2h_s", "save_assemble_s", "save_engine_s", "save_commit_s",
    "save_retain_s", "train_batch_ms", "restore_engine_GBps",
    "restore_telemetry_s", "read_engine_GBps", "read_telemetry_ms"])
def test_nothing_to_read_reads_none(tmp_path, traced, metric):
    read = harness.find_metric(metric)
    units = {"bench.save": [1.0], "bench.restore": [1.0],
             "bench.read": [1.0]}
    # untraced; traced but no trace on disk; a trace without the span or
    # without the benchmark's units
    assert read(_run(tmp_path, trace=False, spans=units)) is None
    assert read(_run(tmp_path / "empty", spans=units)) is None
    traced.spans = {}
    assert read(traced) is None


def test_missing_span_reads_none(traced):
    assert progspans.find(traced, "repro.restore") is None
    assert progspans.per_unit(traced, "repro.write.commit",
                              "bench.save") is None
    assert progspans.gbps(traced, "repro.save.retain") is None
