"""The two split-share readers on a small recorded trace: the share of the
engine's bytes that the memmap engine copied on its thread pool, and
nothing read where the program's spans carry no ``split_bytes`` counter or
the window held nothing to read."""

import pytest

from bench import harness, progspans

# host line "python": bench.restore [0, 100) us; line "worker":
# repro.read.engine [10, 30) and [40, 50), 1500 of their 2000 bytes
# copied on the engine's pool
TRACE = """
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  lines { id: 2 name: "worker" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000
      stats { metadata_id: 1 int64_value: 1600 }
      stats { metadata_id: 2 int64_value: 1500 } }
    events { metadata_id: 2 offset_ps: 40000000 duration_ps: 10000000
      stats { metadata_id: 1 int64_value: 400 }
      stats { metadata_id: 2 int64_value: 0 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.restore" } }
  event_metadata { key: 2 value { id: 2 name: "repro.read.engine" } }
  stat_metadata { key: 1 value { id: 1 name: "bytes" } }
  stat_metadata { key: 2 value { id: 2 name: "split_bytes" } }
}
"""
# the same spans from a program without the counter
NO_COUNTER = TRACE.replace("stats { metadata_id: 2 int64_value: 1500 } ", "") \
                  .replace("stats { metadata_id: 2 int64_value: 0 } ", "")

METRICS = ["read_engine_split_pct", "restore_engine_split_pct"]
UNITS = {"bench.read": [1e-4] * 4, "bench.restore": [1e-4]}


def _run(workdir, text=None, trace=True, spans=UNITS):
    """A run with the trace ``text`` on disk where the harness writes it."""
    if text is not None:
        from jax.profiler import ProfileData
        d = workdir / "trace" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(text))
    run = harness.Run(cell={"name": "ckpt-resume"}, config={}, mix={},
                      seed=0, seconds=1, trace=trace, rehearse=True,
                      workdir=str(workdir), t_process=0.0)
    run.spans = dict(spans)
    return run


@pytest.mark.parametrize("metric", METRICS)
def test_split_share_from_counters(tmp_path, metric):
    run = _run(tmp_path, TRACE)
    assert progspans.find(run, "repro.read.engine").counters == \
        {"bytes": 2000, "split_bytes": 1500}
    assert harness.find_metric(metric)(run) == pytest.approx(75.0)


@pytest.mark.parametrize("metric", METRICS)
def test_split_share_without_the_counter_reads_none(tmp_path, metric):
    assert "split_bytes\" } }" in NO_COUNTER
    assert "metadata_id: 2 int64_value" not in NO_COUNTER
    run = _run(tmp_path, NO_COUNTER)
    assert progspans.find(run, "repro.read.engine").counters == \
        {"bytes": 2000}
    assert harness.find_metric(metric)(run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_split_share_nothing_to_read_reads_none(tmp_path, metric):
    read = harness.find_metric(metric)
    # untraced; traced but no trace on disk; a trace without the
    # benchmark's units
    assert read(_run(tmp_path / "untraced", trace=False)) is None
    assert read(_run(tmp_path / "empty")) is None
    assert read(_run(tmp_path / "traced", TRACE, spans={})) is None
