"""The trace reducer on a small recorded trace: busy time, idle share, the
longest device operations and the idle gaps by the span around them."""

import pytest

from bench import devtrace

# one device, three ops; host spans bench.step [0, 10) us and bench.save
# [10, 40) us; ops at [0, 4), [2, 6) (overlapping) and [30, 35) us
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 30000000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 40000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.save" } }
  event_metadata { key: 3 value { id: 3 name: "np.asarray" } }
}
"""


@pytest.fixture
def summary():
    from jax.profiler import ProfileData
    return devtrace.reduce_profile(ProfileData.from_text_proto(TRACE))


def test_union_merges_overlaps():
    total, merged = devtrace.union_length([(5, 7), (0, 4), (2, 6)])
    assert total == 7
    assert merged == [[0, 7]]


def test_busy_and_idle_share(summary):
    # busy: [0, 6) + [30, 35) = 11 us of a 40 us window; the modules line
    # is not counted beside the ops line
    assert summary.devices == 1
    assert summary.busy_s == pytest.approx(11e-6)
    assert summary.window_s == pytest.approx(40e-6)
    assert summary.idle_share == pytest.approx(1 - 11 / 40)


def test_breakdown(summary):
    b = summary.breakdown()
    ops = dict(b["device_ops"])
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "convolution.2"]
    assert ops["fusion.1"] == pytest.approx(9e-6)
    gaps = dict(b["idle_gaps"])
    # [6, 10) under bench.step; [10, 30) and [35, 40) under bench.save
    assert gaps == pytest.approx({"bench.step": 4e-6, "bench.save": 25e-6})
    assert b["idle_gaps"][0][0] == "bench.save"


def test_a_trace_without_device_ops_is_refused():
    from jax.profiler import ProfileData
    host_only = TRACE[TRACE.index("planes {\n  id: 2"):]
    with pytest.raises(ValueError):
        devtrace.reduce_profile(ProfileData.from_text_proto(host_only))
