"""Program spans in the profiler's trace: a few training steps, two saves
(the second retires the first), a restore and one region read, all under
``jax.profiler.start_trace``.  Every span name appears, children lie inside
their parent on the same thread line, and the counters match the stats
the calls return.  Without JAX, a span is a null context."""

import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.core import (plan_layout, simulate_load_balance,
                        uniform_grid_blocks)
from repro.core.blocks import Block
from repro.data.pipeline import PipelineConfig, SyntheticTokens
from repro.io import Dataset
from repro.models import LM
from repro.train import OptimizerConfig, Trainer

# every span and the spans it may lie inside (None: a top-level span)
PARENTS = {
    "repro.save": None,
    "repro.save.d2h": {"repro.save"},
    "repro.save.plan": {"repro.save"},
    "repro.write.assemble": {"repro.save"},
    "repro.write.engine": {"repro.save"},
    "repro.write.commit": {"repro.save"},
    "repro.save.manifest": {"repro.save"},
    "repro.save.retain": {"repro.save"},
    "repro.restore": None,
    "repro.read.open": {"repro.restore"},
    "repro.read.probe": {"repro.restore", "repro.read.plan"},
    "repro.read.plan": {"repro.restore", "repro.read"},
    "repro.read.engine": {"repro.restore", "repro.read"},
    "repro.read.telemetry": {"repro.restore", "repro.read"},
    "repro.read": None,
    "repro.train.batch": None,
    "repro.train.step": None,
}
SHAPE, BOX = (64, 128, 256), (32, 64, 128)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(events by line, SaveStats, RestoreStats, the read's ReadStats);
    an event is (name, start_ns, end_ns, {counter: value})."""
    tmp = tmp_path_factory.mktemp("spans")
    cfg = get_smoke_config("qwen2.5-3b")
    pcfg = PipelineConfig(global_batch=4, seq_len=32, vocab=cfg.vocab,
                          seed=3)
    tr = Trainer(LM(cfg), OptimizerConfig(warmup_steps=1, total_steps=10),
                 SyntheticTokens(pcfg))
    params, opt = tr.init(jax.random.key(0))
    params, opt, _ = tr.run(params, opt, 1, log_every=0)     # compiles
    mgr = CheckpointManager(str(tmp / "ckpt"), keep=1)
    blocks = simulate_load_balance(uniform_grid_blocks(SHAPE, BOX),
                                   num_procs=4, seed=3)
    field = np.random.default_rng(3).random(SHAPE, dtype=np.float32)
    data = {b.block_id: field[b.slices()] for b in blocks}
    layout = plan_layout("reorganized", blocks, num_procs=4,
                         global_shape=SHAPE, reorg_scheme=(2, 2, 2))
    Dataset.create(str(tmp / "mesh")).write("E", layout, np.float32, data)
    ds = Dataset.open(str(tmp / "mesh"))

    jax.profiler.start_trace(str(tmp / "trace"))
    try:
        params, opt, _ = tr.run(params, opt, 2, log_every=0)
        mgr.save(tr.state.step - 1, {"params": params, "opt_state": opt})
        saved = mgr.save(tr.state.step, {"params": params,
                                          "opt_state": opt})
        _, restored = mgr.restore(tr.state.step)
        region = Block((5, 17, 40), (60, 100, 200))
        arr, read = ds.read("E", region)
    finally:
        jax.profiler.stop_trace()
    ds.close()
    np.testing.assert_array_equal(arr, field[region.slices()])

    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for e in line.events if e.name.startswith("repro.")]
            if evs:
                lines.append(evs)
    return lines, saved, restored, read


def _all(lines, name):
    return [ev for evs in lines for ev in evs if ev[0] == name]


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_every_span_appears(traced, name):
    assert _all(traced[0], name)


def test_children_lie_inside_their_parent(traced):
    lines = traced[0]
    for evs in lines:
        for name, s, e, _ in evs:
            parents = PARENTS[name]
            if parents is None:
                continue
            assert any(p in parents and ps <= s and e <= pe
                       for p, ps, pe, _ in evs), name


def test_engine_bytes_match_the_stats(traced):
    lines, saved, restored, read = traced
    save = max(_all(lines, "repro.save"), key=lambda ev: ev[1])
    in_last = [c for _, s, e, c in _all(lines, "repro.write.engine")
               if save[1] <= s and e <= save[2]]
    assert sum(c["bytes"] for c in in_last) == saved.bytes
    assert save[3]["bytes"] == saved.bytes

    def engine_bytes(outer):
        o, = _all(lines, outer)
        return sum(c["bytes"] for _, s, e, c in _all(lines,
                                                     "repro.read.engine")
                   if o[1] <= s and e <= o[2])
    assert engine_bytes("repro.restore") == restored.bytes_read
    assert engine_bytes("repro.read") == read.bytes_read > 0
    assert _all(lines, "repro.read")[0][3]["bytes"] == read.bytes_read


def test_retain_counts_the_step_removed(traced):
    counts = [c["dirs"] for *_, c in _all(traced[0], "repro.save.retain")]
    assert counts == [0, 1]


def test_spans_are_null_without_jax(tmp_path):
    code = """
import sys
import numpy as np
import repro.io
from repro.core import plan_layout, uniform_grid_blocks
from repro.core.blocks import Block
from repro.core.spans import span
assert "jax" not in sys.modules
with span("repro.x", bytes=1) as s:
    s.set_metadata(chunks=2)
blocks = uniform_grid_blocks((8, 8), (4, 4))
layout = plan_layout("reorganized", blocks, num_procs=2,
                     global_shape=(8, 8), reorg_scheme=(2, 1))
field = np.arange(64, dtype=np.float32).reshape(8, 8)
d = sys.argv[1]
repro.io.Dataset.create(d).write("v", layout, np.float32,
                                 {b.block_id: field[b.slices()]
                                  for b in blocks})
arr, _ = repro.io.Dataset.open(d).read("v", Block((1, 2), (7, 8)))
assert (arr == field[1:7, 2:8]).all()
assert "jax" not in sys.modules
print("ok")
"""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path / "d")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"
