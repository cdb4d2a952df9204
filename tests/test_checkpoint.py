"""Checkpoint round-trip, merged layouts, resharding, async staging."""

import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, CheckpointManager,
                              RestoreStats, blocks_from_sharding,
                              flatten_pytree, unflatten_like)
from repro.core.blocks import Block, regular_decomposition, shard_grid_blocks
from repro.io import ReadStats


def _fake_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "embed": rng.standard_normal((64, 32)).astype(np.float32),
        "segments": [{"attn": {"wq": rng.standard_normal(
            (4, 32, 16)).astype(np.float32)}}],
        "count": np.asarray(7, np.int32),
    }


def _block_map():
    # embed sharded 4x2 over 8 simulated hosts; wq sharded on dim1 over 4
    return {
        "embed": shard_grid_blocks((64, 32), (4, 2),
                                   lambda idx: idx[0] * 2 + idx[1]),
        "segments/0/attn/wq": shard_grid_blocks(
            (4, 32, 16), (1, 4, 1), lambda idx: idx[1]),
    }


@pytest.mark.parametrize("strategy", ["chunked", "subfiled_fpp",
                                      "merged_process", "reorganized"])
def test_save_restore_roundtrip(tmp_path, strategy):
    tree = _fake_tree()
    mgr = CheckpointManager(str(tmp_path / "ckpt"), strategy=strategy,
                            reorg_scheme=(2, 2) if strategy == "reorganized"
                            else None)
    stats = mgr.save(100, tree, block_map=_block_map())
    assert stats.bytes > 0
    restored, rstats = mgr.restore(100, template=tree)
    for a, b in zip(flatten_pytree(tree).values(),
                    flatten_pytree(restored).values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_merged_reduces_chunks(tmp_path):
    tree = _fake_tree()
    bm = {"embed": shard_grid_blocks((64, 32), (8, 1), lambda i: i[0] // 4)}
    raw = CheckpointManager(str(tmp_path / "a"), strategy="subfiled_fpp")
    s1 = raw.save(1, {"embed": tree["embed"]}, block_map=bm)
    merged = CheckpointManager(str(tmp_path / "b"),
                               strategy="merged_process")
    s2 = merged.save(1, {"embed": tree["embed"]}, block_map=bm)
    # 4 contiguous row-slabs per host merge into 1 cuboid per host
    assert s2.num_chunks < s1.num_chunks
    r, _ = merged.restore(1)
    np.testing.assert_array_equal(r["embed"], tree["embed"])


@pytest.mark.parametrize("engine", ["memmap", "pread", "overlapped"])
def test_restore_engine_matrix(tmp_path, engine):
    """Save/restore round-trips through every execution engine."""
    tree = _fake_tree()
    mgr = CheckpointManager(str(tmp_path / "ckpt"), engine=engine)
    mgr.save(3, tree, block_map=_block_map())
    restored, _ = mgr.restore(3, template=tree)
    for a, b in zip(flatten_pytree(tree).values(),
                    flatten_pytree(restored).values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_reports_per_variable_stats(tmp_path):
    """Restore returns RestoreStats: per-variable ReadStats with exactly one
    shared index probe per variable, aggregated on top."""
    tree = _fake_tree()
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, tree, block_map=_block_map())
    targets = {"embed": regular_decomposition((64, 32), (2, 1))}
    _, stats = mgr.restore(1, target_blocks=targets)
    assert isinstance(stats, RestoreStats)
    assert sorted(stats.per_var) == ["embed", "segments/0/attn/wq"]
    for name, vs in stats.per_var.items():
        assert isinstance(vs, ReadStats)
        assert vs.chunks_touched > 0
        assert vs.bytes_read > 0
    # both elastic shards of "embed" were served from the one shared probe
    assert stats.per_var["embed"].chunks_touched >= 2
    assert stats.bytes_read == sum(v.bytes_read
                                   for v in stats.per_var.values())


def test_elastic_reshard_restore(tmp_path):
    """Save on 8 'hosts', restore shards for a 2-host mesh."""
    tree = _fake_tree()
    mgr = CheckpointManager(str(tmp_path / "ckpt"),
                            strategy="merged_process")
    mgr.save(5, tree, block_map=_block_map())
    # new decomposition: 2 hosts, embed split along rows only
    targets = {"embed": regular_decomposition((64, 32), (2, 1))}
    flat, stats = mgr.restore(5, target_blocks=targets)
    shards = flat["embed"]
    full = np.concatenate([shards[0], shards[1]], axis=0)
    np.testing.assert_array_equal(full, tree["embed"])


def test_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    t = {"x": np.ones((4, 4), np.float32)}
    for s in (1, 2, 3, 4):
        mgr.save(s, t)
    assert mgr.steps() == [3, 4]
    step, tree = mgr.restore_latest(template=t)
    assert step == 4


def test_scalars_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    t = {"w": np.ones((4, 4), np.float32), "count": np.asarray(42, np.int32)}
    mgr.save(1, t)
    r, _ = mgr.restore(1, template=t)
    assert int(r["count"]) == 42


def test_async_checkpointer(tmp_path):
    tree = {"w": np.random.default_rng(0).standard_normal(
        (64, 64)).astype(np.float32)}
    bm = {"w": shard_grid_blocks((64, 64), (4, 1), lambda i: i[0])}
    ac = AsyncCheckpointer(str(tmp_path / "async"), reorg_scheme=(2, 2),
                           num_workers=1, queue_depth=2, n_compute=256,
                           m_staging=2, t_w_direct=0.001)
    for step in range(3):
        ac.save(step, tree, block_map=bm)
    results = ac.finish()
    assert len(results) == 3
    timings = ac.timings(results)
    rec = ac.recommendation(t_c=10.0, N=100, timings=timings)
    assert rec.mode in ("on_the_fly", "post_hoc")
    # written data is readable
    from repro.io import Dataset
    ds = Dataset(str(tmp_path / "async"))
    arr, _ = ds.read("w@2", Block((0, 0), (64, 64)))
    np.testing.assert_array_equal(arr, tree["w"])


def test_blocks_from_sharding_single_device():
    """On the 1-CPU container a trivial sharding gives one block."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    sh = NamedSharding(mesh, P())
    blocks = blocks_from_sharding((8, 4), sh, devices_per_host=4)
    assert len(blocks) == 1
    assert blocks[0].shape == (8, 4)


def test_flatten_unflatten_roundtrip():
    t = _fake_tree()
    flat = flatten_pytree(t)
    assert "segments/0/attn/wq" in flat
    back = unflatten_like(t, flat)
    for a, b in zip(flatten_pytree(back).values(), flat.values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- cross-run prior auto-discovery (ISSUE 6 satellite) ----------------------

def _run_with_history(root):
    """A run root with restore telemetry and an exported prior."""
    import os
    mgr = CheckpointManager(root)
    tree = _fake_tree()
    mgr.save(1, tree, block_map=_block_map())
    mgr.restore(1, template=tree)
    p = mgr.export_prior()
    assert os.path.exists(p)
    return p


def test_discover_prior_finds_newest_sibling(tmp_path):
    import os
    runs = tmp_path / "runs"
    p1 = _run_with_history(str(runs / "run_001"))
    p2 = _run_with_history(str(runs / "run_002"))
    os.utime(p1, (1_000_000, 1_000_000))    # run_002's prior is fresher
    m3 = CheckpointManager(str(runs / "run_003"))
    assert m3.discover_prior() == p2
    # discovery feeds layout_policy when no explicit prior was given
    assert m3.layout_policy() is not None


def test_discover_prior_excludes_own_root_and_handles_none(tmp_path):
    runs = tmp_path / "runs"
    m1 = CheckpointManager(str(runs / "run_001"))
    tree = _fake_tree()
    m1.save(1, tree, block_map=_block_map())
    m1.restore(1, template=tree)
    m1.export_prior()                       # only OUR root has a prior
    assert m1.discover_prior() is None      # own root is not a sibling
    lone = CheckpointManager(str(tmp_path / "elsewhere" / "run_x"))
    assert lone.discover_prior() is None    # cold start: no siblings at all


def test_explicit_prior_beats_discovery(tmp_path):
    runs = tmp_path / "runs"
    p1 = _run_with_history(str(runs / "run_001"))
    explicit = _run_with_history(str(tmp_path / "exported"))
    m = CheckpointManager(str(runs / "run_002"), prior=explicit)
    assert m.discover_prior() == p1         # a sibling exists...
    m.layout_policy()                       # ...but the explicit one is used
    assert m.prior == explicit
