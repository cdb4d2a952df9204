"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""

import numpy as np
import pytest
import jax.numpy as jnp
import ml_dtypes

from repro.core import (build_merge_plan, simulate_load_balance,
                        uniform_grid_blocks)
from repro.core.merge import execute_merge_numpy
from repro.kernels import (chunked_to_rowmajor, merge_blocks_device,
                           pack_rows, rowmajor_to_chunked)
from repro.kernels.ref import (chunked_to_rowmajor_ref, pack_rows_ref,
                               plan_row_tables, rowmajor_to_chunked_ref)

DTYPES = [np.float32, ml_dtypes.bfloat16, np.int32, np.int8]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(32, 128), (64, 256), (16, 512)])
def test_pack_rows_sweep(dtype, shape):
    rng = np.random.default_rng(hash((str(dtype), shape)) % 2 ** 31)
    n, w = shape
    src = rng.standard_normal((n, w)).astype(dtype)
    perm = rng.permutation(n)
    m = n + 8
    dst_rows = rng.choice(m, size=n, replace=False).astype(np.int32)
    out = pack_rows(jnp.asarray(src), jnp.asarray(perm.astype(np.int32)),
                    jnp.asarray(dst_rows), n_dst_rows=m, width=w,
                    interpret=True)
    ref = pack_rows_ref(src, perm, dst_rows, n_dst_rows=m, width=w)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_pack_rows_splits_long_tables_across_launches(monkeypatch):
    """Row tables longer than one SMEM prefetch are copied in several
    launches that chain the destination buffer."""
    import jax
    from repro.kernels import pack_blocks
    monkeypatch.setattr(pack_blocks, "ROWS_PER_CALL", 5)
    rng = np.random.default_rng(7)
    n, w, m = 23, 128, 29
    src = rng.standard_normal((n, w)).astype(np.float32)
    perm = rng.permutation(n).astype(np.int32)
    dst_rows = rng.choice(m, size=n, replace=False).astype(np.int32)
    args = (jnp.asarray(src), jnp.asarray(perm), jnp.asarray(dst_rows))
    kw = dict(n_dst_rows=m, width=w, interpret=True)
    jaxpr = str(jax.make_jaxpr(lambda *a: pack_rows(*a, **kw))(*args))
    assert jaxpr.count("pallas_call[") == 5          # ceil(23 / 5)
    out = pack_rows(*args, **kw)
    ref = pack_rows_ref(src, perm, dst_rows, n_dst_rows=m, width=w)
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16])
@pytest.mark.parametrize("grid,chunk", [((4, 2), (8, 128)),
                                        ((2, 4), (16, 128)),
                                        ((3, 3), (8, 256))])
def test_relayout_sweep(dtype, grid, chunk):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((*grid, *chunk)).astype(dtype)
    out = chunked_to_rowmajor(jnp.asarray(x), chunk=chunk, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), chunked_to_rowmajor_ref(x))
    back = rowmajor_to_chunked(out, chunk=chunk, interpret=True)
    np.testing.assert_array_equal(np.asarray(back),
                                  rowmajor_to_chunked_ref(
                                      chunked_to_rowmajor_ref(x), chunk))


@pytest.mark.parametrize("seed", range(4))
def test_merge_blocks_device_matches_numpy(seed):
    """End-to-end: MergePlan -> row tables -> kernel == host merge."""
    rng = np.random.default_rng(seed)
    blocks = simulate_load_balance(
        uniform_grid_blocks((32, 32, 32), (8, 8, 8)), num_procs=4, seed=seed)
    for p in range(4):
        mine = [b for b in blocks if b.owner == p]
        if not mine:
            continue
        plan = build_merge_plan(mine)
        data = {b.block_id: rng.standard_normal(b.shape).astype(np.float32)
                for b in mine}
        ref = execute_merge_numpy(plan, data)
        dev = merge_blocks_device(plan, data, interpret=True)
        assert len(ref) == len(dev)
        for a, b in zip(ref, dev):
            np.testing.assert_array_equal(a, np.asarray(b))


def test_plan_row_tables_widths():
    """Width must divide every run offset/length (alignment invariant)."""
    blocks = simulate_load_balance(
        uniform_grid_blocks((64, 32, 48), (16, 16, 16)), num_procs=3, seed=1)
    mine = [b for b in blocks if b.owner == 0]
    plan = build_merge_plan(mine)
    width, sr, dr, total, _ = plan_row_tables(plan)
    assert total % width == 0
    assert len(sr) == len(dr)
    assert len(set(dr.tolist())) == len(dr)    # no dst row written twice
    covered = len(dr) * width
    assert covered == sum(c.cuboid.volume for c in plan.clusters)


def test_pack_rows_2d_weight_shards():
    """The checkpoint-merge case: row-slab shards of a 2-D weight."""
    rng = np.random.default_rng(0)
    W = np.asarray(rng.standard_normal((64, 256)), np.float32)
    # four shards owned by one host, stored in shuffled log order
    shard_rows = [(32, 48), (0, 16), (48, 64), (16, 32)]
    src = np.concatenate([W[a:b] for a, b in shard_rows])
    src_rows, dst_rows = [], []
    pos = 0
    for a, b in shard_rows:
        for r in range(b - a):
            src_rows.append(pos + r)
            dst_rows.append(a + r)
        pos += b - a
    out = pack_rows(jnp.asarray(src),
                    jnp.asarray(np.asarray(src_rows, np.int32)),
                    jnp.asarray(np.asarray(dst_rows, np.int32)),
                    n_dst_rows=64, width=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), W)
