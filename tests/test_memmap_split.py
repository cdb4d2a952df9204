"""The memmap engine's split read copy: a plan of at least two pieces'
bytes is cut into byte-balanced pieces on the shared copy pool, and the
bytes that land in the output are the serial loop's, bit for bit.  The
piece size is shrunk here so that small datasets split."""

import glob
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import plan_layout, uniform_grid_blocks
from repro.core.blocks import Block
from repro.io import Dataset, engine
from repro.io.engine import MemmapEngine, scatter_row
from repro.serve.coalesce import build_super_plan

PIECE = 4096


@pytest.fixture
def small_pieces(monkeypatch):
    monkeypatch.setattr(engine, "COPY_PIECE_BYTES", PIECE)


def _dataset(d, shape, box, dtype=np.float32, codec="none", seed=0):
    blocks = uniform_grid_blocks(shape, box)
    rng = np.random.default_rng(seed)
    field = rng.standard_normal(shape).astype(dtype)
    layout = plan_layout("chunked", blocks, num_procs=2, global_shape=shape)
    ds = Dataset.create(d, engine="memmap")
    ds.write("v", layout, dtype,
             {b.block_id: field[b.slices()] for b in blocks}, codec=codec)
    return ds, field


def _read_both(ds, region, monkeypatch):
    """(split output, serial output, bytes the split copied on the pool)"""
    plan = ds.plan_read("v", region)
    split = np.empty(region.shape, plan.dtype)
    pooled = MemmapEngine().read_plan(plan, ds._store, split)
    monkeypatch.setattr(engine, "COPY_PIECE_BYTES", 1 << 62)
    serial = np.empty(region.shape, plan.dtype)
    assert MemmapEngine().read_plan(plan, ds._store, serial) == 0
    monkeypatch.setattr(engine, "COPY_PIECE_BYTES", PIECE)
    return split, serial, pooled, plan


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


def test_unaligned_3d_region_over_27_chunks(tmp_path, small_pieces,
                                            monkeypatch):
    ds, field = _dataset(str(tmp_path / "d"), (48, 48, 48), (16, 16, 16))
    region = Block((5, 7, 9), (45, 43, 41))
    split, serial, pooled, plan = _read_both(ds, region, monkeypatch)
    assert plan.num_chunks == 27
    assert pooled == plan.bytes_needed
    np.testing.assert_array_equal(_bits(split), _bits(serial))
    np.testing.assert_array_equal(split, field[region.slices()])
    ds.close()


def test_one_contiguous_row_of_many_pieces(tmp_path, small_pieces,
                                           monkeypatch):
    shape = (512, 48)
    ds, field = _dataset(str(tmp_path / "d"), shape, shape)
    region = Block((0, 0), shape)
    split, serial, pooled, plan = _read_both(ds, region, monkeypatch)
    assert plan.num_chunks == 1
    pieces = engine._copy_pieces(plan, ds._store, np.empty(shape, np.float32))
    assert len(pieces) > 16
    assert pooled == plan.bytes_needed
    np.testing.assert_array_equal(_bits(split), _bits(serial))
    np.testing.assert_array_equal(split, field)
    ds.close()


def test_compressed_rows_are_never_cut(tmp_path, small_pieces, monkeypatch):
    shape, box = (64, 64), (32, 32)
    blocks = uniform_grid_blocks(shape, box)
    field = (np.arange(64 * 64, dtype=np.float32) % 7).reshape(shape)
    ds = Dataset.create(str(tmp_path / "d"), engine="memmap")
    # the left half raw, the right half zlib: one variable, mixed rows
    for codec, half in (("none", 0), ("zlib", 1)):
        part = [b for b in blocks if b.lo[1] // 32 == half]
        layout = plan_layout("chunked", part, num_procs=1,
                             global_shape=shape)
        ds.write("v", layout, np.float32,
                 {b.block_id: field[b.slices()] for b in part}, codec=codec)
    region = Block((1, 2), (63, 61))
    split, serial, pooled, plan = _read_both(ds, region, monkeypatch)
    compressed = [r for r in range(plan.num_chunks) if plan.codecs[r] != 0]
    assert 0 < len(compressed) < plan.num_chunks
    pieces = engine._copy_pieces(plan, ds._store, np.empty(region.shape,
                                                           np.float32))
    for row in compressed:
        holding = [p for p in pieces
                   if any(j.func is scatter_row and j.args[1] == row
                          for j in p)]
        assert len(holding) == 1 and len(holding[0]) == 1
    assert pooled == plan.bytes_needed
    np.testing.assert_array_equal(_bits(split), _bits(serial))
    np.testing.assert_array_equal(split, field[region.slices()])
    ds.close()


def test_super_plan_fetch_of_uint8_spans(tmp_path, small_pieces,
                                         monkeypatch):
    ds, field = _dataset(str(tmp_path / "d"), (64, 64), (16, 16))
    regions = [Block((0, 0), (40, 40)), Block((20, 10), (64, 50)),
               Block((3, 3), (5, 60))]
    sp = build_super_plan(ds.index, "v", regions)
    fetch = sp.fetch_plan()
    assert fetch.dtype == np.uint8 and len(fetch.region.shape) == 1
    flat = np.empty(sp.fetch_bytes, np.uint8)
    assert MemmapEngine().read_plan(fetch, ds._store, flat) == \
        fetch.bytes_needed
    monkeypatch.setattr(engine, "COPY_PIECE_BYTES", 1 << 62)
    serial = np.empty(sp.fetch_bytes, np.uint8)
    assert MemmapEngine().read_plan(fetch, ds._store, serial) == 0
    np.testing.assert_array_equal(flat, serial)
    monkeypatch.setattr(engine, "COPY_PIECE_BYTES", PIECE)
    outs, _, _ = ds.read_super_planned(sp, engine="memmap")
    for region, out in zip(regions, outs):
        np.testing.assert_array_equal(out, field[region.slices()])
    ds.close()


def test_concurrent_decomposed_reads_share_the_pool(tmp_path, small_pieces,
                                                    monkeypatch):
    """More decomposed readers than cores, each splitting its sub-plan on
    the one pool, with a short switch interval: every sub-read lands."""
    ds, field = _dataset(str(tmp_path / "d"), (64, 64, 64), (16, 16, 16))
    got, lock = [], threading.Lock()
    read_planned = ds.read_planned

    def recording(plan, *args, **kwargs):
        arr, st = read_planned(plan, *args, **kwargs)
        with lock:
            got.append((plan.region, arr))
        return arr, st

    monkeypatch.setattr(ds, "read_planned", recording)
    region = Block((3, 1, 2), (61, 63, 60))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=4) as ex:
            stats = list(ex.map(
                lambda _: ds.read_decomposed("v", region, (2, 2, 4),
                                             log_access=False),
                range(4), timeout=120))
    finally:
        sys.setswitchinterval(old)
    assert len(got) == 4 * 16
    for st in stats:
        assert st.bytes_read == region.volume * 4
    for sub, arr in got:
        np.testing.assert_array_equal(arr, field[sub.slices()])
    ds.close()


def test_split_bytes_counter_in_the_trace(tmp_path, small_pieces):
    ds, _ = _dataset(str(tmp_path / "d"), (64, 64), (16, 16))
    small = Block((0, 0), (16, 16))           # 1 KiB: under two pieces
    large = Block((1, 1), (63, 63))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        ds.read("v", small)
        _, st = ds.read("v", large)
    finally:
        jax.profiler.stop_trace()
    ds.close()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    counted = [(dict(e.stats)["bytes"], dict(e.stats)["split_bytes"])
               for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name == "repro.read.engine"]
    assert counted == [(small.volume * 4, 0), (st.bytes_read, st.bytes_read)]


def test_failing_piece_raises_after_the_others_finish(tmp_path, small_pieces,
                                                      monkeypatch):
    shape = (512, 48)
    ds, _ = _dataset(str(tmp_path / "d"), shape, shape)
    plan = ds.plan_read("v", Block((0, 0), shape))
    out = np.empty(shape, np.float32)
    pieces = engine._copy_pieces(plan, ds._store, out)
    first = pieces[0][0].args[1].ctypes.data      # the first piece's source
    done, lock = [], threading.Lock()
    copy = engine._copy

    def slow_or_failing(dst, src):
        if src.ctypes.data == first:
            raise OSError("piece failed")
        time.sleep(0.01)
        copy(dst, src)
        with lock:
            done.append(1)

    monkeypatch.setattr(engine, "_copy", slow_or_failing)
    with pytest.raises(OSError, match="piece failed"):
        MemmapEngine().read_plan(plan, ds._store, out)
    assert len(pieces) > 16 and len(done) == len(pieces) - 1
    ds.close()


def test_a_changed_pid_gets_a_new_pool(monkeypatch):
    pool = engine.copy_pool()
    assert engine.copy_pool() is pool
    pid, _ = engine._copy_pool
    monkeypatch.setattr(engine, "_copy_pool", (pid + 1, pool))
    fresh = engine.copy_pool()
    try:
        assert fresh is not pool
        assert engine.copy_pool() is fresh
        assert engine._copy_pool[0] == pid
    finally:
        fresh.shutdown()
