"""Sweep the memmap engine's split read copy over pool widths and piece
sizes, on the host that will run it.

Usage: PYTHONPATH=src python tools/memmap_copy_sweep.py [--scale S] [--reps R]
       [--out FILE]

Writes two datasets into a temporary directory beside the working
directory, then reads each whole through ``MemmapEngine.read_plan`` into a
fresh ``np.empty`` (as a restore or a region read does), from the page
cache:

- ``row``: one contiguous row, a 151936 x 2048 float32 leaf (1.24 GB, the
  checkpoint's embedding and each of its Adam moments);
- ``strided``: a 512^3 float32 region (512 MiB) at an unaligned offset of a
  768^3 variable stored in 256^3 chunks, so 27 strided rows (a mesh
  ``sub_area`` read).

``--scale`` shrinks every length (0.25 gives a quick run anywhere).  For
each pool width (1, 2, 4, 8, 12, capped by the CPUs this process may use)
and piece size it prints one JSON line with the median seconds and GB/s
over ``--reps`` reads; ``serial`` is the unsplit loop.  No JAX, no device.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

from repro.core import plan_layout, uniform_grid_blocks
from repro.core.blocks import Block
from repro.io import Dataset, engine

WIDTHS = (1, 2, 4, 8, 12)
PIECES_MIB = (1, 2, 4, 8, 16, 32)


def _make(root: str, name: str, shape, box) -> Dataset:
    blocks = uniform_grid_blocks(shape, box)
    layout = plan_layout("chunked", blocks, num_procs=1, global_shape=shape)
    rng = np.random.default_rng(0)
    ds = Dataset.create(os.path.join(root, name), engine="memmap",
                        telemetry=False)
    ds.write("v", layout, np.float32,
             {b.block_id: rng.random(b.shape, dtype=np.float32)
              for b in blocks})
    return ds


def _set(width: int, piece: int) -> None:
    old = engine._copy_pool
    if old is not None:
        old[1].shutdown()
    engine._copy_pool = None
    engine.COPY_WORKERS_MAX = width
    engine.COPY_PIECE_BYTES = piece


def _time(ds: Dataset, region: Block, reps: int) -> tuple:
    plan = ds.plan_read("v", region)
    ref = None
    times, pooled = [], 0
    for _ in range(reps + 1):                 # the first read warms up
        out = np.empty(region.shape, np.float32)
        t0 = time.perf_counter()
        pooled = engine.MemmapEngine().read_plan(plan, ds._store, out)
        times.append(time.perf_counter() - t0)
        if ref is None:
            ref = out[::97, ::89].copy()
        elif not np.array_equal(out[::97, ::89], ref):
            raise SystemExit("a read differs from the first")
        del out
    med = statistics.median(times[1:])
    return med, plan.bytes_needed / med / 1e9, pooled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    s = args.scale
    cpus = len(os.sched_getaffinity(0))
    rows = max(16, int(151936 * s))
    edge = max(12, int(256 * s)) // 4 * 4
    out = open(args.out, "a") if args.out else sys.stdout
    with tempfile.TemporaryDirectory(dir=os.getcwd()) as root:
        row = _make(root, "row", (rows, 2048), (rows, 2048))
        mesh = _make(root, "mesh", (3 * edge,) * 3, (edge,) * 3)
        off = edge * 25 // 64
        cases = {"row": (row, Block((0, 0), (rows, 2048))),
                 "strided": (mesh, Block((off,) * 3, (off + 2 * edge,) * 3))}
        for case, (ds, region) in cases.items():
            settings = [("serial", 1, 1 << 62)] + [
                (f"w{w}_p{p}", w, p << 20) for w in WIDTHS if w <= cpus
                for p in PIECES_MIB]
            for label, width, piece in settings:
                _set(width, piece)
                sec, gbps, pooled = _time(ds, region, args.reps)
                print(json.dumps({"case": case, "setting": label,
                                  "workers": width, "piece": piece,
                                  "cpus": cpus, "seconds": sec,
                                  "GBps": gbps, "split": pooled > 0}),
                      file=out, flush=True)
        row.close()
        mesh.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
