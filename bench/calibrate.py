#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip
at each cell's own size.  The benchmark's runs never call this.

    python bench/calibrate.py train --seeds 1 2 ... --controls 3
    python bench/calibrate.py pic --seeds 1 2 3 --seconds 3

``train``: for each seed, the program's first steps (the loop's own
set-up) against the plain reference, which gives the lower readings; on
the first ``--controls`` seeds, the control (the reference at float8 in
the program's place, through the loop's own comparison, so that its
``correct`` is the harness's) and a planted fault (half of the batch left
out, the mean taken over the rest) against the same reference, which give
the upper readings.  A state left unchanged reads 1 by construction.  The
raw readings (each step's loss, each leaf's norms) go out beside the gaps.

``pic``: the region-read cell with the control in place (the variable
stored in float16 rather than float32; the container cannot hold bfloat16
buffers), a short window each.

Each reading is one JSON line on standard output.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _run(cell_name: str, seed: int, seconds: float, rehearse: bool,
         control=None):
    from bench import harness
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, cell_name)
    workdir = tempfile.mkdtemp(prefix="calib_",
                               dir=os.path.join(ROOT, ".bench_work"))
    run = harness.Run(cell=cell, config=harness.find_config(cell["config"]),
                      mix=harness.find_mix(cell["traffic"]), seed=seed,
                      seconds=seconds, trace=False, rehearse=rehearse,
                      workdir=workdir, t_process=time.perf_counter(),
                      control=control)
    return run, workdir


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def train(args) -> None:
    import jax
    from bench.loops import train as tl

    for i, seed in enumerate(args.seeds):
        run, workdir = _run(args.cell, seed, 0.0, args.rehearse)
        try:
            job = tl.build(run)
            tl.delete_arrays((job.params, job.opt_state))
            job.params = job.opt_state = None
            ref = job.ref.readings(job.cfg, seed,
                                   steps=run.mix["setup_steps"])
            emit(kind="program", seed=seed, **tl.gaps(job.readings, ref))
            emit(kind="readings", seed=seed, program=job.readings,
                 reference=ref)
            if i < args.controls:
                control = dataclasses.replace(run, control="fp8", checks=[])
                tl.compare_with_reference(control, job)
                emit(kind="control_fp8", seed=seed, correct=control.correct,
                     **{c.name: c.value for c in control.checks})
                emit(kind="readings_fp8", seed=seed,
                     control=job.ref.readings(job.cfg, seed,
                                              steps=run.mix["setup_steps"],
                                              fp8=True))
                b = job.cfg["training"]["global_batch"]
                other = job.ref.readings(job.cfg, seed,
                                         steps=run.mix["setup_steps"],
                                         rows=b // 2)
                emit(kind="fault_half_batch", seed=seed,
                     **tl.gaps(other, ref))
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        jax.clear_caches()


def pic(args) -> None:
    from bench import harness
    for seed in args.seeds:
        run, workdir = _run("pic-read", seed, args.seconds, args.rehearse,
                            control="f16_store")
        try:
            harness.find_loop(run.mix["loop"])(run)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        emit(kind="control_f16_store", cell="pic-read", seed=seed,
             correct=run.correct, **{c.name: c.value for c in run.checks})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("train", "pic"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--cell", default="ckpt-save")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    {"train": train, "pic": pic}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
