#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights and data from the seed, every compile) is timed as
``setup_s``; the window then runs whole units of work until ``--seconds``
is up, and what the window produced is compared with a plain reference.
The last line of standard output is one JSON object; the last lines of
standard error are the numbers compared, each beside its limit.  With no
TPU, or fewer chips than the cell asks for, it prints no result and exits
non-zero.  ``--rehearse`` runs the configuration's small sizes on any
backend instead, for the tests.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's small sizes, on any backend")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def _import_paths() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench/run.py: no src/repro under {ROOT}")
    for p in (ROOT, src):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None, t_process: float = T_PROCESS) -> int:
    args = parse(argv)
    _import_paths()
    from bench import harness

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    config = harness.find_config(cell["config"])
    mix = harness.find_mix(cell["traffic"])
    loop = harness.find_loop(mix["loop"])
    for m in bench["per_layer"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            harness.find_metric(m["name"])

    import jax
    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu":
            harness.say(f"no TPU: the first device is {devices[0].platform}"
                        f" ({devices[0].device_kind})")
            return 2
        if len(devices) < cell["chips"]:
            harness.say(f"{cell['chips']} chips asked for, "
                        f"{len(devices)} found")
            return 2
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        # every program, however quick to compile, is found again by later
        # runs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = devices[:cell["chips"]]

    os.makedirs(WORK, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="run_", dir=WORK)
    try:
        run = harness.Run(cell=cell, config=config, mix=mix, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          rehearse=args.rehearse, workdir=workdir,
                          t_process=t_process,
                          device_kind=devices[0].device_kind)
        loop(run)
        result = run.result(bench, devices)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    harness.say(f"setup_s {run.setup_s!r} window_s {run.window_s!r} "
                f"compiles_in_window {run.compiles_in_window}")
    for c in run.checks:
        harness.say(f"check {c.name} {c.value!r} limit {c.limit!r} "
                    f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
