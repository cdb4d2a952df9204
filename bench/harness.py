"""What every cell shares: lookup by name, the run record, spans, checks
and the result line.

A cell of ``BENCHMARK.json`` names a configuration, a traffic mix and its
metrics; each is found here by its name alone:

- ``bench/configs/<config>.json``: the configuration's sizes;
- ``bench/mixes/<traffic>.json``: the mix's parameters, whose ``loop``
  names the loop that drives it (``"<module>.<function>"`` under
  ``bench/loops/``);
- ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

Adding a configuration, a mix or a per-layer metric is adding a file and an
entry; nothing here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import re
import sys
import time
from typing import Callable

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name) or ".." in name:
        raise ValueError(f"bad {kind} name {name!r}")
    return name


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise ValueError(f"unknown workload {name!r}")


def _json_file(kind: str, sub: str, name: str, bench_dir: str) -> dict:
    path = os.path.join(bench_dir, sub, _checked(kind, name) + ".json")
    if not os.path.isfile(path):
        raise ValueError(f"unknown {kind} {name!r}: no {path}")
    with open(path) as f:
        return json.load(f)


def find_config(name: str, bench_dir: str = BENCH) -> dict:
    return _json_file("configuration", "configs", name, bench_dir)


def find_mix(name: str, bench_dir: str = BENCH) -> dict:
    return _json_file("traffic mix", "mixes", name, bench_dir)


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def find_metric(name: str, bench_dir: str = BENCH) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", _checked("metric", name) + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown metric {name!r}: no {path}")
    mod = _load_module(path, "bench_metric_" + re.sub(r"\W", "_", name))
    if not callable(getattr(mod, "read", None)):
        raise ValueError(f"metric {name!r}: {path} has no read(run)")
    return mod.read


def find_loop(spec: str, bench_dir: str = BENCH) -> Callable:
    """``"<module>.<function>"`` under ``bench/loops/``."""
    module, _, func = _checked("loop", spec).partition(".")
    path = os.path.join(bench_dir, "loops", module + ".py")
    if not func or not os.path.isfile(path):
        raise ValueError(f"unknown loop {spec!r}")
    fn = getattr(_load_module(path, "bench_loop_" + module), func, None)
    if not callable(fn):
        raise ValueError(f"unknown loop {spec!r}: {path} has no {func}")
    return fn


def sized_config(config: dict, rehearse: bool) -> dict:
    """The configuration as run: in a rehearsal, its ``rehearse`` sizes
    replace the published ones (nested groups key by key)."""
    cfg = dict(config)
    if rehearse:
        for k, v in config["rehearse"].items():
            cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) and \
                isinstance(cfg.get(k), dict) else v
    return cfg


def load_peaks(kind: str, bench_dir: str = BENCH) -> dict:
    """Published peaks of one chip of ``kind`` (``device_kind``); a kind
    not in ``bench/peaks.json`` is an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default), so every PR reduces alike."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """One run of one cell: what the loop measured, for the metrics'
    readers and the result line."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    workdir: str
    t_process: float                   # perf_counter at process start
    spans: dict = dataclasses.field(default_factory=dict)
    values: dict = dataclasses.field(default_factory=dict)
    e2e: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    setup_s: float | None = None
    window_s: float | None = None
    memory_peak_bytes: int | None = None
    trace_summary: object = None       # bench.trace.TraceSummary
    device_kind: str = ""
    control: str | None = None         # a calibration's control, never the CLI's
    compiles_in_window: int = 0
    _t_window: float | None = None
    _trace_dir: str | None = None
    _listener: Callable | None = None

    # -- spans and readings ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """Host-clock span, also written into the profiler's trace."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def note(self, name: str, value) -> None:
        self.values.setdefault(name, []).append(value)

    def check(self, name: str, value: float, limit: float) -> None:
        """A number compared beside its limit; ``correct`` needs
        ``value <= limit`` for each."""
        self.checks.append(Check(name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)

    # -- the measured window --------------------------------------------------
    def open_window(self) -> float:
        """Ends set-up; starts the profiler in a traced run.  Returns the
        window's start on the host clock."""
        import jax

        def count(event, *_, **__):
            if event in COMPILE_EVENTS:
                self.compiles_in_window += 1

        self._listener = count
        jax.monitoring.register_event_listener(count)
        jax.monitoring.register_event_duration_secs_listener(count)
        if self.trace:
            self._trace_dir = os.path.join(self.workdir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._t_window = time.perf_counter()
        self.setup_s = self._t_window - self.t_process
        return self._t_window

    def window_open(self) -> bool:
        """Whether a new unit of work may start: units that start before
        ``seconds`` is up complete and count."""
        return time.perf_counter() - self._t_window < self.seconds

    def close_window(self) -> float:
        """Ends the window, reads the device's peak memory and reduces the
        trace.  Returns the window's real length."""
        import jax
        self.window_s = time.perf_counter() - self._t_window
        jax.monitoring.unregister_event_listener(self._listener)
        jax.monitoring.unregister_event_duration_listener(self._listener)
        if self.trace:
            jax.profiler.stop_trace()
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        if self.trace:
            from bench.devtrace import reduce_trace_dir
            self.trace_summary = reduce_trace_dir(self._trace_dir)
        return self.window_s

    # -- the result line ------------------------------------------------------
    def result(self, bench: dict, devices) -> dict:
        name = self.cell["name"]
        metrics = {}
        if self.trace:
            for m in bench["per_layer"]:
                if name not in m.get("workloads", [name]):
                    continue
                value = find_metric(m["name"])(self)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in bench["end_to_end"]:
                if name not in m.get("workloads", [name]):
                    continue
                value = (self.setup_s if m["name"] == "setup_s"
                         else self.e2e[m["name"]])
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        d = devices[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": self.memory_peak_bytes}
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": metrics, "device": device}
        if self.trace and self.trace_summary is not None:
            device["busy_s"] = self.trace_summary.busy_s
            device["window_s"] = self.trace_summary.window_s
            out["breakdown"] = self.trace_summary.breakdown()
        out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in self.checks}
        return out


def say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)
