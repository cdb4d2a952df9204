"""The program's own spans in a traced run: each ``repro.*`` span name's
count, duration, self time and summed counters, from the profiler trace of
the window (``<workdir>/trace``).

The program opens its spans (``repro.core.spans.span``, a
``jax.profiler.TraceAnnotation``) on the thread that does the work, so on
each host thread's line of the trace they nest by containment.  A span's
self time is its duration less the part its ``repro.*`` children on the
same line cover.  Counters are the spans' arguments; numeric ones are
summed per name.

A reader returns ``None`` for an untraced run, a run whose trace is gone,
or a span that never occurred: a program without these spans reads nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os

PREFIX = "repro."


@dataclasses.dataclass
class SpanSum:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)


def reduce_profile(pd) -> dict:
    """``{span name: SpanSum}`` of the ``repro.*`` spans of a
    ``jax.profiler.ProfileData``."""
    out: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(
                ((float(e.start_ns), float(e.start_ns + e.duration_ns),
                  e.name, list(e.stats))
                 for e in line.events if e.name.startswith(PREFIX)),
                key=lambda ev: (ev[0], -ev[1]))
            stack = []                    # (end, SpanSum) of open spans
            for start, end, name, stats in events:
                while stack and stack[-1][0] <= start:
                    stack.pop()
                if stack:
                    parent_end, parent = stack[-1]
                    parent.self_s -= (min(end, parent_end) - start) * 1e-9
                s = out.setdefault(name, SpanSum())
                s.count += 1
                s.total_s += (end - start) * 1e-9
                s.self_s += (end - start) * 1e-9
                for key, value in stats:
                    if isinstance(value, (int, float)) and \
                            not isinstance(value, bool):
                        s.counters[key] = s.counters.get(key, 0) + value
                stack.append((end, s))
    return out


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def spans(run) -> dict | None:
    """The run's ``{span name: SpanSum}``, parsed once per trace file."""
    if not run.trace or not run.workdir:
        return None
    files = glob.glob(os.path.join(run.workdir, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not files:
        return None
    return _reduce_file(max(files, key=os.path.getmtime))


def find(run, name: str) -> SpanSum | None:
    sums = spans(run)
    return sums.get(name) if sums else None


def per_unit(run, name: str, unit: str) -> float | None:
    """Self seconds of span ``name`` over the count of the benchmark's
    ``unit`` spans (``bench.save``, ``bench.restore``, ``bench.read``)."""
    units = run.spans.get(unit)
    s = find(run, name) if units else None
    return s.self_s / len(units) if s else None


def gbps(run, name: str) -> float | None:
    """Summed ``bytes`` counter of span ``name`` over its summed self
    time, in GB/s."""
    s = find(run, name)
    if not s or s.self_s <= 0 or not s.counters.get("bytes"):
        return None
    return s.counters["bytes"] / s.self_s / 1e9
