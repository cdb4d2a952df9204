"""Reduce a profiler trace of the measured window to device busy time,
the device operations that took most time, and the idle gaps attributed
to the benchmark's own spans (``bench.*`` host annotations).

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:`` plane; a plane with no
such line contributes all its lines), averaged over the devices.  An idle
gap is any stretch of the window in which no operation ran on a device; it
is cut where the ``bench.*`` host spans begin and end, and each piece is
attributed to the innermost span over it, or to ``unattributed``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


def union_length(intervals) -> tuple:
    """(total length, merged intervals) of ``[(start, end), ...]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                 # averaged over the devices
    window_s: float
    devices: int
    device_ops: list              # [[name, seconds], ...], longest first
    idle_gaps: list               # [[span name, seconds], ...], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(name: str) -> str:
    """An HLO instruction's name, without its text (``%fusion.80 = f32[..]
    fusion(..)`` -> ``fusion.80``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _innermost(spans, starts, t: float) -> str:
    """Name of the shortest span that covers ``t`` (spans sorted by start;
    the benchmark's spans nest a few deep at most)."""
    best = None
    k = bisect.bisect_right(starts, t)
    for s, e, name in reversed(spans[max(0, k - 16):k]):
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "unattributed"


def reduce_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")
                     and not p.name.startswith("/device:CPU")]
    spans = []                    # (start, end, name) of bench.* spans
    host_lo, host_hi = [], []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for name, s, e in _events(line):
                host_lo.append(s)
                host_hi.append(e)
                if name.startswith(SPAN_PREFIX):
                    spans.append((s, e, name))
    per_device = []
    op_time: dict = {}
    lo, hi = [], []
    for p in device_planes:
        lines = [ln for ln in p.lines if ln.name == OPS_LINE] or list(p.lines)
        iv = []
        for line in lines:
            for name, s, e in _events(line):
                iv.append((s, e))
                key = op_name(name)
                op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
        if iv:
            busy, merged = union_length(iv)
            per_device.append((busy, merged))
            lo.append(merged[0][0])
            hi.append(merged[-1][1])
    if not per_device:
        raise ValueError("the trace holds no device operation")
    # the window is what the trace covers: host spans and device ops alike
    t0 = min(lo + host_lo)
    t1 = max(hi + host_hi)
    window_s = (t1 - t0) * 1e-9
    busy_s = sum(b for b, _ in per_device) / len(per_device) * 1e-9
    spans.sort()
    starts = [s for s, _, _ in spans]
    bounds = sorted({x for s, e, _ in spans for x in (s, e)})
    gaps: dict = {}
    for _, merged in per_device:
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            # cut the gap where spans begin or end, so that each piece lies
            # under one innermost span
            i, j = bisect.bisect_right(bounds, g0), bisect.bisect_left(
                bounds, g1)
            cuts = [g0] + bounds[i:j] + [g1]
            for a, b in zip(cuts, cuts[1:]):
                name = _innermost(spans, starts, 0.5 * (a + b))
                gaps[name] = gaps.get(name, 0.0) \
                    + (b - a) * 1e-9 / len(per_device)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(busy_s=busy_s, window_s=window_s,
                        devices=len(per_device),
                        device_ops=[[n, s] for n, s in top_ops],
                        idle_gaps=[[n, s] for n, s in top_gaps])


def reduce_trace_dir(trace_dir: str) -> TraceSummary:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return reduce_profile(ProfileData.from_file(max(files,
                                                    key=os.path.getmtime)))
