"""Dataset session object: symmetric plan/execute I/O in both directions.

A :class:`Dataset` is the single handle on a dataset directory for writers
*and* readers — ``Dataset.create`` starts a new container, ``Dataset.open``
attaches to an existing one, and both directions go through the same
plan/engine split:

* **write** — ``plan_write`` turns a :class:`~repro.core.layouts.LayoutPlan`
  into a :class:`~repro.io.planner.WritePlan` (append offsets + alignment
  assigned at plan time); ``write_planned`` assembles chunk buffers and
  hands the plan to the session's :class:`~repro.io.engine.IOEngine`.  The
  index is committed only after every extent landed, so a crashed write
  leaves ``index.json`` unwritten (log-structured recovery: data extents
  without index entries are dead space, never corruption).
* **read** — ``plan_read`` probes the variable's spatial chunk index and
  emits a :class:`~repro.io.planner.ReadPlan` (paper §3.3: locate all
  intersecting chunks, linearize); ``read_planned`` replays it through the
  engine.  Decomposed/pattern reads share one index probe across all reader
  threads and schemes.

Engines (``memmap`` / ``pread`` / ``overlapped``, see
:mod:`repro.io.engine`) are interchangeable per session or per call, and
``engine="auto"`` defers the choice to plan-execution time: the session
loads (or micro-probes and persists, as ``calibration.json``) an
:class:`~repro.core.cost_model.EngineCalibration` for its storage target
and asks :func:`~repro.core.cost_model.choose_engine` to pick an engine and
queue depth from the plan's shape (groups, runs, bytes).  The decision —
which engine ran and why — is recorded in ``ReadStats.engine`` /
``ReadStats.engine_reason`` (and the write-side ``WriteStats`` twins).
Stats also expose the *structural* costs (chunks touched, contiguous byte
runs == seeks on cold storage, coalesced groups, bytes) alongside measured
wall time, so layout effects are visible even when the page cache hides
device seeks.

Two feedback loops close over those stats (ISSUE 4):

* **Access telemetry** — ``read`` / ``read_decomposed`` / ``read_pattern``
  append a compact pattern fingerprint to ``access_log.json`` next to
  ``index.json`` (see :mod:`repro.core.policy`); ``reorganize(...,
  layout="auto")`` asks the :class:`~repro.core.policy.LayoutPolicy` built
  from that log which target layout the *observed* pattern mix favors.
* **Recalibrate-on-drift** — each ``engine="auto"`` plan's predicted
  seconds are compared with the measured seconds; after
  :data:`~repro.core.cost_model.DRIFT_TRIP_COUNT` consecutive plans off by
  more than 2x, ``calibration.json`` is invalidated and the next auto call
  re-probes the storage.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Mapping, Sequence

import numpy as np

from ..core.blocks import Block
from ..core.codecs import available_codecs, encode
from ..core.cost_model import (CalibrationDrift, EngineCalibration,
                               EngineChoice, choose_engine,
                               invalidate_calibration, storage_calibration)
from ..core.layouts import ChunkPlan, LayoutPlan
from ..core.policy import AccessLog, AccessRecord, LayoutPolicy
from ..core.read_patterns import best_decompositions, decompose_region
from ..core.cost_model import observe_reorg_overhead
from ..core.spans import span
from .engine import (IOEngine, SubfileStore, WriteStats, assemble_chunk,
                     get_engine, resolve_engine, scatter_row)
from .format import ChunkRecord, DatasetIndex, INDEX_NAME, extent_checksum
from .patterns import resolve_pattern
from .planner import ReadPlan, WritePlan, build_read_plan, build_write_plan

__all__ = ["ReadStats", "Dataset", "reorganize", "choose_reorg_layout"]


@dataclasses.dataclass
class ReadStats:
    seconds: float = 0.0
    bytes_read: int = 0
    chunks_touched: int = 0
    runs: int = 0                 # contiguous byte runs (cold-cache seeks)
    groups: int = 0               # coalesced grouped reads actually issued
    probe_seconds: float = 0.0    # spatial-index lookup time
    plan_seconds: float = 0.0     # extent planning time
    engine: str = ""              # engine spec that executed the plan
    engine_reason: str = ""       # auto decision record, or "pinned"
    predicted_seconds: float = 0.0  # cost-model prediction (engine="auto")

    def merge(self, other: "ReadStats") -> None:
        self.bytes_read += other.bytes_read
        self.chunks_touched += other.chunks_touched
        self.runs += other.runs
        self.groups += other.groups
        self.probe_seconds += other.probe_seconds
        self.plan_seconds += other.plan_seconds
        self.predicted_seconds += other.predicted_seconds
        if not self.engine:
            self.engine = other.engine
            self.engine_reason = other.engine_reason
        elif other.engine:
            if other.engine != self.engine:
                # sub-reads resolved to different engines; every sub-read's
                # rationale stays visible (a uring -> overlapped fallback on
                # one variable must survive the merge), joined and deduped
                self.engine = "mixed"
                self._merge_reason("per-plan auto decisions diverged")
            self._merge_reason(other.engine_reason)

    def _merge_reason(self, other_reason: str) -> None:
        parts = [p for p in self.engine_reason.split("; ") if p]
        for p in other_reason.split("; "):
            if p and p not in parts:
                parts.append(p)
        self.engine_reason = "; ".join(parts)

    @property
    def read_gbps(self) -> float:
        return self.bytes_read / max(self.seconds, 1e-12) / 1e9


class Dataset:
    """Read/write session on a dataset directory.

    ``Dataset(dir)`` attaches to an existing dataset (read paths work
    immediately, writes append); ``Dataset.create(dir)`` starts an empty
    one.  ``engine`` is an engine name (``"memmap"``, ``"pread"``,
    ``"overlapped"``/``"overlapped:<depth>"``, or ``"auto"``) or an
    :class:`~repro.io.engine.IOEngine` instance.  With ``"auto"`` the
    session picks an engine *per plan* from the plan's shape and a storage
    calibration (loaded from ``calibration.json`` next to ``index.json``,
    micro-probed and persisted on first use; ``calibration`` injects one
    explicitly, e.g. for tests or read-only media).
    """

    def __init__(self, dirpath: str, engine: str | IOEngine = "memmap", *,
                 create: bool = False, index: DatasetIndex | None = None,
                 calibration: EngineCalibration | None = None,
                 telemetry: bool = True, clock=None):
        self.dirpath = dirpath
        self._auto = isinstance(engine, str) and engine == "auto"
        self._engine = None
        self._fallback_reason = ""
        self._calibration = calibration
        # drift tracking only applies to calibrations this session loaded or
        # probed itself — an explicitly injected calibration is pinned
        self._drift_enabled = calibration is None
        self._drift = CalibrationDrift()
        self._drift_lock = threading.Lock()
        self._telemetry = telemetry
        #: time source stamping access records (and the log's TTL check);
        #: replay injects a deterministic clock so two replays of one
        #: trace produce bit-identical telemetry
        self._clock = clock if clock is not None else time.time
        self._trace = None            # attached TraceRecorder, if capturing
        self._access_log: AccessLog | None = None
        self._index_stat = None
        if index is not None:
            self.index = index
        elif create:
            self.index = DatasetIndex()
        else:
            self.index = DatasetIndex.load(dirpath)
            self._index_stat = self._stat_index()
        if create or index is not None:
            os.makedirs(dirpath, exist_ok=True)
        if not self._auto:
            # after makedirs: the kernel-bypass feature probes (odirect is
            # per-filesystem) need the directory to exist.  A degraded
            # spec ("uring" without io_uring, "odirect" on tmpfs) resolves
            # to its fallback engine here, and every stats record this
            # session emits carries the reason.
            self._engine, self._fallback_reason = \
                resolve_engine(engine, dirpath=dirpath)
        self._store = SubfileStore(dirpath)
        self._lock = threading.Lock()     # index mutation + append cursor
        self._cal_lock = threading.Lock()  # one probe even with many workers
        self._cursor: dict | None = None  # subfile -> first free byte

    # -- session management --------------------------------------------------
    @classmethod
    def create(cls, dirpath: str, engine: str | IOEngine = "memmap",
               calibration: EngineCalibration | None = None,
               telemetry: bool = True, clock=None) -> "Dataset":
        """Start a new (empty) dataset. ``index.json`` is not written until
        the first successful :meth:`write_planned` commit."""
        return cls(dirpath, engine, create=True, calibration=calibration,
                   telemetry=telemetry, clock=clock)

    @classmethod
    def open(cls, dirpath: str, engine: str | IOEngine = "memmap",
             calibration: EngineCalibration | None = None,
             telemetry: bool = True, clock=None) -> "Dataset":
        """Attach to an existing dataset directory.  ``telemetry=False``
        turns off access-log appends (mechanical bulk reads — e.g. the
        source side of :func:`reorganize` — must not pollute the pattern
        history the layout policy learns from)."""
        return cls(dirpath, engine, calibration=calibration,
                   telemetry=telemetry, clock=clock)

    @property
    def engine(self) -> str:
        """Name of the session's default engine (``"auto"`` when the choice
        is deferred to plan-execution time)."""
        return "auto" if self._auto else self._engine.name

    @property
    def generation(self) -> int:
        """The index's layout generation — bumped every time a
        reorganization republishes relocated extents (see
        :class:`~repro.io.format.DatasetIndex.generation`)."""
        return self.index.generation

    def _stat_index(self):
        """Cheap identity of the on-disk ``index.json`` (atomic replace
        changes the inode, appends change mtime/size)."""
        try:
            st = os.stat(os.path.join(self.dirpath, INDEX_NAME))
        except OSError:
            return None
        return (st.st_ino, st.st_mtime_ns, st.st_size)

    def refresh(self) -> bool:
        """Reload ``index.json`` iff another session republished it (a
        reorganization commit, or a writer's append flush).  Returns True
        when the index was reloaded — callers holding plans or decision
        caches keyed on ``(generation, len(index.chunks))`` must drop the
        stale entries.  Sessions created around an in-memory index (fleet
        workers, tests) never refresh: their index IS the truth."""
        if self._index_stat is None:
            return False
        st = self._stat_index()
        if st is None or st == self._index_stat:
            return False
        with self._lock:
            self.index = DatasetIndex.load(self.dirpath)
            self._index_stat = st
            self._cursor = None
        # subfiles may have grown past any cached memmap's length, and an
        # in-place reorg appended extents the old maps cannot see
        self._store.invalidate_all()
        return True

    def calibration(self) -> EngineCalibration:
        """The session's storage calibration (lazy: ``calibration.json`` if
        fresh, the per-device cache, else a micro-probe that is persisted
        next to ``index.json``).  Thread-safe: concurrent first users (e.g.
        staging workers) share one probe."""
        if self._calibration is None:
            with self._cal_lock:
                if self._calibration is None:
                    self._calibration = storage_calibration(self.dirpath)
        return self._calibration

    @property
    def access_log(self) -> AccessLog:
        """The dataset's persistent access log (``access_log.json``) — the
        pattern history :class:`~repro.core.policy.LayoutPolicy` scores
        candidate layouts against.  Appends are batched (a hot read must
        not pay a full ring rewrite); :meth:`flush` / :meth:`close` drain
        the buffer."""
        if self._access_log is None:
            self._access_log = AccessLog(self.dirpath, flush_every=8,
                                         clock=self._clock)
        return self._access_log

    # -- trace capture -------------------------------------------------------
    def attach_trace(self, recorder) -> None:
        """Attach a :class:`~repro.io.trace.TraceRecorder`: every read
        (plain / decomposed / pattern / served), write commit and — via
        the explicit ``trace=`` parameters — staging submit, reorganize
        and checkpoint op is journaled losslessly to its sidecar, on top
        of (never instead of) the ring-bounded access log."""
        self._trace = recorder

    def detach_trace(self):
        """Stop capturing; returns the recorder that was attached."""
        rec, self._trace = self._trace, None
        return rec

    def _record_access(self, var: str, region: Block, stats: "ReadStats",
                       kind: str = "read", tenant: str = "",
                       trace_kind: str | None = None,
                       trace_params: dict | None = None) -> None:
        """Append one pattern fingerprint; telemetry never breaks a read.
        ``tenant`` namespaces the record (multi-tenant read service) — the
        aggregate mix still feeds the layout policy, but per-tenant slices
        stay exportable via ``AccessLog.export_prior(tenant=...)``.
        ``trace_kind``/``trace_params`` name the event an attached trace
        recorder journals (capture is lossless and schema-checked, so
        unlike the ring append it raises on misuse)."""
        if not self._telemetry:
            return
        try:
            with span("repro.read.telemetry"):
                self.access_log.append(AccessRecord.from_stats(
                    var, kind, region, self.index.var_shape(var), stats,
                    tenant=tenant, ts=self._clock()))
        except Exception:               # noqa: BLE001 — telemetry only
            pass
        if self._trace is not None:
            self._trace.record_read(trace_kind or kind, var, region, stats,
                                    tenant=tenant, **(trace_params or {}))

    def _note_drift(self, choice: EngineChoice | None,
                    measured_seconds: float) -> None:
        """Recalibrate-on-drift: after persistently divergent auto plans,
        drop the calibration so the next auto decision re-probes."""
        if choice is None or not self._drift_enabled:
            return
        with self._drift_lock:
            tripped = self._drift.note(choice.predicted_seconds,
                                       measured_seconds)
        if tripped:
            invalidate_calibration(self.dirpath)
            with self._cal_lock:
                self._calibration = None

    def _resolve_engine(self, override, *, groups: int, runs: int,
                        bytes_moved: int, span_bytes: int,
                        direction: str) -> tuple:
        """Resolve a per-call ``engine`` override (or the session default)
        to an engine instance; returns ``(engine, EngineChoice | None,
        pinned_reason)``.  ``"auto"`` — per call or as the session default
        — consults the cost model with this plan's shape.  Pinned specs
        that the kernel/filesystem cannot honor degrade through
        :func:`repro.io.engine.resolve_engine`, and ``pinned_reason``
        carries the fallback explanation into the stats record."""
        spec = override if override is not None else \
            ("auto" if self._auto else self._engine)
        if isinstance(spec, str) and spec == "auto":
            choice = choose_engine(self.calibration(), groups=groups,
                                   runs=runs, bytes_moved=bytes_moved,
                                   span_bytes=span_bytes,
                                   direction=direction)
            eng, fb = resolve_engine(choice.engine, dirpath=self.dirpath)
            if fb:
                # a calibration probed elsewhere promised support this
                # host lacks (copied calibration.json): degrade, but keep
                # the decision record honest about what actually ran
                choice = dataclasses.replace(choice, engine=eng.name,
                                             reason=f"{choice.reason}; "
                                                    f"{fb}")
            return eng, choice, ""
        if override is not None:
            eng, fb = resolve_engine(spec, dirpath=self.dirpath)
            return eng, None, fb or "pinned"
        return self._engine, None, self._fallback_reason or "pinned"

    def flush(self) -> None:
        """Persist ``index.json`` (atomic replace) and any buffered
        access-log records."""
        self.index.save(self.dirpath)
        if self._access_log is not None:
            self._access_log.flush()

    def close(self) -> None:
        if self._access_log is not None:
            self._access_log.flush()
        self._store.close()

    # -- write path ----------------------------------------------------------
    def _cursor_dict(self) -> dict:
        """subfile -> first free byte, log-structured append (lazy-built from
        the index, then maintained by :meth:`plan_write`). Caller holds the
        lock."""
        if self._cursor is None:
            cur: dict = {}
            for rec in self.index.chunks:
                end = rec.offset + rec.nbytes
                if end > cur.get(rec.subfile, 0):
                    cur[rec.subfile] = end
            self._cursor = cur
        return self._cursor

    def plan_write(self, var: str, layout: LayoutPlan, dtype,
                   align: int | None = None) -> WritePlan:
        """Plan (but do not execute) the append of ``var`` under ``layout``.

        Reserves the extents immediately: concurrent planners (staging
        workers) get disjoint offsets even before either plan commits.
        """
        with self._lock:
            cursor = self._cursor_dict()
            plan = build_write_plan(layout, var, dtype, align=align,
                                    base_offsets=cursor)
            for sf, end in plan.file_sizes.items():
                if end > cursor.get(sf, 0):
                    cursor[sf] = end
        return plan

    def write_planned(self, plan: WritePlan,
                      data: Mapping[int, np.ndarray], *,
                      engine: str | IOEngine | None = None,
                      fsync: bool = False, flush: bool = True,
                      codec: str = "none",
                      encoded: Sequence[np.ndarray] | None = None
                      ) -> WriteStats:
        """Execute a write plan: assemble each chunk from its source blocks,
        run the engine over the extent groups, then commit the records.
        Returns :class:`~repro.io.engine.WriteStats` (including which engine
        executed the plan and, under ``"auto"``, why).

        ``codec``/``encoded`` is the compressed-write contract: because the
        plan's append offsets depend on the STORED sizes, encoding happens
        *before* planning — the caller passes the pre-encoded extent
        buffers (``layout.chunks`` order, one ``uint8`` array per chunk;
        the plan was built with ``sizes=``) and the codec they carry.  The
        committed records then store the codec name, the logical size, and
        a checksum over the *stored* (encoded) bytes — the same bytes the
        journal/kill-matrix validation path re-reads.
        """
        if codec != "none" and encoded is None:
            raise ValueError("codec != 'none' requires pre-encoded buffers "
                             "(use Dataset.write(..., codec=...))")
        eng, choice, pinned_reason = self._resolve_engine(
            engine, groups=plan.num_groups, runs=plan.num_chunks,
            bytes_moved=plan.bytes_total, span_bytes=plan.span_bytes,
            direction="write")
        t_start = time.perf_counter()

        t0 = time.perf_counter()
        if encoded is not None:
            buffers = [encoded[int(cid)] for cid in plan.chunk_ids]
        else:
            with span("repro.write.assemble"):
                buffers = [assemble_chunk(plan.layout.chunks[int(cid)],
                                          data, plan.dtype)
                           for cid in plan.chunk_ids]
        assemble_seconds = time.perf_counter() - t0

        t0 = time.perf_counter()
        with span("repro.write.engine", bytes=plan.bytes_total):
            for sf, size in plan.file_sizes.items():
                self._store.ensure_size(sf, size)
            eng.write_plan(plan, buffers, self._store)
            if fsync:
                self._store.fsync()
        write_seconds = time.perf_counter() - t0

        # commit: records enter the index only after every extent landed
        with self._lock, span("repro.write.commit"):
            if plan.var not in self.index.variables:
                self.index.add_variable(plan.var, plan.global_shape,
                                        plan.dtype, plan.strategy)
            for row in np.argsort(plan.chunk_ids):   # original layout order
                lbytes = None
                if codec != "none":
                    lbytes = int((plan.chunk_his[row]
                                  - plan.chunk_los[row]).prod()) \
                        * plan.dtype.itemsize
                self.index.chunks.append(ChunkRecord(
                    var=plan.var, lo=tuple(int(v) for v in plan.chunk_los[row]),
                    hi=tuple(int(v) for v in plan.chunk_his[row]),
                    subfile=int(plan.subfiles[row]),
                    offset=int(plan.file_lo[row]),
                    nbytes=int(plan.nbytes[row]),
                    checksum=extent_checksum(
                        np.ascontiguousarray(buffers[row])),
                    codec=codec, lbytes=lbytes))
            cursor = self._cursor_dict()
            for sf, end in plan.file_sizes.items():   # plans built directly
                if end > cursor.get(sf, 0):
                    cursor[sf] = end
            self.index.num_subfiles = max(self.index.num_subfiles,
                                          len(cursor))
            if flush:
                self.flush()

        self._note_drift(choice, write_seconds)
        wstats = WriteStats(assemble_seconds=assemble_seconds,
                            write_seconds=write_seconds,
                            total_seconds=time.perf_counter() - t_start,
                            bytes_written=int(plan.bytes_total),
                            num_extents=plan.num_chunks,
                            num_subfiles=len(plan.file_sizes),
                            groups=plan.num_groups,
                            plan_seconds=plan.plan_seconds,
                            engine=choice.engine if choice else eng.name,
                            engine_reason=choice.reason if choice
                            else pinned_reason,
                            predicted_seconds=choice.predicted_seconds
                            if choice else 0.0)
        if self._trace is not None and plan.num_chunks:
            extra = {"codec": codec} if codec != "none" else {}
            self._trace.record_write("write", plan, wstats, **extra)
        return wstats

    def write(self, var: str, layout: LayoutPlan, dtype,
              data: Mapping[int, np.ndarray], *,
              align: int | None = None, fsync: bool = False,
              codec: str = "none") -> WriteStats:
        """Plan + execute in one call (the common non-staged case).
        Argument order mirrors :meth:`plan_write`.

        ``codec`` compresses every extent with the named codec from
        :mod:`repro.core.codecs` before planning (append offsets depend on
        the encoded sizes); the records carry the codec and logical size
        (index v4) and reads decode transparently through every engine.
        """
        if codec == "none":
            return self.write_planned(self.plan_write(var, layout, dtype,
                                                      align=align),
                                      data, fsync=fsync)
        dtype = np.dtype(dtype)
        t0 = time.perf_counter()
        with span("repro.write.assemble"):
            enc = [np.frombuffer(
                       encode(codec, np.ascontiguousarray(
                           assemble_chunk(cp, data, dtype))),
                       dtype=np.uint8)
                   for cp in layout.chunks]
        encode_seconds = time.perf_counter() - t0
        sizes = np.asarray([b.nbytes for b in enc], dtype=np.int64)
        with self._lock:
            cursor = self._cursor_dict()
            plan = build_write_plan(layout, var, dtype, align=align,
                                    base_offsets=cursor, sizes=sizes)
            for sf, end in plan.file_sizes.items():
                if end > cursor.get(sf, 0):
                    cursor[sf] = end
        wstats = self.write_planned(plan, data, fsync=fsync,
                                    codec=codec, encoded=enc)
        wstats.assemble_seconds += encode_seconds
        wstats.total_seconds += encode_seconds
        return wstats

    # -- read path -----------------------------------------------------------
    def plan_read(self, var: str, region: Block,
                  candidates: np.ndarray | None = None,
                  coalesce_gap: int = 0) -> ReadPlan:
        """Plan (but do not execute) a region read; see
        :func:`repro.io.planner.build_read_plan`."""
        with span("repro.read.plan"):
            return build_read_plan(self.index, var, region,
                                   candidates=candidates,
                                   coalesce_gap=coalesce_gap)

    def read_planned(self, plan: ReadPlan, out: np.ndarray | None = None,
                     engine: str | IOEngine | None = None,
                     note_drift: bool = True) -> tuple:
        """Execute a read plan. Returns (array, ReadStats); the stats record
        which engine ran and — under ``"auto"`` — the decision rationale.

        ``note_drift=False`` excludes this plan from recalibrate-on-drift
        accounting — concurrent sub-plans (decomposed reads) measure
        bandwidth-contended times that would falsely indict a healthy
        calibration."""
        if out is None:
            out = np.empty(plan.region.shape, dtype=plan.dtype)
        eng, choice, pinned_reason = self._resolve_engine(
            engine, groups=plan.num_groups, runs=plan.runs,
            bytes_moved=plan.bytes_needed, span_bytes=plan.span_bytes,
            direction="read")
        stats = ReadStats(chunks_touched=plan.num_chunks, runs=plan.runs,
                          groups=plan.num_groups,
                          bytes_read=plan.bytes_needed,
                          probe_seconds=plan.probe_seconds,
                          plan_seconds=plan.plan_seconds,
                          engine=choice.engine if choice else eng.name,
                          engine_reason=choice.reason if choice
                          else pinned_reason,
                          predicted_seconds=choice.predicted_seconds
                          if choice else 0.0)
        t0 = time.perf_counter()
        with span("repro.read.engine", bytes=plan.bytes_needed) as s:
            split = eng.read_plan(plan, self._store, out)
            s.set_metadata(split_bytes=split or 0)
        stats.seconds = time.perf_counter() - t0
        if note_drift:
            self._note_drift(choice, stats.seconds)
        return out, stats

    def read_super_planned(self, sp, outs: Sequence[np.ndarray] | None = None,
                           engine: str | IOEngine | None = None) -> tuple:
        """Execute a :class:`~repro.serve.coalesce.SuperPlan`: ONE engine
        gather over the merged byte spans, then scatter slices of the flat
        fetch buffer into every member's output array (no further I/O).

        Returns ``(outs, fetch_stats, member_stats)`` — the per-member
        arrays (region-shaped, same bytes as independent :meth:`read`
        calls), the :class:`ReadStats` of the shared gather, and one
        ``ReadStats`` per member whose structural fields come from the
        member's own plan and whose ``seconds`` apportions the batch wall
        time by payload bytes."""
        t0 = time.perf_counter()
        flat = np.empty(sp.fetch_bytes, dtype=np.uint8)
        fetch = sp.fetch_plan()
        _, fstats = self.read_planned(fetch, out=flat, engine=engine,
                                      note_drift=False)
        if outs is None:
            outs = [np.empty(p.region.shape, p.dtype) for p in sp.members]
        programs = sp.scatter_programs()
        for plan, span_of, out, prog in zip(sp.members, sp.member_span,
                                            outs, programs):
            fl, ol, nb, fallback = prog
            if len(fl) and out.flags.c_contiguous:
                # coalesced fast path: whole-segment flat byte copies
                dst = out.reshape(-1).view(np.uint8)
                for i in range(len(fl)):
                    o, f, n = int(ol[i]), int(fl[i]), int(nb[i])
                    dst[o:o + n] = flat[f:f + n]
                rows = fallback
            else:
                rows = range(plan.num_chunks)
            if len(rows):
                base = sp.span_out[span_of] - sp.span_lo[span_of]
                for row in rows:
                    lo = int(plan.file_lo[row] + base[row])
                    hi = int(plan.file_hi[row] + base[row])
                    scatter_row(plan, row, flat[lo:hi], out)
        wall = time.perf_counter() - t0
        fstats.probe_seconds += sp.probe_seconds
        fstats.plan_seconds += sp.plan_seconds
        total = max(1, sum(int(p.bytes_needed) for p in sp.members))
        member_stats = []
        for plan in sp.members:
            st = ReadStats(seconds=wall * plan.bytes_needed / total,
                           bytes_read=plan.bytes_needed,
                           chunks_touched=plan.num_chunks, runs=plan.runs,
                           groups=plan.num_groups,
                           engine=fstats.engine,
                           engine_reason=fstats.engine_reason)
            member_stats.append(st)
        return outs, fstats, member_stats

    def read(self, var: str, region: Block,
             candidates: np.ndarray | None = None,
             engine: str | IOEngine | None = None) -> tuple:
        """Assemble ``region`` of ``var``. Returns (array, ReadStats)."""
        with span("repro.read") as s:
            plan = self.plan_read(var, region, candidates=candidates)
            arr, stats = self.read_planned(plan, engine=engine)
            stats.seconds += plan.probe_seconds + plan.plan_seconds
            self._record_access(var, region, stats, trace_kind="read")
            s.set_metadata(bytes=stats.bytes_read)
        return arr, stats

    def read_decomposed(self, var: str, region: Block,
                        scheme: Sequence[int],
                        materialize: bool = True,
                        candidates: np.ndarray | None = None,
                        engine: str | IOEngine | None = None,
                        log_access: bool = True) -> ReadStats:
        """Concurrent read of ``region`` split over ``prod(scheme)`` readers
        (threads). Returns aggregated stats; ``seconds`` is wall time.

        The spatial index is probed once for the whole region; per-reader
        sub-plans narrow that candidate set vectorized instead of re-scanning
        per thread.  ``log_access=False`` suppresses the telemetry record —
        used by :meth:`read_pattern`, whose best-of-schemes sweep is one
        logical access, not ``len(schemes)`` of them.
        """
        parts = decompose_region(region, scheme)
        agg = ReadStats()

        t0 = time.perf_counter()
        if candidates is None:
            tp = time.perf_counter()
            candidates = self.index.spatial_index(var).query(region.lo,
                                                             region.hi)
            agg.probe_seconds += time.perf_counter() - tp
        plans = [build_read_plan(self.index, var, p, candidates=candidates)
                 for p in parts]

        concurrent = len(plans) > 1

        def one(plan: ReadPlan):
            _, st = self.read_planned(plan, engine=engine,
                                      note_drift=not concurrent)
            return st

        if not concurrent:
            results = [one(plans[0])]
        else:
            with ThreadPoolExecutor(max_workers=min(32, len(plans))) as ex:
                results = list(ex.map(one, plans))
        agg.seconds = time.perf_counter() - t0
        for st in results:
            agg.merge(st)
        if log_access:
            self._record_access(
                var, region, agg, trace_kind="read_decomposed",
                trace_params={"scheme": [int(k) for k in scheme]})
        return agg

    def read_pattern(self, var: str, pattern: str,
                     num_readers: int = 1,
                     slab_thickness: int | None = None,
                     engine: str | IOEngine | None = None) -> tuple:
        """Read a Fig.-6 pattern with the best decomposition for
        ``num_readers`` (the paper reports best-of over schemes).
        Returns (best_scheme, ReadStats of best).

        One index probe serves the whole best-of-schemes sweep: every scheme
        shares the region's candidate set.
        """
        shape = self.index.var_shape(var)
        region = resolve_pattern(shape, pattern, slab_thickness)
        tp = time.perf_counter()
        candidates = self.index.spatial_index(var).query(region.lo, region.hi)
        probe_seconds = time.perf_counter() - tp
        best = None
        for scheme in best_decompositions(num_readers, ndim=len(shape)):
            st = self.read_decomposed(var, region, scheme,
                                      candidates=candidates, engine=engine,
                                      log_access=False)
            if best is None or st.seconds < best[1].seconds:
                best = (scheme, st)
        # the one shared index probe is attributed to the reported best;
        # the whole best-of-schemes sweep is ONE logical access pattern
        best[1].probe_seconds += probe_seconds
        trace_params = {"pattern": pattern, "num_readers": int(num_readers),
                        "best_scheme": [int(k) for k in best[0]]}
        if slab_thickness is not None:
            trace_params["slab_thickness"] = int(slab_thickness)
        self._record_access(var, region, best[1], trace_kind="read_pattern",
                            trace_params=trace_params)
        return best

    # -- integrity -----------------------------------------------------------
    def verify_checksums(self, var: str | None = None) -> tuple:
        """Re-read every stored extent that carries a format-v3 CRC and
        validate it.  Returns ``(checked, bad)`` — the number of records
        validated and the list of record positions (rows into
        ``index.chunks``) whose stored bytes no longer match.  Records
        without a checksum (v2 indexes, pre-v3 writers) are skipped, so a
        mixed-history dataset verifies what it can."""
        checked = 0
        bad = []
        for i, rec in enumerate(self.index.chunks):
            if rec.checksum is None or (var is not None and rec.var != var):
                continue
            fd = self._store.fd(rec.subfile)
            buf = os.pread(fd, rec.nbytes, rec.offset)
            checked += 1
            if len(buf) != rec.nbytes or extent_checksum(buf) != rec.checksum:
                bad.append(i)
        return checked, bad


def sample_codec_ratios(src: Dataset, var: str, *,
                        max_bytes: int = 4 << 20) -> dict:
    """Measure each available codec's stored/logical size ratio on a sample
    of ``var``'s actual data (the first stored chunk, capped at
    ``max_bytes`` along its leading axis).  The ratios feed
    :meth:`~repro.core.policy.LayoutPolicy.choose_layout`'s
    ``codec_ratios`` so the policy scores *measured* compressibility, not a
    guess.  Returns ``{}`` when the variable has no extents or every codec
    fails — callers degrade to raw-only scoring."""
    rows = src.index.var_rows(var)
    if rows.n == 0:
        return {}
    lo = np.array(rows.los[0], dtype=np.int64)
    hi = np.array(rows.his[0], dtype=np.int64)
    itemsize = np.dtype(src.index.var_dtype(var)).itemsize
    vol = int((hi - lo).prod()) * itemsize
    if vol > max_bytes and hi[0] - lo[0] > 1:
        keep = max(1, int((hi[0] - lo[0]) * max_bytes // vol))
        hi = hi.copy()
        hi[0] = lo[0] + keep
    try:
        arr, _ = src.read(var, Block(tuple(int(v) for v in lo),
                                     tuple(int(v) for v in hi)))
    except (OSError, ValueError, KeyError):
        return {}
    raw = np.ascontiguousarray(arr)
    if raw.nbytes == 0:
        return {}
    ratios = {}
    for name in available_codecs():
        if name == "none":
            continue
        try:
            ratios[name] = len(encode(name, raw)) / raw.nbytes
        except Exception:
            continue
    return ratios


def choose_reorg_layout(src: Dataset, var: str, *,
                        align: int | None = None,
                        policy: LayoutPolicy | None = None,
                        prior: str | None = None,
                        expected_reads: float | None = None,
                        codec_ratios: dict | None = None,
                        now: float | None = None):
    """The ``layout="auto"`` decision both :func:`reorganize` and
    :func:`repro.distributed.reorg.distributed_reorganize` make: ask the
    source dataset's :class:`~repro.core.policy.LayoutPolicy` (its access
    log + calibration + learned reorg overhead) which target layout the
    observed pattern mix favors, charging each candidate the cost of
    gathering out of the source's *current* extents.  Returns the
    :class:`~repro.core.policy.PolicyDecision`."""
    pol = policy if policy is not None else \
        LayoutPolicy.for_dataset(src.dirpath)
    if prior is not None:
        pol = pol.with_prior(prior)
    rows = src.index.var_rows(var)
    blocks = [Block(tuple(int(v) for v in rows.los[i]),
                    tuple(int(v) for v in rows.his[i]),
                    owner=int(rows.subfiles[i]), block_id=i)
              for i in range(rows.n)]
    return pol.choose_layout(var, blocks, src.index.var_shape(var),
                             num_stagers=max(1, src.index.num_subfiles),
                             align=align, current_extents=rows,
                             expected_reads=expected_reads,
                             codec_ratios=codec_ratios, now=now)


def reorganize(src_dir: str, dst_dir: str, var: str,
               layout: LayoutPlan | str = "auto", *,
               engine: str | IOEngine = "memmap",
               align: int | None = None,
               policy: LayoutPolicy | None = None,
               prior: str | None = None,
               expected_reads: float | None = None,
               now: float | None = None,
               clock=None, trace=None) -> tuple:
    """Post-hoc reorganization (paper §5.1): pull each chunk region of the
    new ``layout`` from ``src_dir`` through the read planner and write the
    reorganized dataset to ``dst_dir`` through the write planner.

    ``layout="auto"`` (the default) asks the source dataset's
    :class:`~repro.core.policy.LayoutPolicy` — built from its
    ``access_log.json`` pattern history and persisted calibration — which
    target layout the observed read mix favors.  The decision is
    *lifecycle-aware*: each candidate is charged the cost of gathering its
    chunks out of the source's current extents and writing them, plus
    ``expected_reads`` replays of the observed mix (default: derived from
    the history's decayed record mass).  With no usable history the policy
    degrades to the dimension-aware default scheme.  Either way the
    decision (scheme, scores, ``reason``) is persisted in the destination's
    ``index.json`` under ``attrs["policy"][var]``.  ``policy`` injects a
    prepared policy instead (tests, cross-dataset history); ``prior``
    points at a previous run's ``access_log.json`` / exported prior /
    directory, seeding the decision when this dataset's own telemetry is
    thin (see :meth:`~repro.core.policy.LayoutPolicy.with_prior`).

    With ``dst_dir == src_dir`` the reorganization happens **in place,
    online**: the new layout's extents are appended past the live ones
    (log-structured — existing extents never move), and the index is then
    republished in one atomic replace with its generation bumped.  A
    concurrent reader holds either the old index (whose extents are
    intact) or the new one — never a torn mix — and generation-keyed plan
    caches (the read service's) detect the commit and drop stale plans.
    Records of *other* variables carry over unchanged.

    Returns ``(read_seconds, Dataset, WriteStats)`` — the returned session
    is open on the destination.

    ``now`` pins the policy's recency-decay reference time and ``clock``
    the destination session's record stamping (deterministic replay);
    ``trace`` journals one ``reorganize`` event — layout request, chosen
    scheme, decision audit — to an attached
    :class:`~repro.io.trace.TraceRecorder` after the commit.
    """
    if isinstance(layout, str) and layout != "auto":
        raise ValueError(f"layout must be a LayoutPlan or 'auto', "
                         f"got {layout!r}")
    in_place = os.path.abspath(src_dir) == os.path.abspath(dst_dir)
    requested = layout if isinstance(layout, str) else {
        "strategy": layout.strategy,
        "chunks": [[[int(v) for v in c.chunk.lo],
                    [int(v) for v in c.chunk.hi], int(c.subfile)]
                   for c in layout.chunks]}
    # the source session's bulk chunk reads are mechanical, not an
    # application access pattern: keep them out of the telemetry
    src = Dataset.open(src_dir, engine=engine, telemetry=False, clock=clock)
    decision = None
    if isinstance(layout, str):
        decision = choose_reorg_layout(src, var, align=align, policy=policy,
                                       prior=prior,
                                       expected_reads=expected_reads,
                                       codec_ratios=sample_codec_ratios(
                                           src, var),
                                       now=now)
        layout = decision.layout
    codec = decision.codec if decision is not None else "none"
    t0 = time.perf_counter()
    data = {}
    synth = []
    engine_seconds = 0.0
    for i, cp in enumerate(layout.chunks):
        arr, st = src.read(var, cp.chunk)
        engine_seconds += st.seconds - st.probe_seconds - st.plan_seconds
        synth.append(Block(cp.chunk.lo, cp.chunk.hi, owner=cp.writer,
                           block_id=i))
        data[i] = arr
    read_seconds = time.perf_counter() - t0
    # rewrite with chunk==source identity
    ident = LayoutPlan(strategy=layout.strategy,
                       global_shape=layout.global_shape,
                       chunks=tuple(ChunkPlan(chunk=b, sources=(b,),
                                              writer=b.owner,
                                              subfile=layout.chunks[i].subfile)
                                    for i, b in enumerate(synth)),
                       num_subfiles=layout.num_subfiles,
                       inter_process_moved=layout.inter_process_moved,
                       intra_node_moved=layout.intra_node_moved)
    dtype = src.index.var_dtype(var)
    if in_place:
        # online in-place republish: the fresh index starts with only the
        # OTHER variables' records (they don't move), the new extents are
        # appended past the current cursor so live readers' old extents
        # stay byte-identical, and write_planned's commit is the atomic
        # index replace that flips readers to the new layout.
        new_index = DatasetIndex(num_subfiles=src.index.num_subfiles,
                                 attrs=dict(src.index.attrs),
                                 generation=src.index.generation + 1)
        for name, meta in src.index.variables.items():
            if name != var:
                new_index.variables[name] = dict(meta)
        for rec in src.index.chunks:
            if rec.var != var:
                new_index.chunks.append(dataclasses.replace(rec))
        with src._lock:
            cursor = dict(src._cursor_dict())
        src.close()
        dst = Dataset(dst_dir, engine=engine, index=new_index, clock=clock)
        dst._cursor = cursor                  # append past the live extents
        wstats = dst.write(var, ident, dtype, data, align=align, codec=codec)
    else:
        src.close()
        dst = Dataset.create(dst_dir, engine=engine, clock=clock)
        # layout lineage: the destination supersedes the source's layout
        dst.index.generation = src.index.generation + 1
        wstats = dst.write(var, ident, dtype, data, align=align, codec=codec)
    if decision is not None:
        dst.index.attrs.setdefault("policy", {})[var] = decision.to_json()
        dst.flush()
    # learned per-chunk reorg overhead: everything the gather loop paid on
    # top of raw engine time (probe, plan, python bookkeeping) per chunk,
    # folded into the source's reorg_stats.json so the NEXT policy decision
    # over it charges a measured constant instead of the static default.
    # Recorded only after the destination committed — a crashed run leaves
    # the source directory byte-identical.
    if len(layout.chunks):
        observe_reorg_overhead(
            src_dir,
            max(0.0, read_seconds - engine_seconds) / len(layout.chunks),
            num_chunks=len(layout.chunks))
    if trace is not None:
        trace.record(
            "reorganize", var=var,
            seconds=read_seconds + wstats.total_seconds,
            engine=wstats.engine, nbytes=wstats.bytes_written,
            dst="" if in_place else os.path.basename(
                os.path.abspath(dst_dir)),
            layout=requested, align=align,
            decision=decision.to_json() if decision is not None else None)
    return read_seconds, dst, wstats
