"""Layout-aware checkpoint manager.

Checkpoints are datasets in the paper's container format; the layout strategy
is a policy knob:
  * ``subfiled_fpp``   — write-optimal: every host logs its shards (ADIOS2
    default; fastest save, fragmented restore);
  * ``merged_process`` — the paper's contribution 1: Berger–Rigoutsos merge
    of each host's shards before writing (near-write-optimal save, far fewer
    chunks on restore);
  * ``merged_node``    — merge across a node group (pod slice);
  * ``reorganized``    — the paper's contribution 2 target layout: regular
    K-way decomposition, read-optimal for elastic restarts (written post-hoc
    or on-the-fly via repro.checkpoint.async_ckpt);
  * ``auto``           — ISSUE 4: per-variable layouts chosen by a
    :class:`~repro.core.policy.LayoutPolicy` from the *restore patterns this
    manager has observed*.  Every restore appends pattern fingerprints to
    ``access_log.json`` at the checkpoint root; the next ``save`` scores
    candidate layouts against that history (elastic restores onto a new
    mesh keep cubic-ish schemes, slice-inspection workloads get slab
    schemes).  With no history yet, the dimension-aware default scheme is
    used and the reason recorded in the manifest.

Restore is resharding-aware: a different target mesh/sharding reads each new
shard as a region query against the stored chunk index.

Both directions execute through the symmetric plan/engine API: save plans
every variable with ``Dataset.plan_write`` (one session per step dir),
restore probes each variable's spatial index once and replays per-shard
:class:`~repro.io.planner.ReadPlan`\\ s via ``read_planned`` —
:class:`RestoreStats` reports the per-variable :class:`~repro.io.reader.
ReadStats` alongside the aggregate, including which engine executed each
variable's plans and why (``engine``/``engine_reason``; useful with
``engine="auto"``, where the choice may differ between a merged save
layout and a fragmented restore pattern).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time
from typing import Mapping, Sequence

import jax
import numpy as np

from ..core.blocks import Block
from ..core.layouts import plan_layout
from ..core.policy import (ACCESS_PRIOR_NAME, AccessLog, AccessRecord,
                           LayoutPolicy)
from ..core.spans import span
from ..io.engine import IOEngine
from ..io.reader import Dataset, ReadStats
from .blocks_map import blocks_from_sharding, flatten_pytree, unflatten_like

__all__ = ["CheckpointManager", "SaveStats", "RestoreStats"]

MANIFEST = "manifest.json"


@dataclasses.dataclass
class SaveStats:
    step: int
    seconds: float
    bytes: int
    num_chunks: int
    num_original_blocks: int


@dataclasses.dataclass
class RestoreStats(ReadStats):
    """Aggregate restore stats plus the per-variable breakdown
    (``per_var[name]`` is that variable's merged :class:`ReadStats`,
    including its single shared index probe)."""

    per_var: dict = dataclasses.field(default_factory=dict)


class CheckpointManager:
    def __init__(self, root: str, strategy: str = "merged_process",
                 devices_per_host: int = 4, hosts_per_node: int = 1,
                 keep: int = 3, reorg_scheme=None, align=None,
                 engine: str | IOEngine = "memmap",
                 policy: LayoutPolicy | None = None,
                 prior: str | None = None, auto_prior: bool = True,
                 clock=None, trace=None):
        self.root = root
        self.strategy = strategy
        self.devices_per_host = devices_per_host
        self.hosts_per_node = hosts_per_node
        self.keep = keep
        self.reorg_scheme = reorg_scheme
        self.align = align
        self.engine = engine
        #: time source for restore-record stamping and the ``auto`` save
        #: decision's recency reference (replay injects a deterministic
        #: clock); ``trace`` journals every save/restore to an attached
        #: :class:`~repro.io.trace.TraceRecorder`
        self._clock = clock if clock is not None else time.time
        self.trace = trace
        os.makedirs(root, exist_ok=True)
        #: restore-pattern history, shared across steps (checkpoint root);
        #: appends are batched — an elastic restore logs one record per
        #: shard and must not pay a ring rewrite each — and flushed once
        #: at the end of every restore.  Every record carries the restore's
        #: engine decision and measured seconds (``RestoreStats`` feed), so
        #: ``strategy="auto"`` weighs expensive restore patterns harder.
        self.access_log = AccessLog(root, flush_every=16, clock=clock)
        #: cross-run prior: a previous run's checkpoint root (or exported
        #: prior file) whose restore history seeds ``strategy="auto"``
        #: saves until this root has restore telemetry of its own
        self.prior = prior
        #: with no explicit ``prior``, scan sibling run roots (directories
        #: next to this one) for the freshest exported ``access_prior.json``
        #: — run N+1 inherits run N's restore patterns without any plumbing
        self.auto_prior = auto_prior
        self._policy = policy

    def discover_prior(self) -> str | None:
        """Auto-discover a cross-run prior: the newest
        ``access_prior.json`` exported by any *sibling* run root (a
        directory next to this manager's root — the layout run launchers
        produce: ``runs/run_001``, ``runs/run_002``, ...).  The manager's
        own root is excluded; no sibling prior means ``None`` (fresh cold
        start).  An explicit ``prior=`` always wins over discovery."""
        own = os.path.abspath(self.root)
        parent = os.path.dirname(own)
        best = None
        try:
            entries = os.listdir(parent)
        except OSError:
            return None
        for e in entries:
            d = os.path.join(parent, e)
            if os.path.abspath(d) == own or not os.path.isdir(d):
                continue
            p = os.path.join(d, ACCESS_PRIOR_NAME)
            try:
                mt = os.path.getmtime(p)
            except OSError:
                continue
            if best is None or mt > best[0]:
                best = (mt, p)
        return best[1] if best else None

    def layout_policy(self, prior: str | None = None) -> LayoutPolicy:
        """The policy ``strategy="auto"`` consults — over this manager's
        own restore-pattern log unless one was injected, seeded with
        ``prior`` (or the manager-level one, or the freshest sibling-run
        prior :meth:`discover_prior` finds) when available."""
        if self._policy is None:
            self._policy = LayoutPolicy(log=self.access_log)
            src = self.prior
            if src is None and self.auto_prior:
                src = self.discover_prior()
            if src is not None:
                self._policy = self._policy.with_prior(src)
        pol = self._policy
        if prior is not None:
            pol = pol.with_prior(prior)
        return pol

    def export_prior(self, path: str | None = None) -> str:
        """Snapshot this root's restore-pattern history as a cross-run
        prior a future run can pass as ``prior=`` (see
        :meth:`~repro.core.policy.AccessLog.export_prior`)."""
        return self.access_log.export_prior(path)

    # -- paths ---------------------------------------------------------------
    def step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:08d}")

    def steps(self) -> list:
        out = []
        for d in os.listdir(self.root):
            if d.startswith("step_"):
                out.append(int(d.split("_")[1]))
        return sorted(out)

    # -- save ------------------------------------------------------------------
    def save(self, step: int, tree, shardings=None,
             block_map: Mapping[str, Sequence[Block]] | None = None,
             prior: str | None = None) -> SaveStats:
        """``tree``: pytree of arrays (params / opt state / KV caches).
        ``shardings``: matching pytree of shardings (or None: single block).
        ``block_map``: explicit name->blocks override (tests / simulated
        hosts).  ``prior``: seed this save's ``strategy="auto"`` decisions
        from a previous run's restore history (per-call override of the
        manager-level ``prior=``)."""
        with span("repro.save") as s:
            stats = self._save(step, tree, shardings, block_map, prior)
            s.set_metadata(bytes=stats.bytes)
        return stats

    def _save(self, step, tree, shardings, block_map, prior) -> SaveStats:
        t0 = time.perf_counter()
        d = self.step_dir(step)
        flat = flatten_pytree(tree)
        flat_sh = flatten_pytree(shardings) if shardings is not None else {}
        ds = Dataset.create(d, engine=self.engine, clock=self._clock)
        policy_info = {}
        total_bytes = 0
        n_chunks = 0
        n_blocks = 0
        scalars = {}
        vars_meta = {}
        for name, arr in flat.items():
            with span("repro.save.d2h"):
                arr = np.asarray(arr)
            if arr.ndim == 0:
                scalars[name] = {"dtype": arr.dtype.name,
                                 "value": arr.item()}
                continue
            with span("repro.save.plan"):
                if block_map and name in block_map:
                    blocks = list(block_map[name])
                elif name in flat_sh and flat_sh[name] is not None:
                    blocks = blocks_from_sharding(arr.shape, flat_sh[name],
                                                  self.devices_per_host)
                else:
                    blocks = [Block((0,) * arr.ndim, arr.shape, owner=0,
                                    block_id=0)]
                hosts = max(b.owner for b in blocks) + 1
                if self.strategy == "auto":
                    # a save stages from memory: no gather term, only the
                    # write-side build cost vs the expected restore mix
                    decision = self.layout_policy(prior).choose_layout(
                        name, blocks, arr.shape, num_procs=hosts,
                        procs_per_node=self.hosts_per_node, align=self.align,
                        now=self._clock())
                    plan = decision.layout
                    policy_info[name] = decision.to_json()
                else:
                    scheme = None
                    if self.reorg_scheme is not None:
                        scheme = (tuple(self.reorg_scheme[:arr.ndim])
                                  + (1,) * max(0, arr.ndim
                                               - len(self.reorg_scheme)))
                    plan = plan_layout(self.strategy, blocks,
                                       num_procs=hosts,
                                       procs_per_node=self.hosts_per_node,
                                       global_shape=arr.shape,
                                       reorg_scheme=scheme)
                wplan = ds.plan_write(name, plan, arr.dtype, align=self.align)
            vars_meta[name] = {
                "shape": [int(s) for s in arr.shape],
                "dtype": arr.dtype.name,
                "blocks": [[[int(v) for v in b.lo], [int(v) for v in b.hi],
                            int(b.owner), int(b.block_id)] for b in blocks]}
            # index.json is re-committed per variable, so a crash mid-save
            # leaves a readable prefix of the checkpoint
            ds.write_planned(wplan, {b.block_id: arr[b.slices()]
                                     for b in blocks})
            total_bytes += arr.nbytes
            n_chunks += plan.num_chunks
            n_blocks += len(blocks)
        with span("repro.save.manifest"):
            ds.close()
            manifest = {"step": step, "strategy": self.strategy,
                        "scalars": scalars,
                        "variables": sorted(k for k in flat
                                            if k not in scalars)}
            if policy_info:
                manifest["policy"] = policy_info
            with open(os.path.join(d, MANIFEST), "w") as f:
                json.dump(manifest, f)
        self._retain()
        stats = SaveStats(step=step, seconds=time.perf_counter() - t0,
                          bytes=total_bytes, num_chunks=n_chunks,
                          num_original_blocks=n_blocks)
        if self.trace is not None:
            self.trace.record(
                "ckpt_save", seconds=stats.seconds, nbytes=total_bytes,
                step=int(step), strategy=self.strategy, vars=vars_meta,
                scalars={k: v["dtype"] for k, v in scalars.items()},
                align=self.align)
        return stats

    def _retain(self) -> None:
        steps = self.steps()
        old = steps[:-self.keep] if self.keep else []
        with span("repro.save.retain", dirs=len(old)):
            for s in old:
                shutil.rmtree(self.step_dir(s), ignore_errors=True)

    # -- restore -----------------------------------------------------------------
    def restore(self, step: int, template=None,
                target_blocks: Mapping[str, Sequence[Block]] | None = None,
                engine: str | IOEngine | None = None):
        """Restore full arrays (or per-host shards when ``target_blocks``
        names a new decomposition — elastic restart).  Returns
        (tree_or_flat, RestoreStats).

        Every variable is probed exactly once (its full stored region);
        per-shard :class:`~repro.io.planner.ReadPlan`\\ s narrow that shared
        candidate set vectorized and are replayed with ``read_planned``.
        ``RestoreStats.per_var`` carries each variable's merged stats.
        """
        with span("repro.restore"):
            return self._restore(step, template, target_blocks, engine)

    def _restore(self, step, template, target_blocks, engine):
        d = self.step_dir(step)
        with open(os.path.join(d, MANIFEST)) as f:
            manifest = json.load(f)
        agg = RestoreStats()
        flat = {}
        ds = None
        if manifest["variables"]:
            with span("repro.read.open"):
                ds = Dataset.open(d, engine=engine if engine is not None
                                  else self.engine)
        for name in manifest["variables"]:
            shape = ds.index.var_shape(name)
            full = Block((0,) * len(shape), shape)
            tp = time.perf_counter()
            with span("repro.read.probe"):
                cand = ds.index.spatial_index(name).query(full.lo, full.hi)
            vstats = ReadStats(probe_seconds=time.perf_counter() - tp)
            regions = (list(target_blocks[name])
                       if target_blocks and name in target_blocks else [full])
            shards = {}
            for b in regions:
                plan = ds.plan_read(name, b, candidates=cand)
                arr, st = ds.read_planned(plan)
                st.seconds += st.probe_seconds + st.plan_seconds
                self._record_restore(name, b, shape, st)
                vstats.merge(st)
                vstats.seconds += st.seconds
                shards[b.block_id] = arr
            flat[name] = (shards if target_blocks and name in target_blocks
                          else shards[full.block_id])
            agg.merge(vstats)
            agg.seconds += vstats.seconds
            agg.per_var[name] = vstats
        if ds is not None:
            ds.close()
        with span("repro.read.telemetry"):
            self.access_log.flush()
        for name, rec in manifest["scalars"].items():
            flat[name] = np.asarray(rec["value"], dtype=rec["dtype"])
        if self.trace is not None:
            targets = None
            if target_blocks:
                targets = {
                    name: [[[int(v) for v in b.lo], [int(v) for v in b.hi],
                            int(b.owner), int(b.block_id)] for b in blks]
                    for name, blks in target_blocks.items()}
            self.trace.record(
                "ckpt_restore", seconds=agg.seconds, nbytes=agg.bytes_read,
                engine=agg.engine, runs=agg.runs, groups=agg.groups,
                step=int(step), targets=targets)
        if template is not None:
            return unflatten_like(template, flat), agg
        return flat, agg

    def _record_restore(self, name: str, region: Block, shape,
                        st: ReadStats) -> None:
        """Feed one restore read back into the manager-root access log —
        the history ``strategy="auto"`` saves consult.  Telemetry never
        breaks a restore."""
        try:
            with span("repro.read.telemetry"):
                self.access_log.append(
                    AccessRecord.from_stats(name, "restore", region, shape,
                                            st, ts=self._clock()))
        except Exception:               # noqa: BLE001 — telemetry only
            pass

    def restore_latest(self, template=None):
        steps = self.steps()
        if not steps:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        tree, _ = self.restore(steps[-1], template=template)
        return steps[-1], tree
