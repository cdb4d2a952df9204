#!/usr/bin/env python3
"""Bring-up smoke run: the system's main paths on a TPU, each checked
against a plain oracle.

    python chip_smoke.py              # phases A-D on one chip
    python chip_smoke.py --chips 4    # sharded save -> reshard-restore only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

Phases (one process, in order; any failure exits non-zero and prints no
result line):

A  device: the first device JAX reports is a TPU (a CPU is a failure).
B  train -> checkpoint -> resume: qwen2.5-3b at its published widths cut to
   4 of 36 layers, global batch 4, sequence 512, f32 weights + Adam.  Three
   steps, ``CheckpointManager.save`` (merged_process) of {params,
   opt_state}, restore with ``template=``, ``device_put``, two more steps.
   Oracles: the restored bytes equal the saved ones, host and device, and
   the resumed losses equal those of the uninterrupted run.
C  the PIC motif: one float32 mesh variable of 512x1024x1024 (2 GiB, 64x
   fewer cells than the paper's 2048x4096x4096) in load-balanced 128^3
   boxes over 48 simulated processes, written through ``Dataset``,
   reorganized into the regular 4x4x4 layout, then a sub-volume, a z-slab
   and a pencil read back onto the device.  Oracle: the source volume.
D  compiled kernels on the device: ``merge_blocks_device`` on one process's
   real merge plan from C, ``pack_rows`` on a Phase-B weight in float32 and
   bfloat16, and a relayout round trip of a z-plane from C.  Oracles: the
   numpy merge and the row/relayout references.

``--chips 4`` runs Phase A and then only the sharded path: the Phase-B
model sharded over a (2, 2) data x model mesh, one step, a save whose
blocks come from the shardings, a restore onto a (4, 1) mesh, one step.
Oracle: a host gather of the saved arrays, shard by shard on every device.

``--rehearse`` runs the same code at a tiny size on any backend with the
Pallas kernels interpreted (with ``--chips 4`` on four virtual CPU
devices).  The last line of standard output is one JSON object.
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


@dataclasses.dataclass(frozen=True)
class Sizes:
    smoke_widths: bool          # the config's smoke widths, not published
    n_layers: int               # of qwen2.5-3b's 36
    batch: int                  # global batch
    seq: int
    pic_shape: tuple            # the mesh variable
    pic_box: tuple              # AMReX box (block) shape
    pic_procs: int              # simulated writer processes
    reorg_scheme: tuple         # the regular layout's K-way split


CHIP = Sizes(smoke_widths=False, n_layers=4, batch=4, seq=512,
             pic_shape=(512, 1024, 1024), pic_box=(128, 128, 128),
             pic_procs=48, reorg_scheme=(4, 4, 4))
REHEARSAL = Sizes(smoke_widths=True, n_layers=4, batch=4, seq=64,
                  pic_shape=(64, 128, 256), pic_box=(32, 64, 128),
                  pic_procs=4, reorg_scheme=(2, 2, 2))
PAPER_PIC_SHAPE = (2048, 4096, 4096)
ARCH = "qwen2.5-3b"


def say(line: str) -> None:
    print(line, flush=True)


def _import_checkout() -> None:
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke.py: no src/repro next to {HERE}; "
                         f"run it from a checkout of the repository")
    sys.path.insert(0, src)


# -- oracles --------------------------------------------------------------------

def same_bytes(a, b) -> bool:
    import numpy as np
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def check_same_tree(expected, got, what: str) -> int:
    """Leaf by leaf byte identity; device leaves are copied to the host one
    at a time.  Returns the leaf count."""
    import jax
    from repro.checkpoint.blocks_map import flatten_pytree
    fe, fg = flatten_pytree(expected), flatten_pytree(got)
    if fe.keys() != fg.keys():
        raise AssertionError(f"{what}: leaf names differ")
    for name in fe:
        if not same_bytes(jax.device_get(fe[name]), jax.device_get(fg[name])):
            raise AssertionError(f"{what}: {name} differs")
    return len(fe)


def timed(fn, *args, **kwargs):
    """(result, seconds) of ``fn`` until its device result is ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def _secs(xs) -> str:
    return "[" + ", ".join(f"{x:.3f}" for x in xs) + "]"


# -- phases ---------------------------------------------------------------------

def phase_a(require_tpu: bool, chips: int, workdir: str):
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise RuntimeError(f"first device is {d.platform!r} "
                           f"({d.device_kind}), not a TPU")
    if len(devs) < chips:
        raise RuntimeError(f"{chips} chips asked for, {len(devs)} found")
    free = shutil.disk_usage(workdir).free
    say(f"[A] device platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"workdir_free={free / 2**30:.1f}GiB")
    return d


def build_model(sizes: Sizes):
    from repro.configs import get_config, get_smoke_config
    from repro.models import LM
    cfg = (get_smoke_config if sizes.smoke_widths else get_config)(ARCH)
    full_layers = get_config(ARCH).n_layers
    cfg = dataclasses.replace(cfg, n_layers=sizes.n_layers,
                              program=(("attn", sizes.n_layers),))
    return cfg, LM(cfg), full_layers


def phase_b(sizes: Sizes, workdir: str):
    """Returns one Phase-B weight (host) for Phase D."""
    import jax
    import numpy as np
    from repro.checkpoint import CheckpointManager
    from repro.data.pipeline import (PipelineConfig, SyntheticTokens,
                                     make_pipeline)
    from repro.train import OptimizerConfig, Trainer

    cfg, model, full_layers = build_model(sizes)
    say(f"[B] cuts: {ARCH} layers {full_layers}->{cfg.n_layers} at "
        f"d_model={cfg.d_model} heads={cfg.n_heads} kv={cfg.n_kv} "
        f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab}; "
        f"global batch {sizes.batch}, seq {sizes.seq}; "
        f"{model.num_params():,} f32 params + Adam moments")
    pcfg = PipelineConfig(global_batch=sizes.batch, seq_len=sizes.seq,
                          vocab=cfg.vocab, seed=0)
    _, data = make_pipeline(pcfg, prefetch=2)
    tr = Trainer(model, OptimizerConfig(peak_lr=1e-3, warmup_steps=2,
                                        total_steps=100), data)
    # one compiled init: run op by op, each distinct leaf shape compiles its
    # own random-normal (67.5 s on a v5e)
    (params, opt_state), init_s = timed(jax.jit(tr.init), jax.random.key(0))
    params, opt_state, hist = tr.run(params, opt_state, 3, log_every=0)
    losses = [m["loss"] for _, m in hist]
    step_s = [m["step_seconds"] for _, m in hist]
    state = {"params": params, "opt_state": opt_state}

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt"),
                             strategy="merged_process", keep=1)
    save = ckpt.save(3, state)
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            state)
    t0 = time.perf_counter()
    restored, rstats = ckpt.restore(3, template=template)
    restore_s = time.perf_counter() - t0
    n_leaves = check_same_tree(state, restored, "restore vs saved")

    # the uninterrupted run: two more steps from the saved state
    _, _, ref_hist = tr.run(state["params"], state["opt_state"], 2,
                            log_every=0)
    ref = [m["loss"] for _, m in ref_hist]
    del state, params, opt_state

    dev, put_s = timed(jax.device_put, restored, jax.devices()[0])
    check_same_tree(restored, dev, "device_put vs restored")
    tr.data = SyntheticTokens(dataclasses.replace(pcfg, start_step=3))
    tr.state.step = 3
    _, _, res_hist = tr.run(dev["params"], dev["opt_state"], 2,
                            log_every=0)
    del dev
    resumed = [m["loss"] for _, m in res_hist]
    if not np.all(np.isfinite(losses + ref + resumed)):
        raise AssertionError(f"non-finite loss: {losses} {ref} {resumed}")
    if not np.allclose(resumed, ref, rtol=1e-6, atol=0.0):
        raise AssertionError(f"resumed losses {resumed} != uninterrupted "
                             f"{ref}")
    if int(restored["opt_state"]["count"]) != 3:
        raise AssertionError("restored optimizer step count is not 3")
    say(f"[B] init {init_s:.3f}s; steps 1-3 loss {_secs(losses)} "
        f"step_s {_secs(step_s)} (step 1 includes compile); "
        f"save merged_process {save.bytes / 2**30:.3f}GiB "
        f"{save.num_original_blocks} blocks->{save.num_chunks} chunks "
        f"{save.seconds:.3f}s; restore {restore_s:.3f}s "
        f"engine={rstats.engine}; {n_leaves} leaves byte-identical on host "
        f"and after device_put ({put_s:.3f}s); resumed steps 4-5 loss "
        f"{_secs(resumed)} == uninterrupted {_secs(ref)} "
        f"exact={resumed == ref}")
    shutil.rmtree(os.path.join(workdir, "ckpt"))
    return np.array(restored["params"]["segments"][0]["mlp"]["w_up"][0])


@dataclasses.dataclass
class PicWorld:
    vol: object                 # the source volume (numpy)
    blocks: list
    plane: object               # one z-plane, on the device


def phase_c(sizes: Sizes, workdir: str) -> PicWorld:
    import jax
    import numpy as np
    from repro.core import (plan_layout, simulate_load_balance,
                            uniform_grid_blocks)
    from repro.core.blocks import Block
    from repro.io import Dataset, reorganize

    shape = sizes.pic_shape
    cut = int(np.prod(PAPER_PIC_SHAPE) // np.prod(shape))
    say(f"[C] cuts: mesh variable {shape} float32 "
        f"({np.prod(shape) * 4 / 2**30:.3f}GiB), {cut}x fewer cells than "
        f"the paper's {PAPER_PIC_SHAPE}; {sizes.pic_procs} simulated "
        f"processes, {sizes.pic_box} boxes")
    t0 = time.perf_counter()
    vol = np.random.default_rng(0).random(shape, dtype=np.float32)
    blocks = simulate_load_balance(uniform_grid_blocks(shape, sizes.pic_box),
                                   num_procs=sizes.pic_procs, seed=0)
    data = {b.block_id: vol[b.slices()] for b in blocks}
    gen_s = time.perf_counter() - t0

    src_dir = os.path.join(workdir, "pic_written")
    dst_dir = os.path.join(workdir, "pic_reorganized")
    write_plan = plan_layout("merged_process", blocks,
                             num_procs=sizes.pic_procs, global_shape=shape)
    ds = Dataset.create(src_dir)
    ws = ds.write_planned(ds.plan_write("E", write_plan, np.float32), data)
    ds.close()
    regular = plan_layout("reorganized", blocks, num_procs=sizes.pic_procs,
                          global_shape=shape,
                          reorg_scheme=sizes.reorg_scheme)
    t0 = time.perf_counter()
    _, rds, rws = reorganize(src_dir, dst_dir, "E", regular)
    reorg_s = time.perf_counter() - t0

    z, y, x = shape
    lo = (z // 4 + 3, y // 4 + 3, x // 4 + 3)
    regions = {
        "subvolume": Block(lo, (lo[0] + z // 4, lo[1] + y // 4,
                                lo[2] + x // 4)),
        "z-slab": Block((z // 2 - 3, 0, 0), (z // 2 + 5, y, x)),
        "pencil": Block((0, y // 2 + 1, x // 3), (z, y // 2 + 2, x // 3 + 1)),
    }
    parts = []
    plane = None
    try:
        for name, region in regions.items():
            plan = rds.plan_read("E", region)
            arr, st = rds.read_planned(plan)
            dev, put_s = timed(jax.device_put, arr)
            if not same_bytes(jax.device_get(dev), vol[region.slices()]):
                raise AssertionError(f"{name} {region} differs from the "
                                     f"source volume")
            parts.append(f"{name} {region.shape} {arr.nbytes / 2**20:.3f}MiB "
                         f"chunks={plan.num_chunks} runs={plan.runs} "
                         f"read {st.seconds:.4f}s h2d {put_s:.4f}s")
            if name == "z-slab":
                plane = dev[0]
    finally:
        rds.close()
    say(f"[C] data {gen_s:.3f}s; write merged_process "
        f"{ws.bytes_written / 2**30:.3f}GiB {len(blocks)} blocks->"
        f"{write_plan.num_chunks} chunks {ws.total_seconds:.3f}s; "
        f"reorganize->{regular.num_chunks} chunks {reorg_s:.3f}s "
        f"(write {rws.total_seconds:.3f}s); reads byte-identical on device: "
        + "; ".join(parts))
    shutil.rmtree(src_dir)
    shutil.rmtree(dst_dir)
    return PicWorld(vol=vol, blocks=blocks, plane=plane)


def _first_and_second(fn, *args, **kwargs):
    """Run twice: (result, first-call seconds incl. compile, second)."""
    _, first = timed(fn, *args, **kwargs)
    out, second = timed(fn, *args, **kwargs)
    return out, first, second


def phase_d(pic: PicWorld, weight, interpret: bool) -> None:
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    from repro.core import build_merge_plan
    from repro.core.merge import execute_merge_numpy
    from repro.kernels import (chunked_to_rowmajor, merge_blocks_device,
                               pack_rows, rowmajor_to_chunked)
    from repro.kernels.ref import plan_row_tables, rowmajor_to_chunked_ref

    parts = []
    # 1. the merge of the most-loaded process's boxes
    owners = {}
    for b in pic.blocks:
        owners.setdefault(b.owner, []).append(b)
    mine = max(owners.values(), key=len)
    plan = build_merge_plan(mine)
    width = plan_row_tables(plan)[0]
    data = {b.block_id: pic.vol[b.slices()] for b in mine}
    merged, first, second = _first_and_second(
        merge_blocks_device, plan, data, interpret=interpret)
    ref = execute_merge_numpy(plan, data)
    if len(ref) != len(merged) or not all(
            same_bytes(r, jax.device_get(m)) for r, m in zip(ref, merged)):
        raise AssertionError("merge_blocks_device differs from numpy merge")
    parts.append(f"merge_blocks_device {len(mine)} boxes->{len(ref)} "
                 f"cuboids width={width} "
                 f"{sum(r.nbytes for r in ref) / 2**20:.3f}MiB "
                 f"{first:.3f}s/{second:.4f}s")

    # 2. the checkpoint-merge case: shuffled row slabs of a weight
    rows, cols = weight.shape
    cut = [0, rows // 4, rows // 2, 3 * rows // 4, rows]
    # row i of the logged shards is row order[i] of the weight
    order = np.concatenate([np.arange(cut[i], cut[i + 1])
                            for i in (2, 0, 3, 1)]).astype(np.int32)
    for dtype in (np.float32, ml_dtypes.bfloat16):
        w = weight.astype(dtype)
        out, first, second = _first_and_second(
            pack_rows, jnp.asarray(w[order]),
            jnp.arange(rows, dtype=jnp.int32), jnp.asarray(order),
            n_dst_rows=rows, width=cols, interpret=interpret)
        if not same_bytes(jax.device_get(out), w):
            raise AssertionError(f"pack_rows {np.dtype(dtype).name} "
                                 f"differs from the weight")
        parts.append(f"pack_rows {np.dtype(dtype).name} ({rows},{cols}) "
                     f"{first:.3f}s/{second:.4f}s")

    # 3. relayout round trip of one z-plane at the (8, 128) tile
    chunk = (8, 128)
    host = jax.device_get(pic.plane)
    tiles, first, second = _first_and_second(
        rowmajor_to_chunked, pic.plane, chunk=chunk, interpret=interpret)
    back, bfirst, bsecond = _first_and_second(
        chunked_to_rowmajor, tiles, chunk=chunk, interpret=interpret)
    if not same_bytes(jax.device_get(tiles),
                      np.ascontiguousarray(rowmajor_to_chunked_ref(host,
                                                                   chunk))):
        raise AssertionError("rowmajor_to_chunked differs from reference")
    if not same_bytes(jax.device_get(back), host):
        raise AssertionError("relayout round trip differs from the plane")
    parts.append(f"relayout {host.shape} chunk={chunk} "
                 f"to_chunked {first:.3f}s/{second:.4f}s "
                 f"to_rowmajor {bfirst:.3f}s/{bsecond:.4f}s")
    say(f"[D] interpret={interpret}; byte-identical to oracles "
        f"(first call incl. compile/second call): " + "; ".join(parts))


def phase_sharded(sizes: Sizes, workdir: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.checkpoint import CheckpointManager
    from repro.checkpoint.blocks_map import (blocks_from_sharding,
                                             flatten_pytree, unflatten_like)
    from repro.data.pipeline import PipelineConfig, SyntheticTokens
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.params import shardings as param_shardings
    from repro.train import OptimizerConfig, Trainer, adamw_init

    cfg, model, full_layers = build_model(sizes)
    say(f"[S] cuts: {ARCH} layers {full_layers}->{cfg.n_layers} at "
        f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab}; global "
        f"batch {sizes.batch}, seq {sizes.seq}")
    devs = jax.devices()[:4]
    rules = shd.DEFAULT_RULES

    def state_shardings(mesh):
        with shd.use_sharding(mesh, rules):
            ps = param_shardings(model.skeleton())
        rep = NamedSharding(mesh, P())
        return {"params": ps, "opt_state": {"m": ps, "v": ps,
                                            "count": rep}}

    def batches(mesh, start_step):
        batch = NamedSharding(mesh, P("data"))
        src = SyntheticTokens(PipelineConfig(
            global_batch=sizes.batch, seq_len=sizes.seq, vocab=cfg.vocab,
            seed=0, start_step=start_step))
        return (jax.device_put(b, batch) for b in src)

    mesh22 = make_mesh((2, 2), ("data", "model"), devices=devs)
    sh22 = state_shardings(mesh22)
    params = jax.jit(model.init,
                     out_shardings=sh22["params"])(jax.random.key(0))
    opt_state = jax.jit(adamw_init,
                        out_shardings=sh22["opt_state"])(params)
    tr = Trainer(model, OptimizerConfig(peak_lr=1e-3, warmup_steps=2,
                                        total_steps=100),
                 batches(mesh22, 0))
    with shd.use_sharding(mesh22, rules):
        params, opt_state, hist = tr.run(params, opt_state, 1, log_every=0)
    state = {"params": params, "opt_state": opt_state}

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt_sharded"),
                             strategy="merged_process", keep=1)
    save = ckpt.save(1, state, shardings=sh22)
    host = jax.device_get(state)                 # the host gather
    with shd.use_sharding(mesh22, rules):        # the uninterrupted step
        _, _, ref_hist = tr.run(state["params"], state["opt_state"], 1,
                                log_every=0)
    del state, params, opt_state

    mesh41 = make_mesh((4, 1), ("data", "model"), devices=devs)
    sh41 = flatten_pytree(state_shardings(mesh41))
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            host)
    flat_host = flatten_pytree(host)
    targets = {n: blocks_from_sharding(a.shape, sh41[n], devices_per_host=4)
               for n, a in flat_host.items() if np.ndim(a)}
    t0 = time.perf_counter()
    shards, _ = ckpt.restore(1, target_blocks=targets)
    flat = {}
    for name, a in flat_host.items():
        if name not in targets:                  # 0-d: manifest scalar
            flat[name] = jax.device_put(shards[name], sh41[name])
            continue
        by_lo = {b.lo: shards[name][b.block_id] for b in targets[name]}
        flat[name] = jax.make_array_from_callback(
            a.shape, sh41[name],
            lambda idx, _m=by_lo: _m[tuple(s.start or 0 for s in idx)])
    restored = jax.block_until_ready(unflatten_like(template, flat))
    restore_s = time.perf_counter() - t0

    n_shards = 0
    for name, arr in flatten_pytree(restored).items():
        want = sh41[name].devices_indices_map(arr.shape)
        on = {s.device for s in arr.addressable_shards}
        if on != set(devs):
            raise AssertionError(f"{name} lives on {on}, not on all 4 chips")
        for s in arr.addressable_shards:
            if s.data.devices() != {s.device}:
                raise AssertionError(f"{name}: shard for {s.device} is on "
                                     f"{s.data.devices()}")
            if tuple(s.index) != tuple(want[s.device]):
                raise AssertionError(f"{name}: {s.device} holds {s.index}, "
                                     f"its sharding says {want[s.device]}")
            if not same_bytes(jax.device_get(s.data),
                              np.asarray(flat_host[name])[s.index]):
                raise AssertionError(f"{name}: shard on {s.device} differs "
                                     f"from the host gather")
            n_shards += 1

    tr.data = batches(mesh41, 1)
    tr.state.step = 1
    with shd.use_sharding(mesh41, rules):
        _, _, new_hist = tr.run(restored["params"], restored["opt_state"],
                                1, log_every=0)
    loss, ref = new_hist[0][1]["loss"], ref_hist[0][1]["loss"]
    # activations are bfloat16 and the two meshes split the contractions
    # differently, so partial sums round differently: a few bf16 ulps
    if not (np.isfinite(loss) and abs(loss - ref) <= 2.0**-6 * abs(ref)):
        raise AssertionError(f"step on (4,1) loss {loss} vs (2,2) {ref}")
    say(f"[S] (2,2) data x model: step 1 loss {hist[0][1]['loss']:.6f} "
        f"({hist[0][1]['step_seconds']:.3f}s incl. compile); save "
        f"shardings= {save.bytes / 2**30:.3f}GiB {save.num_original_blocks} "
        f"blocks from shardings->{save.num_chunks} chunks "
        f"{save.seconds:.3f}s; restore onto (4,1) {restore_s:.3f}s, "
        f"{n_shards} shards on {len(devs)} chips byte-identical to the host "
        f"gather and placed as their shardings say; step 2 loss on (4,1) "
        f"{loss:.6f} vs (2,2) {ref:.6f} "
        f"({new_hist[0][1]['step_seconds']:.3f}s incl. compile)")
    shutil.rmtree(os.path.join(workdir, "ckpt_sharded"))


# -- main -----------------------------------------------------------------------

class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend, interpreted kernels")
    args = ap.parse_args(argv)
    if args.rehearse and args.chips == 4:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    _import_checkout()
    import jax
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    events = CacheEvents()
    jax.monitoring.register_event_listener(events)
    sizes = REHEARSAL if args.rehearse else CHIP
    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        dev = phase_a(not args.rehearse, args.chips, workdir)
        if args.chips == 4:
            phase_sharded(sizes, workdir)
        else:
            weight = phase_b(sizes, workdir)
            pic = phase_c(sizes, workdir)
            phase_d(pic, weight, interpret=args.rehearse)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(f"[cache] dir={cache_dir} hits={events.hits} "
        f"misses={events.misses}; total {time.perf_counter() - t_start:.1f}s")
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(jax.devices())}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
