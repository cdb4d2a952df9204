"""Mesh-variable cells: an analysis client reading regions of a simulation's
field back onto the device.

The field is made on the device from the seed, one box array per AMReX box
(the boxes placed over the simulated ranks by the program's load
balancer), and handed to ``Dataset`` as it lives there.  The reference is
the same field made again from the seed once the window has closed.
"""

from __future__ import annotations

import os
import time

import numpy as np

from bench import regions
from bench.harness import percentile, say, sized_config
from bench.seeds import seed_key

SAMPLE = 8                        # reads kept for the comparison


def _field_fns(shape, box):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def field(key):
        return jax.random.uniform(key, shape, jnp.float32)

    @jax.jit
    def cut(f, lo):
        return jax.lax.dynamic_slice(f, lo, box)

    return field, cut


def _boxes(cfg, seed):
    """The program's boxes of the mesh, placed from the seed, and the field
    as one device array per box."""
    import jax.numpy as jnp
    from repro.core import simulate_load_balance, uniform_grid_blocks

    shape, box = tuple(cfg["global_shape"]), tuple(cfg["box"])
    blocks = simulate_load_balance(uniform_grid_blocks(shape, box),
                                   num_procs=cfg["ranks"], seed=seed)
    field, cut = _field_fns(shape, box)
    f = field(seed_key(seed))
    data = {b.block_id: cut(f, jnp.asarray(b.lo, jnp.int32)) for b in blocks}
    return blocks, data


def _plan(cfg, blocks):
    from repro.core import plan_layout
    lay = cfg["layout"]
    return plan_layout(lay["strategy"], blocks, num_procs=cfg["ranks"],
                       global_shape=tuple(cfg["global_shape"]),
                       reorg_scheme=tuple(lay["reorg_scheme"]))


def _bits_differing(a, b) -> int:
    """Elements whose 32-bit patterns differ (device arrays)."""
    import jax
    import jax.numpy as jnp
    if a.shape != b.shape:
        return int(np.prod(b.shape))
    return int(jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint32)
                       != jax.lax.bitcast_convert_type(b, jnp.uint32)))


def region_reads(run) -> None:
    """One closed-loop client: each read is ``Dataset.read`` of a region,
    placed on the device and ready, before the next is sent.  The client
    then reduces the region on the device (its energy, the sum of squares),
    outside the read's latency."""
    import jax
    import jax.numpy as jnp
    from repro.core.blocks import Block
    from repro.io import Dataset

    cfg = run.config = sized_config(run.config, run.rehearse)
    blocks, data = _boxes(cfg, run.seed)
    dtype = np.dtype(cfg["dtype"])
    if run.control == "f16_store":
        dtype = np.dtype(np.float16)
        data = {k: v.astype(dtype) for k, v in data.items()}
    d = os.path.join(run.workdir, "mesh")
    w = Dataset.create(d, engine=cfg["engine"]).write(
        "E", _plan(cfg, blocks), dtype, data, codec=cfg["codec"],
        fsync=cfg["fsync"])
    say(f"[pic-read] wrote {w.bytes_written / 2**30:.3f} GiB in "
        f"{w.total_seconds:.3f}s")
    del data
    ds = Dataset.open(d, engine=cfg["engine"])
    shape = tuple(cfg["global_shape"])
    mix = run.mix
    gen = regions.reads(shape, mix["weights"], run.seed, mix["thickness"])
    warm = regions.reads(shape, mix["weights"], run.seed + 1,
                         mix["thickness"])
    analyze = jax.jit(lambda r: jnp.sum(r * r))
    for _ in range(sum(mix["weights"].values())):
        _, lo, hi = next(warm)
        dev = jax.device_put(ds.read("E", Block(lo, hi))[0])
        analyze(dev).block_until_ready()

    rng = np.random.default_rng((run.seed, 1))
    kept = []                     # reservoir of (pattern, lo, hi, array)
    energy = []                   # the analysis' answers, one per read
    n = 0
    run.open_window()
    while run.window_open():
        pattern, lo, hi = next(gen)
        t0 = time.perf_counter()
        try:
            with run.span("bench.read"):
                arr, st = ds.read("E", Block(lo, hi))
            with run.span("bench.h2d"):
                dev = jax.block_until_ready(jax.device_put(arr))
        except Exception as e:            # noqa: BLE001 — counted, reported
            run.failed += 1
            say(f"[pic-read] read {pattern} {lo}-{hi} failed: {e!r}")
            run.note("read_s", time.perf_counter() - t0)
            n += 1
            continue
        run.note("read_s", time.perf_counter() - t0)
        with run.span("bench.analyze"):
            energy.append(analyze(dev))
        run.note("probe_plan_s", st.probe_seconds + st.plan_seconds)
        run.note("engine_s", st.seconds - st.probe_seconds - st.plan_seconds)
        del arr
        if len(kept) < SAMPLE:
            kept.append((pattern, lo, hi, dev))
        else:
            j = int(rng.integers(0, n + 1))
            if j < SAMPLE:
                kept[j] = (pattern, lo, hi, dev)
        n += 1
    jax.block_until_ready(energy)
    window = run.close_window()
    run.attempted = n
    ms = [s * 1e3 for s in run.values["read_s"]]
    run.e2e["read_p95_ms"] = percentile(ms, 95)
    say(f"[pic-read] {n} reads in {window:.3f}s, p50 "
        f"{percentile(ms, 50):.3f} ms, p95 {percentile(ms, 95):.3f} ms")
    ds.close()

    field, _ = _field_fns(shape, tuple(cfg["box"]))
    ref = field(seed_key(run.seed))
    differing = 0
    for pattern, lo, hi, dev in kept:
        want = ref[tuple(slice(a, b) for a, b in zip(lo, hi))]
        differing += _bits_differing(dev.astype(np.float32), want)
    run.check("read_elements_differing", differing, 0)
    run.check("reads_failed", run.failed, 0)
