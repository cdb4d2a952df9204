"""Training cells: periodic full-state saves, and resumes back to back.

Set-up builds one ``Trainer`` with its compiled step, gives it the weights
made from the seed (one jitted call, on the device) and drives it through
its first steps on the data pipeline; that same trainer and state go on
into the window.  The first steps' numbers are taken from the program's own
state and compared with the plain reference once the window has closed.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
import time

from bench.harness import say, sized_config


@dataclasses.dataclass
class Job:
    cfg: dict
    ref: object                   # the configuration's reference module
    trainer: object
    params: object
    opt_state: object
    pcfg: object                  # the pipeline's configuration
    readings: dict                # the program's numbers of its first steps


def _program_mapping():
    seg_keys = {"ln1": ("ln1",), "ln2": ("ln2",), "wq": ("attn", "wq"),
                "wk": ("attn", "wk"), "wv": ("attn", "wv"),
                "wo": ("attn", "wo"), "bq": ("attn", "bq"),
                "bk": ("attn", "bk"), "bv": ("attn", "bv"),
                "w_up": ("mlp", "w_up"), "w_gate": ("mlp", "w_gate"),
                "w_down": ("mlp", "w_down")}

    def to_program(flat):
        seg = {"attn": {}, "mlp": {}}
        for k, path in seg_keys.items():
            if len(path) == 1:
                seg[path[0]] = flat[k]
            else:
                seg[path[0]][path[1]] = flat[k]
        return {"embed": flat["embed"], "segments": [seg],
                "final_norm": flat["final_norm"]}

    def from_program(tree):
        seg = tree["segments"][0]
        out = {"embed": tree["embed"], "final_norm": tree["final_norm"]}
        for k, path in seg_keys.items():
            out[k] = seg[path[0]] if len(path) == 1 else \
                seg[path[0]][path[1]]
        return out

    return to_program, from_program


def _leaf_norms(tree) -> dict:
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in tree.items()}


def _check_program_matches(cfg: dict, ref, pcfg) -> None:
    """Refuses a configuration that states what the program does not do:
    its weights' and products' types, its norm's epsilon and the tokens it
    draws (the Zipf exponent), which the program fixes in code."""
    import inspect

    import jax.numpy as jnp
    import numpy as np
    from repro.data.pipeline import SyntheticTokens
    from repro.models import layers

    t = cfg["training"]
    if t["param_dtype"] != "float32":
        raise ValueError("the program keeps float32 weights")
    if jnp.dtype(t["compute_dtype"]) != jnp.dtype(layers._COMPUTE):
        raise ValueError(f"the program computes in {layers._COMPUTE}, the "
                         f"configuration says {t['compute_dtype']}")
    eps = inspect.signature(layers.rms_norm).parameters["eps"].default
    if eps != cfg["rms_norm_eps"]:
        raise ValueError(f"the program's RMSNorm epsilon is {eps}, the "
                         f"configuration says {cfg['rms_norm_eps']}")
    first = next(SyntheticTokens(pcfg))
    tokens, labels = ref.batch(cfg, pcfg.seed, 0)
    if not (np.array_equal(first["tokens"], tokens)
            and np.array_equal(first["labels"], labels)):
        raise ValueError("the program's tokens are not the configuration's "
                         f"(Zipf exponent {t['zipf_a']})")


def build(run) -> Job:
    """Weights from the seed, the trainer on its pipeline, and its first
    ``setup_steps`` steps with the numbers the reference is held to."""
    import jax
    from repro.data.pipeline import PipelineConfig, make_pipeline
    from repro.models import LM
    from repro.models.transformer import ModelConfig
    from repro.train import OptimizerConfig, Trainer, adamw_init

    cfg = run.config = sized_config(run.config, run.rehearse)
    ref = importlib.import_module("bench.references." + cfg["reference"])
    d = ref.dims(cfg)
    t = cfg["training"]
    mcfg = ModelConfig(name=cfg["name"], family="dense", n_layers=d["n"],
                       d_model=d["d"], n_heads=d["h"], n_kv=d["kv"],
                       head_dim=d["hd"], d_ff=d["ff"], vocab=d["v"],
                       program=(("attn", d["n"]),), qkv_bias=True,
                       act=cfg["hidden_act"],
                       rope_theta=cfg["rope_theta"],
                       tie_embed=cfg["tie_word_embeddings"])
    pcfg = PipelineConfig(global_batch=t["global_batch"], seq_len=t["seq_len"],
                          vocab=d["v"], seed=run.seed)
    _check_program_matches(cfg, ref, pcfg)
    _, data = make_pipeline(pcfg, prefetch=run.mix.get("prefetch", 2))
    opt = t["optimizer"]
    trainer = Trainer(LM(mcfg), OptimizerConfig(**opt), data)
    to_program, from_program = _program_mapping()
    key = ref.seed_key(run.seed)

    @jax.jit
    def init(key):
        params = to_program(ref.make_params(cfg, key))
        return params, adamw_init(params)

    @jax.jit
    def first_grad_norms(opt_state):
        # m after one step is (1 - b1) * the clipped gradient
        m = from_program(opt_state["m"])
        return {k: v / (1.0 - opt["b1"]) for k, v in _leaf_norms(m).items()}

    @jax.jit
    def change_norms(params, key):
        p0 = ref.make_params(cfg, key)
        p = from_program(params)
        return _leaf_norms({k: p[k] - p0[k] for k in p})

    params, opt_state = init(key)
    losses = []
    n_setup = run.mix["setup_steps"]
    params, opt_state, hist = trainer.run(params, opt_state, 1, log_every=0)
    losses += [m["loss"] for _, m in hist]
    grads = {k: float(v) for k, v in first_grad_norms(opt_state).items()}
    params, opt_state, hist = trainer.run(params, opt_state, n_setup - 1,
                                          log_every=0)
    losses += [m["loss"] for _, m in hist]
    change = {k: float(v) for k, v in change_norms(params, key).items()}
    say(f"[train] {cfg['name']}: {d['n']} layers, batch {t['global_batch']} "
        f"x seq {t['seq_len']}; set-up losses {losses}")
    return Job(cfg=cfg, ref=ref, trainer=trainer, params=params,
               opt_state=opt_state, pcfg=pcfg,
               readings={"losses": losses, "grad_norms": grads,
                         "change_norms": change})


def delete_arrays(tree) -> None:
    import jax
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared with the reference: the largest relative gap of
    a step's loss; and, by the worst leaf, the gap between the program's
    norm and the reference's, over the larger of the reference's norm of
    that leaf and of the median leaf.  Leaves whose reference gradient is
    under a thousandth of the median leaf's (a key's bias, under softmax)
    move by round-off alone and are left out of the change.  The worst
    leaf's change swings by seed with the noise of one small leaf, so the
    mean over the leaves' change gaps stands beside it: it is what
    separates a cheaper precision from the program's own."""
    import statistics
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    out = {"loss_gap": loss}
    g_med = statistics.median(ref["grad_norms"].values())
    moved = [k for k, v in ref["grad_norms"].items() if v >= 1e-3 * g_med]
    for what, names in (("grad_norms", list(ref["grad_norms"])),
                        ("change_norms", moved)):
        r, p = ref[what], prog[what]
        med = statistics.median(r[k] for k in names)
        leaf = [abs(p[k] - r[k]) / max(r[k], med) for k in names]
        out[what.replace("_norms", "_gap")] = max(leaf)
        if what == "change_norms":
            out["change_mean_gap"] = statistics.mean(leaf)
    return out


def compare_with_reference(run, job: Job) -> None:
    """Runs the reference over the same first steps and records the gaps
    beside their limits (the configuration's ``limits``).  The control
    (``run.control == "fp8"``) puts the reference at float8 in the
    program's place."""
    steps = run.mix["setup_steps"]
    t0 = time.perf_counter()
    ref = job.ref.readings(job.cfg, run.seed, steps=steps)
    say(f"[reference] {steps} steps {time.perf_counter() - t0:.3f}s")
    prog = job.readings
    if run.control == "fp8":
        prog = job.ref.readings(job.cfg, run.seed, steps=steps, fp8=True)
    elif run.control is not None:
        raise ValueError(f"unknown control {run.control!r}")
    g = gaps(prog, ref)
    lim = job.cfg["limits"]
    for name, value in g.items():
        run.check(name, value, lim[name])


def _saved(state: dict, ck: dict) -> dict:
    """The tree a save writes: the parts of the state the configuration's
    checkpoint names."""
    return {k: state[k] for k in ck["state"]}


def save_rounds(run) -> None:
    """Rounds of ``steps_per_round`` steps, each followed by a save of the
    whole state; the window ends once the last save's manifest is on disk.

    The stall per save is the window's time outside its rounds of steps,
    or, where the window's steps ran slower than a round of the same steps
    without saves in set-up (a save that overlaps them), the window less
    its steps at set-up's pace, whichever is larger; over the saves."""
    import jax
    import numpy as np
    from repro.checkpoint import CheckpointManager

    job = build(run)
    ck = job.cfg["checkpoint"]
    root = os.path.join(run.workdir, "ckpt")
    mgr = CheckpointManager(root, strategy=ck["strategy"], keep=ck["keep"])
    tr, params, opt_state = job.trainer, job.params, job.opt_state
    n_round = run.mix["steps_per_round"]
    t0 = time.perf_counter()
    params, opt_state, _ = tr.run(params, opt_state, n_round, log_every=0)
    paced = (time.perf_counter() - t0) / n_round
    steps = 0
    run.open_window()
    while run.window_open():
        with run.span("bench.step"):
            params, opt_state, hist = tr.run(params, opt_state, n_round,
                                             log_every=0)
        for _, m in hist:
            run.note("step_seconds", m["step_seconds"])
            run.note("loss", m["loss"])
        steps += len(hist)
        with run.span("bench.save"):
            st = mgr.save(tr.state.step, _saved({"params": params,
                                                 "opt_state": opt_state}, ck))
            manifest = os.path.join(mgr.step_dir(st.step), "manifest.json")
            if not os.path.exists(manifest):
                raise RuntimeError(f"no manifest after save: {manifest}")
    window = run.close_window()
    saves = len(run.spans["bench.save"])
    run.attempted = steps
    outside = window - sum(run.spans["bench.step"])
    run.e2e["save_stall_s"] = max(outside, window - steps * paced) / saves
    say(f"[ckpt-save] {steps} steps, {saves} saves in {window:.3f}s; "
        f"outside the steps {outside:.3f}s; set-up's pace "
        f"{1e3 * paced:.3f} ms a step")

    # the last save, read back by a fresh manager, against the live state
    t0 = time.perf_counter()
    last = tr.state.step
    live = _saved({"params": params, "opt_state": opt_state}, ck)
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            live)
    back, _ = CheckpointManager(root, strategy=ck["strategy"],
                                keep=ck["keep"]).restore(last,
                                                         template=template)
    differing = 0
    for a, b in zip(jax.tree.leaves(live), jax.tree.leaves(back)):
        host = np.ascontiguousarray(jax.device_get(a))
        b = np.ascontiguousarray(b)
        if host.shape != b.shape or host.dtype != b.dtype or \
                not np.array_equal(host.reshape(-1).view(np.uint8),
                                   b.reshape(-1).view(np.uint8)):
            differing += 1
    del back
    run.check("saved_leaves_differing", differing, 0)
    say(f"[ckpt-save] read-back check {time.perf_counter() - t0:.3f}s")
    run.check("window_losses_nonfinite",
              sum(not math.isfinite(x) for x in run.values["loss"]), 0)
    delete_arrays((params, opt_state))
    del params, opt_state, live
    compare_with_reference(run, job)


def _fingerprint_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fingerprint(tree):
        """Two wrapping sums of each leaf's 32-bit words: any changed word
        changes the first (its weight is odd)."""
        out = []
        for x in jax.tree.leaves(tree):
            w = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
            i = jnp.arange(w.shape[0], dtype=jnp.uint32)
            out.append(jnp.stack([jnp.sum(w * (2 * i + 1)),
                                  jnp.sum(w ^ (i * jnp.uint32(0x9E3779B9)))]))
        return jnp.stack(out)

    return fingerprint


def resume_loop(run) -> None:
    """Set-up saves the state after its first steps and drops it from the
    device; the window repeats resumes: restore onto a sharded template,
    place what is not on the device, one step with its loss ready, and
    drop the state again."""
    import jax
    import numpy as np
    from repro.checkpoint import CheckpointManager
    from repro.data.pipeline import SyntheticTokens

    job = build(run)
    ck = job.cfg["checkpoint"]
    root = os.path.join(run.workdir, "ckpt")
    mgr = CheckpointManager(root, strategy=ck["strategy"], keep=ck["keep"])
    tr = job.trainer
    step0 = tr.state.step
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    # committed to the device, as the restored state will be, so that the
    # step and the fingerprint compile here for the window's arguments
    state = jax.device_put(_saved({"params": job.params,
                                   "opt_state": job.opt_state}, ck),
                           sharding)
    mgr.save(step0, state)
    fingerprint = _fingerprint_fn()
    saved = np.asarray(fingerprint(state))
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        state)
    # the uninterrupted run's next step, from the live state
    p, o, hist = tr.run(state["params"], state["opt_state"], 1, log_every=0)
    uninterrupted = hist[0][1]["loss"]
    delete_arrays((p, o, state))
    del p, o, state
    job.params = job.opt_state = None
    resumed_pcfg = dataclasses.replace(job.pcfg, start_step=step0)

    def place(x, t):
        if isinstance(x, jax.Array) and x.sharding == t.sharding:
            return x
        return jax.device_put(x, t.sharding)

    prints = []
    run.open_window()
    while run.window_open():
        with run.span("bench.restore"):
            tree, st = mgr.restore(step0, template=template)
        run.note("restore_read_s", st.seconds)
        with run.span("bench.h2d"):
            on_dev = jax.block_until_ready(jax.tree.map(place, tree,
                                                        template))
        del tree
        prints.append(fingerprint(on_dev))
        tr.data = SyntheticTokens(resumed_pcfg)
        tr.state.step = step0
        with run.span("bench.step"):
            p, o, hist = tr.run(on_dev["params"], on_dev["opt_state"], 1,
                                log_every=0)
        run.note("resume_loss", hist[0][1]["loss"])
        delete_arrays((p, o, on_dev))
        del p, o, on_dev
    window = run.close_window()
    n = len(prints)
    run.attempted = n
    run.e2e["resume_s"] = window / n
    say(f"[ckpt-resume] {n} resumes in {window:.3f}s")
    differing = sum(int(np.any(np.asarray(fp) != saved, axis=1).sum())
                    for fp in prints)
    run.check("restored_leaves_differing", differing, 0)
    run.check("resume_loss_gap",
              max(abs(x - uninterrupted) for x in run.values["resume_loss"]),
              0)
    compare_with_reference(run, job)
