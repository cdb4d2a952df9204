"""Trainer loop, optimizer, data pipeline, fault tolerance, collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import PipelineConfig, Prefetcher, SyntheticTokens
from repro.distributed.collectives import (compressed_psum_tree,
                                           dequantize_int8, quantize_int8)
from repro.distributed.fault_tolerance import (HeartbeatMonitor,
                                               StragglerTracker, plan_rescale)
from repro.models import LM
from repro.train import OptimizerConfig, Trainer, warmup_cosine
from repro.train.optimizer import zero_moment_defs
from repro.models.params import ParamDef


def test_warmup_cosine_shape():
    cfg = OptimizerConfig(peak_lr=1e-3, end_lr=1e-4, warmup_steps=10,
                          total_steps=100)
    lrs = [float(warmup_cosine(cfg, s)) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 1e-3) < 1e-9          # peak at end of warmup
    assert lrs[-1] == pytest.approx(1e-4, rel=1e-3)
    assert all(a >= b for a, b in zip(lrs[1:], lrs[2:]))   # decays


def test_trainer_loss_decreases():
    cfg = get_smoke_config("qwen2.5-3b")
    model = LM(cfg)
    pcfg = PipelineConfig(global_batch=8, seq_len=32, vocab=cfg.vocab,
                          seed=1)
    data = SyntheticTokens(pcfg)
    tr = Trainer(model, OptimizerConfig(peak_lr=3e-3, warmup_steps=5,
                                        total_steps=60), data)
    params, opt = tr.init(jax.random.key(0))
    params, opt, hist = tr.run(params, opt, num_steps=30, log_every=0)
    first = np.mean([m["loss"] for _, m in hist[:5]])
    last = np.mean([m["loss"] for _, m in hist[-5:]])
    assert last < first, (first, last)
    rep = tr.straggler_report()
    assert "median" in rep


def test_grad_accum_equivalence():
    """grad_accum=2 must match grad_accum=1 on the same global batch."""
    from repro.train import make_train_step, adamw_init
    cfg = get_smoke_config("yi-9b")
    model = LM(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                   jnp.int32)}
    params = model.init(jax.random.key(0))
    ocfg = OptimizerConfig(warmup_steps=1, total_steps=10)
    p1, _, m1 = jax.jit(make_train_step(model, ocfg, grad_accum=1))(
        params, adamw_init(params), batch)
    p2, _, m2 = jax.jit(make_train_step(model, ocfg, grad_accum=2))(
        params, adamw_init(params), batch)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-4)


def test_pipeline_determinism_and_restore():
    cfg = PipelineConfig(global_batch=4, seq_len=16, vocab=100, seed=7)
    a = SyntheticTokens(cfg)
    b1 = next(a)
    state = a.state()
    b2 = next(a)
    b = SyntheticTokens(cfg)
    b.restore(state)
    b2r = next(b)
    np.testing.assert_array_equal(b2["tokens"], b2r["tokens"])
    assert not np.array_equal(b1["tokens"], b2["tokens"])


def test_pipeline_host_sharding():
    full = PipelineConfig(global_batch=8, seq_len=16, vocab=100, seed=3)
    h0 = SyntheticTokens(PipelineConfig(global_batch=8, seq_len=16,
                                        vocab=100, seed=3, host_id=0,
                                        num_hosts=2))
    h1 = SyntheticTokens(PipelineConfig(global_batch=8, seq_len=16,
                                        vocab=100, seed=3, host_id=1,
                                        num_hosts=2))
    b0, b1 = next(h0), next(h1)
    assert b0["tokens"].shape == (4, 16)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_prefetcher():
    cfg = PipelineConfig(global_batch=2, seq_len=8, vocab=50, seed=0)
    pf = Prefetcher(SyntheticTokens(cfg), depth=2)
    batches = [next(pf) for _ in range(4)]
    ref = SyntheticTokens(cfg)
    for b in batches:
        np.testing.assert_array_equal(b["tokens"], next(ref)["tokens"])


def test_heartbeat_and_rescale():
    clock = [0.0]
    mon = HeartbeatMonitor(list(range(8)), timeout_s=10.0,
                           clock=lambda: clock[0])
    clock[0] = 5.0
    for h in range(6):
        mon.beat(h)
    clock[0] = 12.0
    assert set(mon.dead_hosts()) == {6, 7}
    plan = plan_rescale((16, 16), num_alive_devices=208,
                        surviving_hosts=mon.alive_hosts())
    assert plan.new_mesh == (13, 16)
    assert plan.batch_refactor == pytest.approx(16 / 13)


def test_straggler_tracker():
    st = StragglerTracker(range(4))
    for _ in range(5):
        for h in range(4):
            st.record(h, 1.0 if h != 2 else 3.0)
    assert st.stragglers() == [2]
    plan = st.reassignment({h: 4 for h in range(4)})
    assert 2 in plan and plan[2]["to"] != 2


def test_int8_compression_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 64)) * 0.1, jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x)).max()
    assert err <= float(s) * 0.51 + 1e-9      # half-ULP of the quantizer


def test_compressed_psum_inside_shard_map():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    grads = {"w": jnp.ones((8, 8), jnp.float32) * 0.3}

    def f(g):
        out, fb = compressed_psum_tree(g, "pod")
        return out, fb

    out, fb = jax.shard_map(f, mesh=mesh,
                                in_specs=(jax.sharding.PartitionSpec(),),
                                out_specs=jax.sharding.PartitionSpec())(grads)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.3, rtol=0.02)


def test_zero_moment_defs_adds_data_axis():
    skel = {"w": ParamDef((128, 64), ("embed", "mlp"))}
    z = zero_moment_defs(skel)
    assert "zero_data" in z["w"].axes
    assert z["w"].dtype == "float32"
