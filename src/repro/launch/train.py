"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt --ckpt-strategy merged_process

On this container the full configs are dry-run-only; ``--smoke`` selects the
reduced config (trainable on CPU).  On a real pod the same launcher runs the
full config on the production mesh (``--mesh production``).
"""

from __future__ import annotations

import argparse

import jax

from ..checkpoint import CheckpointManager
from ..configs import get_config, get_smoke_config, list_archs
from ..data.pipeline import PipelineConfig, make_pipeline
from ..distributed import sharding as shd
from ..models import LM
from ..train import OptimizerConfig, Trainer
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh, make_production_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "production", "production-multi"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-strategy", default="merged_process")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = LM(cfg)
    print(f"arch={cfg.name} params={model.num_params():,}")

    mesh = {"host": make_host_mesh,
            "production": lambda: make_production_mesh(multi_pod=False),
            "production-multi": lambda: make_production_mesh(multi_pod=True),
            }[args.mesh]()
    rules = shd.FSDP_RULES if cfg.fsdp else shd.DEFAULT_RULES

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir,
                                 strategy=args.ckpt_strategy, keep=2)

    pcfg = PipelineConfig(global_batch=args.global_batch,
                          seq_len=args.seq_len, vocab=cfg.vocab,
                          seed=args.seed, frontend=cfg.frontend,
                          d_model=cfg.d_model)
    src, data = make_pipeline(pcfg, prefetch=2)

    with shd.use_sharding(mesh, rules), mesh:
        tr = Trainer(model,
                     OptimizerConfig(peak_lr=args.lr, warmup_steps=10,
                                     total_steps=max(args.steps, 100)),
                     data, ckpt_manager=ckpt, ckpt_every=args.ckpt_every)
        params, opt = tr.init(jax.random.key(args.seed))
        if args.resume and ckpt is not None and ckpt.steps():
            step, params = ckpt.restore_latest(template=params)
            tr.state.step = step
            src.restore({"step": step})
            print(f"resumed from step {step}")
        params, opt, hist = tr.run(params, opt, num_steps=args.steps,
                                   log_every=10)
    print("straggler report:", tr.straggler_report())
    if ckpt is not None:
        stats = ckpt.save(tr.state.step, params)
        print(f"checkpoint: {stats.num_original_blocks} blocks -> "
              f"{stats.num_chunks} chunks ({stats.bytes / 1e6:.1f} MB)")


if __name__ == "__main__":
    main()
