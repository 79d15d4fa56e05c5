"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
        --steps 100 --batch 8 --seq 64 --data 2 --model 2 [--smoke] \
        [--zero1] [--loss-chunk 512] [--seq-shard] [--grad-accum 2]

``--data/--model`` build a local mesh over the visible devices (use
``--devices N`` to force a host-device count for mesh experiments).  With
``--smoke`` the reduced same-family config is used (CPU-friendly); without
it the full assigned config is instantiated — expect accelerator-scale
memory.  Checkpoints are atomic + resumable: re-running with the same
--workdir continues from the last commit.
"""
import argparse
import dataclasses
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pod", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force this many CPU host devices (set BEFORE jax import)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "none"])
    ap.add_argument("--straggler-deadline", type=float, default=None)
    args = ap.parse_args()

    if args.devices:
        # forced host devices live on the CPU backend, never on a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax

    from ..configs import get_config, smoke_config
    from ..data import ShardedLoader
    from ..data.prefetch import PrefetchingFeed
    from ..models import init_params
    from ..optim import OptConfig
    from ..train import Trainer, TrainerConfig
    from .compile_cache import enable_compile_cache
    from .mesh import make_host_mesh

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.loss_chunk:
        cfg = dataclasses.replace(cfg, loss_chunk=args.loss_chunk)
    if args.seq_shard:
        cfg = dataclasses.replace(cfg, seq_shard_acts=True)

    mesh = None
    if args.data * args.model * max(1, args.pod) > 1:
        mesh = make_host_mesh(data=args.data, model=args.model,
                              pod=args.pod or None)

    print(f"arch={cfg.name} ({cfg.n_params()/1e6:.1f}M params) "
          f"mesh={dict(mesh.shape) if mesh else None} steps={args.steps}")
    params = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    loader = ShardedLoader(cfg, global_batch=args.batch, seq_len=args.seq)
    feed = PrefetchingFeed(loader.batch_at, depth=2)

    trainer = Trainer(
        cfg, params, mesh=mesh,
        opt_cfg=OptConfig(peak_lr=args.lr, warmup_steps=max(5, args.steps // 20),
                          total_steps=args.steps, zero1=args.zero1),
        tcfg=TrainerConfig(steps=args.steps,
                           checkpoint_every=max(10, args.steps // 5),
                           log_every=max(1, args.steps // 20),
                           grad_accum=args.grad_accum, remat=args.remat,
                           straggler_deadline_s=args.straggler_deadline),
        workdir=args.workdir,
        batch_at=feed.get_batch,
    )
    try:
        out = trainer.run()
    finally:
        feed.close()
    print(f"final step {out['final_step']}  loss {out['final_loss']:.4f}  "
          f"stragglers {out['stragglers']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
