"""Training launcher: ``python -m repro.launch.train --arch olmo-1b ...``

Runs real training (synthetic data) on whatever devices exist; ``--mesh
DxM`` builds a (data, model) mesh over them — the same code path the
production mesh uses.  ``--devices N`` is for a CPU rehearsal only: it
forces N host platform devices (set before jax initializes).
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU rehearsal only: force N host platform devices")
    ap.add_argument("--mesh", default="", help="e.g. 4x2 -> (data=4, model=2)")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--preemption-file", default=None)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={args.devices}",
        )))

    import jax

    from ..configs import get_arch
    from ..training.optimizer import AdamWConfig
    from ..training.train_step import TrainConfig
    from ..training.trainer import Trainer, TrainerConfig
    from .compile_cache import use_compile_cache
    from .mesh import make_mesh

    use_compile_cache()
    cfg = get_arch(args.arch).config
    if args.smoke:
        cfg = cfg.reduced()

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    tc = TrainConfig(
        adamw=AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 10, 1)),
        microbatches=args.microbatches,
    )
    trainer = Trainer(
        cfg,
        tc,
        TrainerConfig(
            total_steps=args.steps,
            ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt_dir,
            preemption_file=args.preemption_file,
        ),
        mesh=mesh,
    )
    state = trainer.run()
    final = trainer.metrics_log[-1] if trainer.metrics_log else {}
    print(f"done at step {int(jax.device_get(state['step']))}: {final}")


if __name__ == "__main__":
    main()
