"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 300 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

``--smoke`` selects the reduced config (CPU-feasible); without it the full
config is used (meant for a real pod; on this container it would not fit).
``--devices N`` forces N host devices (via XLA flags) and trains on an
(N/model_parallel, model_parallel) mesh — the launcher path a pod slice uses.
"""
import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices and shard over them")
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    from repro.configs import base as CB
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import OptHParams
    from repro.train.trainer import Trainer, TrainConfig

    cfg = CB.get_config(args.arch, smoke=args.smoke)
    mesh = None
    if args.devices:
        mp = args.model_parallel
        assert args.devices % mp == 0
        mesh = make_mesh((args.devices // mp, mp), ("data", "model"))

    tc = TrainConfig(seq_len=args.seq, global_batch=args.batch,
                     microbatches=args.microbatches, num_steps=args.steps,
                     log_every=args.log_every, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir, seed=args.seed)
    hp = OptHParams(learning_rate=args.lr, warmup_steps=max(args.steps // 10, 1),
                    decay_steps=args.steps)
    trainer = Trainer(cfg, tc, hp=hp, mesh=mesh)
    if trainer.maybe_restore():
        print(f"resumed from step {trainer.step}", flush=True)
    print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) "
          f"on {jax.device_count()} device(s)", flush=True)
    final = trainer.run()
    print(f"done: step {trainer.step} loss {final['loss']:.4f}")
    if trainer.monitor.flagged:
        print(f"straggler flags: {trainer.monitor.flagged}")
    return 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    sys.exit(main())
