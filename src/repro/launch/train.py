"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_3b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real TPU fleet this binary runs once per host (jax.distributed
initializes from TPU metadata); in this container it drives the same code
single-process. --mesh data,model shapes a device mesh over the visible
devices and shards params/optimizer/batch with the LP-derived specs.
"""

from __future__ import annotations

import argparse
import logging

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (full configs need a real pod)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default=None, help="token file (uint32)")
    ap.add_argument("--mesh", default=None,
                    help="e.g. '2,4' -> (data=2, model=4) over local devices")
    ap.add_argument("--backend", choices=("xla", "pallas", "im2col"),
                    default=None,
                    help="kernel backend override; default resolves from "
                         "REPRO_BACKEND and then the --target preset")
    ap.add_argument("--target", default=None,
                    help="hardware target preset (tpu_v5e | gemmini | "
                         "cpu_interpret); sets the plan/precision policy "
                         "and the default backend. Default: the attached "
                         "device's preset")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--remat", action="store_true")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    from repro.configs import get_config, get_smoke
    from repro.data.pipeline import DataConfig
    from repro.launch.devices import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.ops import ExecutionContext, default_context
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer

    use_compile_cache()
    backend = args.backend
    if args.target:
        from repro.plan import get_target

        ctx = ExecutionContext(target=get_target(args.target),
                               backend=backend).resolved()
    else:
        ctx = default_context() if backend is None else \
            default_context().with_backend(backend)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model")[:len(shape)])

    dcfg = DataConfig(batch_size=args.batch, seq_len=args.seq,
                      vocab_size=cfg.vocab_size, path=args.data)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                       total_steps=args.steps)
    tcfg = TrainConfig(steps=args.steps, microbatches=args.microbatches,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       remat=args.remat, ctx=ctx,
                       compress_grads=args.compress_grads,
                       n_groups=max(1, np.gcd(args.batch * args.seq,
                                              len(jax.devices()))))
    trainer = Trainer(cfg, ocfg, tcfg, dcfg, mesh=mesh)
    hist = trainer.run()
    if hist["loss"]:
        print(f"final loss {hist['loss'][-1]:.4f} over {len(hist['loss'])} steps "
              f"({np.mean(hist['step_time'][1:] or hist['step_time']):.3f}s/step, "
              f"skipped={trainer.skipped_steps})")


if __name__ == "__main__":
    main()
