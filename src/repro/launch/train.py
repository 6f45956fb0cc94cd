"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 200 --batch 8 --seq 128 [--crossbar] [--ckpt-dir ckpts/run0]

Uses the reduced config on CPU; on a real pod drop --reduced and pass
--mesh single|multi (the launcher then builds the production mesh and
expects 256/512 devices from the runtime).
"""
from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import get_config, get_reduced_config
from repro.data.pipeline import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim import adamw, cosine_schedule, make_optimizer
from repro.runtime import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--crossbar", action="store_true",
                    help="enable the paper's crossbar execution mode")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "pulse_sgd"])
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "single", "multi"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.crossbar:
        cfg = cfg.replace(crossbar=True)

    mesh = None
    if args.mesh == "host":
        mesh = make_host_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    lr = cosine_schedule(args.lr, warmup_steps=max(args.steps // 20, 1),
                         total_steps=args.steps)
    opt = make_optimizer(args.optimizer, lr)
    trainer = Trainer(cfg, opt, mesh=mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, seed=args.seed)
    stream = TokenStream(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    state, hist = trainer.run(stream, args.steps)
    print(f"final step {state.step}: loss {hist[-1]['loss']:.4f} "
          f"(first {hist[0]['loss']:.4f})")
    if trainer.watchdog.events:
        print(f"straggler events: {trainer.watchdog.events}")


if __name__ == "__main__":
    main()
