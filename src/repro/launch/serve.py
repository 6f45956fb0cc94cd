"""Serving launcher: batched greedy decoding on a trained or random model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --batch 4 --max-new 32 [--ckpt-dir ckpts/run0]
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, get_reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import BatchedServer, checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = build_model(cfg)
    if args.ckpt_dir:
        params, step, _ = ckpt.restore(args.ckpt_dir,
                                       {"params": model.abstract_params(),
                                        "opt": None})
        params = params["params"]
        print(f"restored params from step {step}")
    else:
        params = model.init(jax.random.PRNGKey(args.seed))

    server = BatchedServer(model, params, batch=args.batch,
                           max_len=args.max_len)
    prompts = [[1 + (i * 7 + j) % (cfg.vocab_size - 1) for j in range(8)]
               for i in range(args.batch)]
    t0 = time.perf_counter()
    outs = server.generate(prompts, args.max_new)
    dt = time.perf_counter() - t0
    for i, o in enumerate(outs):
        print(f"req{i}: {o[:16]}{'...' if len(o) > 16 else ''}")
    tok = server.stats.tokens_out
    print(f"{tok} tokens in {dt:.2f}s = {tok/dt:.1f} tok/s "
          f"({server.stats.steps} decode steps)")


if __name__ == "__main__":
    main()
