"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
        --reduced --batch 4 --prompt-len 16 --new-tokens 32

On the CPU container this serves reduced configs; on a TPU fleet the same
entry point shards the full configs over ``make_production_mesh()``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs.registry import get_config, get_reduced
from repro.models import transformer as T
from repro.models.generate import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="none",
                    choices=("none", "single", "multi"))
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = None
    dp_axes = ("data",)
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        dp_axes = ("pod", "data") if args.mesh == "multi" else ("data",)

    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params,
                      max_len=args.prompt_len + args.new_tokens,
                      mesh=mesh, dp_axes=dp_axes)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extra = None
    if cfg.is_encoder_decoder:
        extra = {"enc_embeds": rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)}

    t0 = time.perf_counter()
    res = eng.generate(prompts, max_new_tokens=args.new_tokens,
                       temperature=args.temperature, seed=args.seed,
                       extra_inputs=extra)
    dt = time.perf_counter() - t0
    n = args.batch * args.new_tokens
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new_tokens}: {n} tokens in {dt:.2f}s "
          f"({n/dt:.0f} tok/s)")
    for b in range(min(2, args.batch)):
        print(f"  seq[{b}]: {res.tokens[b][:16].tolist()}")


if __name__ == "__main__":
    main()
