"""Batched serving example: prefill + decode with the ServeEngine.

    PYTHONPATH=src python examples/serve_lm.py [--arch mamba2-1.3b]
"""
import argparse
import time

import numpy as np
import jax

from repro.configs.registry import get_reduced
from repro.models import transformer as T
from repro.models.generate import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=64)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, 16)).astype(np.int32)
    extra = None
    if cfg.is_encoder_decoder:
        extra = {"enc_embeds": rng.standard_normal(
            (args.batch, 16, cfg.d_model)).astype(np.float32)}

    t0 = time.perf_counter()
    res = eng.generate(prompts, max_new_tokens=args.new_tokens,
                       temperature=0.8, seed=1, extra_inputs=extra)
    dt = time.perf_counter() - t0
    toks = args.batch * args.new_tokens
    print(f"arch={cfg.name} batch={args.batch} "
          f"generated {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.0f} tok/s on 1 CPU core, reduced config)")
    print("sample token ids:", res.tokens[0][:12].tolist())


if __name__ == "__main__":
    main()
