"""LM substrate micro-benchmarks (CPU-scale, reduced configs).

Times one jitted train step and one decode step per architecture family —
the wall numbers are CPU-only sanity signals; the TPU performance story
lives in the dry-run roofline (EXPERIMENTS.md §Roofline/§Perf).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.config.base import ShapeConfig, TrainConfig
from repro.configs.registry import get_reduced
from repro.core.optimizer import get_optimizer
from repro.models import io as IO
from repro.models import transformer as T

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"
FAMILIES = ["yi-6b", "qwen3-moe-30b-a3b", "mamba2-1.3b", "zamba2-1.2b",
            "seamless-m4t-large-v2"]


def bench_arch(arch: str, steps: int = 5) -> dict:
    cfg = get_reduced(arch)
    shape = ShapeConfig("bench", "train", 64, 4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = IO.random_batch(cfg, shape)
    opt_init, opt_update = get_optimizer(TrainConfig(optimizer="flexa"))
    opt_state = opt_init(params)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, _), g = jax.value_and_grad(
            lambda p: T.loss_fn(cfg, p, batch), has_aux=True)(params)
        p2, o2, _ = opt_update(g, opt_state, params, loss)
        return p2, o2, loss

    # warmup/compile
    params, opt_state, _ = step(params, opt_state, batch)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    train_us = (time.perf_counter() - t0) / steps * 1e6

    # decode step
    dshape = ShapeConfig("d", "decode", 64, 4)
    cache = IO.zero_cache(cfg, dshape)
    tok = jnp.zeros((4, 1), jnp.int32)

    @jax.jit
    def dstep(params, tok, cache, pos):
        return T.decode_step(cfg, params, tok, cache, pos)

    lg, cache = dstep(params, tok, cache, 0)
    jax.block_until_ready(lg)
    t0 = time.perf_counter()
    for i in range(steps):
        lg, cache = dstep(params, tok, cache, i + 1)
    jax.block_until_ready(lg)
    decode_us = (time.perf_counter() - t0) / steps * 1e6
    return {"arch": arch, "train_us": round(train_us),
            "decode_us": round(decode_us)}


def main() -> list[dict]:
    RESULTS.mkdir(parents=True, exist_ok=True)
    rows = [bench_arch(a) for a in FAMILIES]
    (RESULTS / "lm_step.json").write_text(json.dumps(rows, indent=2))
    return rows


if __name__ == "__main__":
    from repro.launch.runtime import device_banner
    print(device_banner())
    for r in main():
        print(r)
