"""LM text generation: batched prefill + decode with a static-shape KV
cache.

* requests are padded/packed into a fixed (batch, max_len) grid — static
  shapes keep one compiled executable per (batch, len) bucket;
* prefill builds the cache at ``max_len`` capacity; decode then appends
  one token per step for the whole batch in lock-step;
* greedy or temperature sampling (seeded, deterministic).

This is the substrate the decode_32k / long_500k dry-run cells lower
(``serve_step`` = one engine decode step).  Entry point:
``python -m repro.launch.serve``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, ShapeConfig
from repro.models import io as IO
from repro.models import transformer as T


@dataclass
class GenerationResult:
    tokens: np.ndarray        # (batch, generated)
    prefill_logits: np.ndarray


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 256,
                 mesh=None, dp_axes=("data",)):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.mesh = mesh
        self.dp_axes = dp_axes

        def prefill_fn(params, batch):
            return T.prefill(cfg, params, batch, mesh=mesh, dp_axes=dp_axes)

        def decode_fn(params, token, cache, pos):
            return T.decode_step(cfg, params, token, cache, pos,
                                 mesh=mesh, dp_axes=dp_axes)

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))

    def _grow_cache(self, cache, batch: int):
        """Re-home the prefill cache into max_len-capacity buffers."""
        shape = ShapeConfig("serve", "decode", self.max_len, batch)
        full = IO.zero_cache(self.cfg, shape)

        def fit(dst, src):
            sl = tuple(slice(0, s) for s in src.shape)
            return dst.at[sl].set(src.astype(dst.dtype))
        return jax.tree_util.tree_map(fit, full, cache)

    def generate(self, prompts: np.ndarray, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 extra_inputs: dict | None = None) -> GenerationResult:
        """prompts: (batch, prompt_len) int32."""
        B, Lp = prompts.shape
        assert Lp + max_new_tokens <= self.max_len
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.use_mrope:
            pos = jnp.broadcast_to(jnp.arange(Lp, dtype=jnp.int32),
                                   (B, Lp))
            batch["positions"] = jnp.broadcast_to(pos[:, None, :],
                                                  (B, 3, Lp))
        if self.cfg.is_encoder_decoder:
            if extra_inputs is None or "enc_embeds" not in extra_inputs:
                raise ValueError("encdec serving needs enc_embeds")
            batch["enc_embeds"] = jnp.asarray(extra_inputs["enc_embeds"])

        logits, cache = self._prefill(self.params, batch)
        cache = self._grow_cache(cache, B)

        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits, temperature, key)
        out.append(np.asarray(tok))
        pos = Lp
        for i in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            lg, cache = self._decode(self.params, tok, cache,
                                     jnp.asarray(pos, jnp.int32))
            tok = self._sample(lg, temperature, sub)
            out.append(np.asarray(tok))
            pos += 1
        return GenerationResult(
            tokens=np.concatenate(out, axis=1),
            prefill_logits=np.asarray(logits))

    @staticmethod
    def _sample(logits, temperature: float, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        g = jax.random.gumbel(key, logits.shape)
        return jnp.argmax(logits / temperature + g,
                          axis=-1)[:, None].astype(jnp.int32)
