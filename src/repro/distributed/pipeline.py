"""GPipe-style pipeline parallelism over the ``data`` mesh axis.

Motivation (EXPERIMENTS.md §Perf, deepseek-67b × train_4k): with FSDP×TP×SP
the dominant roofline term is collective time — layer weights are
re-gathered over the data axis for every forward/remat/backward pass of
every microbatch, and sequence-parallel boundaries all-gather activations
per layer (measured 52.7 s of ICI time per step at mb=4).  Pipeline
parallelism makes stage weights *stationary*: inter-stage traffic is one
microbatch activation per boundary per tick — a ~10³× reduction in weight-
movement bytes for deep dense models.

Design:
* mesh axis ``data`` (16) becomes the **stage** axis; ``model`` (16) stays
  an *auto* axis inside the shard_map, so TP/SP still partition the stage
  body via GSPMD;
* layers split contiguously: stacked (L, ...) params sharded over ``data``
  on the layer dim (L/P layers per stage, feature dims TP-sharded);
* schedule: GPipe fill-drain, ``T = n_micro + P − 1`` ticks, one
  ``ppermute`` shift per tick; bubble ticks compute on junk and their
  outputs are masked;
* the pipeline emits final-norm'ed last-stage activations only; the loss
  runs *outside*, data-parallel, through the existing vocab-chunked fused
  xent — computing logits inside the schedule would replicate that matmul
  across all stages × ticks (a ~16× logits-FLOPs blowup, rejected during
  design);
* backward = jax autodiff through the schedule (reverse ppermutes are
  generated automatically); the stage body is rematerialized per tick.

Bubble fraction = (P−1)/(n_micro+P−1); n_micro is a knob (default 16 ⇒ 48%
fill-drain overhead on paper, amortizable by raising n_micro — recorded in
EXPERIMENTS.md, where the collective term is the objective).

Scope: dense/vlm decoder stacks (uniform layers).  Other families keep
FSDP×TP — strategy selection per arch is launcher policy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T


def supports_pipeline(cfg: ModelConfig) -> bool:
    return cfg.family in ("dense", "vlm")


def pipeline_loss_fn(cfg: ModelConfig, params, batch, dist,
                     n_micro: int = 16):
    """Pipelined train loss.  Same contract as T.loss_fn."""
    mesh = dist.mesh
    stage_axis = "data"
    n_stages = mesh.shape[stage_axis]
    L_total = cfg.num_layers
    assert supports_pipeline(cfg), cfg.family
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    T_ticks = n_micro + n_stages - 1

    # Indivisible depths (e.g. deepseek's 95 layers over 16 stages) are
    # padded with zero layers — exactly the identity for pre-norm residual
    # blocks (every sub-block contributes additively through zero weights),
    # costing 1/96 of the compute and nothing in correctness.
    pad = (-L_total) % n_stages
    layers = params["layers"]
    if pad:
        layers = jax.tree_util.tree_map(
            lambda t: jnp.concatenate(
                [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)], axis=0),
            layers)
    L_eff = L_total + pad
    stage_params = jax.tree_util.tree_map(
        lambda t: t.reshape((n_stages, L_eff // n_stages) + t.shape[1:]),
        layers)

    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (mb, S))
    if cfg.use_mrope:
        positions = jnp.broadcast_to(positions[:, None, :], (mb, 3, S))

    # Two-level remat: the OUTER checkpoint makes each tick save only its
    # (mb, S, D) input — without it the per-tick stash holds every layer
    # boundary of every in-flight microbatch (measured 37 GB/device); the
    # inner per-layer checkpoint keeps the recompute-pass working set at
    # one layer.  Cost: one extra stage-forward per tick (~+33% FLOPs),
    # traded for ~18× stash memory — the classic GPipe trade.
    @jax.checkpoint
    def stage_body(sp, x):
        def body(h, p):
            h, _ = T._dense_block(p, h, positions, cfg)
            return h, None
        x, _ = jax.lax.scan(jax.checkpoint(body), x, sp,
                            unroll=T._unroll())
        return x

    def shard_fn(tok_mb, sp, embed_tab, final_norm):
        """Manual over `data` (stages), auto over `model` (TP/SP)."""
        # local view keeps a leading size-1 stage dim — drop it
        sp = jax.tree_util.tree_map(lambda t: t[0], sp)
        stage = jax.lax.axis_index(stage_axis)
        first = stage == 0
        last = stage == n_stages - 1

        # Sequence-shard the tick carries/emissions over the (auto) model
        # axis: without the constraint GSPMD replicates them, and the
        # scan's saved-per-tick residuals blow up 16× (observed 62 GB/dev).
        # A bare PartitionSpec resolves against the (partial-manual)
        # context mesh — a concrete NamedSharding would not match it.
        seq_sharded = P(None, "model", None)

        def tick(carry, t):
            x_prev, acc = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            tok = jax.lax.dynamic_index_in_dim(tok_mb, mb_idx, 0, False)
            x0 = embed_tab.astype(dt)[tok]            # (mb, S, D)
            x_in = jnp.where(first, x0, x_prev)
            # x_in is the checkpointed stage body's saved input (one per
            # tick): it must be sequence-sharded or the stash replicates.
            x_in = jax.lax.with_sharding_constraint(x_in, seq_sharded)
            y = stage_body(sp, x_in)
            y = jax.lax.with_sharding_constraint(y, seq_sharded)

            # Drain: write this tick's output into the accumulator slot
            # (predicated read-modify-write — bubbles rewrite their own
            # slot's current value, a no-op).
            out_idx = t - (n_stages - 1)
            valid = last & (out_idx >= 0)
            slot = jnp.clip(out_idx, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(acc, slot, 0, False)
            y_out = jnp.where(
                valid, L.rms_norm(y, final_norm, cfg.norm_eps).astype(dt),
                cur)
            acc = jax.lax.dynamic_update_index_in_dim(acc, y_out, slot, 0)

            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            x_next = jax.lax.ppermute(y, stage_axis, perm)
            return (x_next, acc), None

        x0 = jnp.zeros((mb, S, cfg.d_model), dt)
        acc0 = jax.lax.with_sharding_constraint(
            jnp.zeros((n_micro, mb, S, cfg.d_model), dt),
            P(None, None, "model", None))
        (_, acc), _ = jax.lax.scan(tick, (x0, acc0), jnp.arange(T_ticks))
        # acc is zero on every stage but the last (bubble slots rewrite
        # their own zero); the cross-stage reduction happens OUTSIDE the
        # manual region (psum of partial-auto values crashes XLA here).
        return acc[None]                              # (1, n_micro, mb, S, D)

    tok_mb = tokens.reshape(n_micro, mb, S)
    # Manual over the stage axis only; `model` (and `pod`) stay auto —
    # GSPMD keeps TP/SP partitioning inside the stage body.  The mesh
    # context lets the bare PartitionSpec constraints inside shard_fn
    # resolve on jax versions that require an ambient mesh.
    with mesh:
        buf = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), jax.tree_util.tree_map(
                lambda _: P(stage_axis), stage_params),
                P(), P()),
            out_specs=P(stage_axis),              # (P, n_micro, mb, S, D)
            check_vma=False,
            axis_names=frozenset({stage_axis}),
        )(tok_mb, stage_params, params["embed"], params["final_norm"])
    # Sum over the stage-sharded dim (all-zero except the last stage):
    # GSPMD lowers this to a local reduce + one activation-sized psum.
    x_last = jnp.sum(buf, axis=0, dtype=jnp.float32).astype(dt)
    x_full = x_last.reshape(B, S, cfg.d_model)
    x_full = jax.lax.with_sharding_constraint(
        x_full, NamedSharding(mesh, P(dist.dp, "model", None)))

    loss = T.fused_logits_xent(
        x_full, T.lm_head_table(cfg, params), labels, mesh, dist.dp_axes)
    return loss, {"xent": loss, "aux": jnp.asarray(0.0, jnp.float32)}
