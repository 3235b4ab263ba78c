"""Token-choice top-k MoE layer with expert parallelism (shard_map EP).

Layout (DESIGN.md §5): tokens stay sharded over the data axes, experts are
sharded over the ``model`` axis.  Because TP already leaves activations
replicated across ``model`` at the FFN position, *no all-to-all is needed*:
every model-shard routes the (locally visible) tokens to its own experts and
the combine is the same ``psum`` a dense TP FFN would issue.  This trades
the classical EP all-to-all for (a) replicated routing compute (tiny) and
(b) the TP psum we pay anyway — a deliberately TPU-friendly schedule, and
one of the hillclimb levers examined in EXPERIMENTS §Perf.

Routing: softmax router, top-k, renormalized gates, Switch-style load
balancing aux loss, fixed per-expert capacity C = ceil(T·k/E·cf) with
overflow dropping (capacity_factor 1.25 default).

The local compute is one batched gather → (E_loc, C, D) → SwiGLU expert
matmuls → scatter-add, all MXU-shaped.  A mesh-free dense path (same code,
full expert range) serves single-device smoke tests.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.config.base import ModelConfig
from repro.models import layers as L


def init_moe_params(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": L.init_dense(ks[0], (d, e)),
        "w1": L.init_dense(ks[1], (e, d, f)),
        "w3": L.init_dense(ks[2], (e, d, f)),
        "w2": L.init_dense(ks[3], (e, f, d)),
    }


def capacity(tokens_local: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens_local * cfg.moe_top_k / cfg.num_experts
                  * cfg.capacity_factor)
    return max(4, -(-c // 4) * 4)        # round up to a multiple of 4


def _moe_local(x, router_w, w1, w3, w2, *, cfg: ModelConfig, e_start,
               n_local: int, cap: int):
    """Per-shard MoE compute.

    x: (T, D) local tokens; w1/w3/w2: (n_local, …) local expert slices;
    ``e_start``: first global expert id of this shard (traced or static).
    Returns (partial combine (T, D), aux loss scalar).
    """
    T, D = x.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    dt = x.dtype

    logits = (x @ router_w.astype(dt)).astype(jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_ids = jax.lax.top_k(probs, k)                # (T, k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # Switch-style load-balance aux (computed on full routing, replicated).
    pe = jnp.mean(probs, axis=0)                               # (E,)
    fe = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_ids, E, dtype=jnp.float32), axis=1),
        axis=0) / k
    aux = E * jnp.sum(pe * fe)

    # Position of each (token, choice) within its expert's capacity buffer.
    flat_e = top_ids.reshape(-1)                               # (T·k,)
    flat_g = top_vals.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (T·k, E)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    keep = pos < cap

    # Keep only this shard's expert range; out-of-range → dropped indices.
    e_loc = flat_e - e_start
    in_slice = keep & (e_loc >= 0) & (e_loc < n_local)
    e_safe = jnp.where(in_slice, e_loc, 0)
    p_safe = jnp.where(in_slice, pos, 0)

    buf = jnp.full((n_local, cap), T, jnp.int32)               # T ⇒ zero row
    buf = buf.at[e_safe, p_safe].set(
        jnp.where(in_slice, flat_t, T), mode="drop")
    gbuf = jnp.zeros((n_local, cap), jnp.float32)
    gbuf = gbuf.at[e_safe, p_safe].set(
        jnp.where(in_slice, flat_g, 0.0), mode="drop")

    x_pad = jnp.concatenate([x, jnp.zeros((1, D), dt)], axis=0)
    xg = x_pad[buf]                                            # (E_loc, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w1.astype(dt))) \
        * jnp.einsum("ecd,edf->ecf", xg, w3.astype(dt))
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(dt))         # (E_loc, C, D)
    out = out * gbuf[..., None].astype(dt)

    y = jnp.zeros((T + 1, D), jnp.float32)
    y = y.at[buf.reshape(-1)].add(
        out.reshape(-1, D).astype(jnp.float32))
    return y[:T].astype(dt), aux


def moe_layer(params, x, cfg: ModelConfig, *, mesh=None,
              dp_axes=("data",), tp_axis: str = "model"):
    """MoE FFN over x: (B, S, D).  Returns (y, aux_loss).

    With ``mesh`` given, runs the shard_map EP path (experts over
    ``tp_axis``, tokens over ``dp_axes``); otherwise the dense single-shard
    path (smoke tests / CPU examples).
    """
    B, S, D = x.shape

    if mesh is None:
        cap = capacity(B * S, cfg)
        y, aux = _moe_local(
            x.reshape(B * S, D), params["router"], params["w1"],
            params["w3"], params["w2"], cfg=cfg, e_start=0,
            n_local=cfg.num_experts, cap=cap)
        return y.reshape(B, S, D), aux

    from jax.sharding import PartitionSpec as P
    tp_size = mesh.shape[tp_axis]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    n_local = cfg.num_experts // tp_size
    t_local = (B // dp_size) * S
    cap = capacity(t_local, cfg)

    def shard_fn(x_blk, router_w, w1, w3, w2):
        bs, s, d = x_blk.shape
        e_start = jax.lax.axis_index(tp_axis) * n_local
        y, aux = _moe_local(
            x_blk.reshape(bs * s, d), router_w, w1, w3, w2, cfg=cfg,
            e_start=e_start, n_local=n_local, cap=cap)
        y = jax.lax.psum(y, tp_axis)          # combine expert partials (TP sum)
        aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(bs, s, d), aux

    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    y, aux = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["w1"], params["w3"], params["w2"])
    return y, aux
