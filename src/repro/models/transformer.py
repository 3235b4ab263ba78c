"""Model assembly for all assigned architecture families.

One parameter-dict + pure-function design:

  init_params(cfg, key)                      → pytree (stacked layer dims)
  forward(cfg, params, batch, mesh)          → logits (train/prefill path)
  loss_fn(cfg, params, batch, mesh)          → scalar loss (+ MoE aux)
  prefill(cfg, params, batch, mesh)          → (last-token logits, cache)
  decode_step(cfg, params, token, cache, pos, mesh) → (logits, new cache)

Layer stacks run under ``lax.scan`` with per-layer ``jax.checkpoint``
(remat): the HLO stays one-layer-sized (fast 512-device AOT compiles) and
activation memory is one (B, S, D) carry per layer.

Families: dense / moe (token-choice EP) / ssm (Mamba2) / hybrid (Zamba2:
Mamba2 backbone + ONE shared attention+MLP block applied every
``attn_every`` layers — shared weights, per-application KV caches) /
encdec (Seamless backbone, stubbed frontend) / vlm (Qwen2-VL backbone,
M-RoPE, stubbed vision tower).
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config.base import ModelConfig
from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

AUX_WEIGHT = 0.01  # MoE load-balance loss weight


def _unroll() -> int:
    """Scan unroll factor (roofline FLOPs disaggregation, see dryrun)."""
    return int(os.environ.get("REPRO_SCAN_UNROLL", "1"))


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _c(x, mesh, dp_axes):
    """Constrain boundary activations: batch → dp axes, sequence → model.

    Two effects, both essential at 512 devices:
    * without any constraint GSPMD can leave scan carries replicated
      (observed: 32× activation blowup on the first dry-run cell);
    * sharding only the batch 16-way leaves 0.8 GB/device/layer of remat
      saves (observed) — sharding the *sequence* dim over the ``model`` axis
      at layer boundaries (sequence parallelism: norms/residuals are
      elementwise over S) shrinks saves by another 16×; GSPMD inserts the
      all-gather/reduce-scatter pair around attention exactly as Megatron-SP
      does explicitly.
    """
    if mesh is None:
        return x
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    if x.shape[0] == 1:
        dp = None                          # batch-1 long-context cells
    if x.ndim == 3 and x.shape[1] > 1 and "model" not in dp_axes:
        spec = P(dp, "model", None)        # sequence-parallel boundary
    else:
        # ZeRO-3 layout: the model axis already carries batch shards.
        spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# Ambient activation-sharding constraint, installed by forward/prefill/
# decode_step for the duration of a trace (single-threaded tracing).
_CON = None


def _install_con(mesh, dp_axes):
    global _CON
    _CON = (lambda t: _c(t, mesh, dp_axes)) if mesh is not None else None


def _con_carry(c):
    if _CON is None:
        return c
    # Only 3-D (B, S, D) activations; caches/states carried through decode
    # loops keep their own layouts.
    return jax.tree_util.tree_map(
        lambda t: _CON(t) if getattr(t, "ndim", 0) == 3 else t, c)


def _rscan(body, init, xs):
    """Remat layer scan with carry-sharding constraint + unroll control."""
    def b2(c, x):
        c2, y = body(c, x)
        return _con_carry(c2), y
    return jax.lax.scan(jax.checkpoint(b2), init, xs, unroll=_unroll())


def _pscan(body, init, xs):
    """Plain (no-remat) scan — decode paths."""
    def b2(c, x):
        c2, y = body(c, x)
        return _con_carry(c2), y
    return jax.lax.scan(b2, init, xs, unroll=_unroll())


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _embed(tokens, table, dt, mesh, dp_axes):
    """Token embedding with a distribution-aware gradient path.

    Table layout is (vocab replicated, d_model → "model").  The forward
    gather is local either way; the *backward* is the trap — GSPMD lowers
    the gather's transpose to a full replicated (V, D) fp32 scatter +
    all-reduce (3.4 GB/device at 67B scale, measured).  Under shard_map the
    transpose stays local: a (V, D/16) scatter-add and a psum over the data
    axes only of the 16×-smaller shard.
    """
    if mesh is None:
        return L.embed(tokens, table, dt)
    # The embed/xent shard_maps use `model` for the feature/seq dims; under
    # ZeRO-3 the model axis carries batch elsewhere — strip it here (the
    # boundary reshard is one small activation copy).
    dp_axes = tuple(a for a in dp_axes if a != "model") or ("data",)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    # batch=1 long-context cells can't split the batch: replicate it.
    dp = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) \
        if tokens.shape[0] % dp_size == 0 else None

    def f(tok, tab):
        return tab.astype(dt)[tok]          # fully local: (B_l, S, D_l)

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, None), P(None, "model")),
        out_specs=P(dp, None, "model"),
        check_vma=False,
    )(tokens, table)


# ===================================================================== #
# Parameter initialization                                              #
# ===================================================================== #
def _init_dense_layer(cfg: ModelConfig, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "attn": ATT.init_attn_params(k1, cfg),
        "ln2": jnp.ones((d,), jnp.float32),
        "mlp": {"w1": L.init_dense(k2, (d, f)),
                "w3": L.init_dense(k3, (d, f)),
                "w2": L.init_dense(k4, (f, d))},
    }


def _init_moe_layer(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": ATT.init_attn_params(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "moe": MOE.init_moe_params(k2, cfg),
    }


def _init_ssm_layer(cfg: ModelConfig, key):
    return {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ssm": SSM.init_ssm_params(key, cfg),
    }


def _init_cross_layer(cfg: ModelConfig, key):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "self_attn": ATT.init_attn_params(k1, cfg),
        "ln2": jnp.ones((d,), jnp.float32),
        "cross_attn": ATT.init_attn_params(k2, cfg),
        "ln3": jnp.ones((d,), jnp.float32),
        "mlp": {"w1": L.init_dense(k3, (d, f)),
                "w3": L.init_dense(k4, (d, f)),
                "w2": L.init_dense(k5, (f, d))},
    }


def init_params(cfg: ModelConfig, key):
    kE, kL, kS, kH = jax.random.split(key, 4)
    params = {
        "embed": jax.random.normal(kE, (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            kH, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _stack_init(
            partial(_init_dense_layer, cfg), kL, cfg.num_layers)
    elif fam == "moe":
        params["layers"] = _stack_init(
            partial(_init_moe_layer, cfg), kL, cfg.num_layers)
    elif fam == "ssm":
        params["layers"] = _stack_init(
            partial(_init_ssm_layer, cfg), kL, cfg.num_layers)
    elif fam == "hybrid":
        params["layers"] = _stack_init(
            partial(_init_ssm_layer, cfg), kL, cfg.num_layers)
        params["shared"] = _init_dense_layer(cfg, kS)  # ONE shared block
    elif fam == "encdec":
        params["enc_layers"] = _stack_init(
            partial(_init_dense_layer, cfg), kL, cfg.enc_layers)
        params["dec_layers"] = _stack_init(
            partial(_init_cross_layer, cfg), kS, cfg.num_layers)
    else:
        raise ValueError(fam)
    return params


def lm_head_table(cfg: ModelConfig, params):
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


# ===================================================================== #
# Layer bodies (shared by forward / prefill)                            #
# ===================================================================== #
def _dense_block(p, h, positions, cfg, *, causal=True, collect_kv=False):
    a, kv = ATT.attention_layer(
        p["attn"], L.rms_norm(h, p["ln1"], cfg.norm_eps), positions, cfg,
        causal=causal)
    h = h + a
    h = h + L.swiglu(L.rms_norm(h, p["ln2"], cfg.norm_eps),
                     p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"])
    return (h, kv) if collect_kv else (h, None)


def _moe_block(p, h, positions, cfg, mesh, dp_axes, *, collect_kv=False):
    a, kv = ATT.attention_layer(
        p["attn"], L.rms_norm(h, p["ln1"], cfg.norm_eps), positions, cfg)
    h = h + a
    y, aux = MOE.moe_layer(p["moe"], L.rms_norm(h, p["ln2"], cfg.norm_eps),
                           cfg, mesh=mesh, dp_axes=dp_axes)
    return h + y, aux, (kv if collect_kv else None)


def _ssm_block(p, h, cfg):
    return h + SSM.ssm_layer(p["ssm"],
                             L.rms_norm(h, p["ln1"], cfg.norm_eps), cfg)


# ===================================================================== #
# Forward (train) per family                                            #
# ===================================================================== #
def forward_hidden(cfg: ModelConfig, params, batch, mesh=None,
                   dp_axes=("data",)):
    """Full-sequence forward up to the final norm → (hidden, aux loss)."""
    dt = _dtype(cfg)
    fam = cfg.family
    tokens = batch["tokens"]
    B, S = tokens.shape
    _install_con(mesh, dp_axes)
    x = _c(_embed(tokens, params["embed"], dt, mesh, dp_axes), mesh, dp_axes)
    if cfg.use_mrope:
        positions = batch["positions"]          # (B, 3, S)
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    aux_total = jnp.asarray(0.0, jnp.float32)

    if fam in ("dense", "vlm"):
        def body(h, p):
            h, _ = _dense_block(p, h, positions, cfg)
            return h, None
        x, _ = _rscan(body, x, params["layers"])

    elif fam == "moe":
        def body(h, p):
            h, aux, _ = _moe_block(p, h, positions, cfg, mesh, dp_axes)
            return h, aux
        x, auxs = _rscan(body, x, params["layers"])
        aux_total = jnp.sum(auxs)

    elif fam == "ssm":
        def body(h, p):
            return _ssm_block(p, h, cfg), None
        x, _ = _rscan(body, x, params["layers"])

    elif fam == "hybrid":
        x = _hybrid_forward(cfg, params, x, positions)

    elif fam == "encdec":
        enc = batch["enc_embeds"].astype(dt)
        epos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None, :],
                                (B, enc.shape[1]))

        def ebody(h, p):
            h, _ = _dense_block(p, h, epos, cfg, causal=False)
            return h, None
        enc_out, _ = _rscan(ebody, enc,
                                  params["enc_layers"])

        def dbody(h, p):
            h, _ = _dec_block(p, h, positions, enc_out, cfg)
            return h, None
        x, _ = _rscan(dbody, x, params["dec_layers"])
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def forward(cfg: ModelConfig, params, batch, mesh=None, dp_axes=("data",)):
    """Full-sequence forward → fp32 logits (B, S, V) and aux loss."""
    x, aux_total = forward_hidden(cfg, params, batch, mesh=mesh,
                                  dp_axes=dp_axes)
    lg = L.logits(x, lm_head_table(cfg, params))
    return lg, aux_total


def _dec_block(p, h, positions, enc_out, cfg, *, collect_kv=False):
    a, kv = ATT.attention_layer(
        p["self_attn"], L.rms_norm(h, p["ln1"], cfg.norm_eps), positions,
        cfg, causal=True)
    h = h + a
    h = h + ATT.cross_attention_layer(
        p["cross_attn"], L.rms_norm(h, p["ln2"], cfg.norm_eps),
        ATT.encoder_kv(p["cross_attn"], enc_out, cfg), cfg)
    h = h + L.swiglu(L.rms_norm(h, p["ln3"], cfg.norm_eps),
                     p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"])
    return (h, kv) if collect_kv else (h, None)


def _hybrid_split(cfg: ModelConfig):
    k = cfg.attn_every
    n_groups = cfg.num_layers // k
    rem = cfg.num_layers - n_groups * k
    return n_groups, k, rem


def _hybrid_forward(cfg, params, x, positions):
    """Zamba2: groups of k Mamba2 layers, shared attn block after each."""
    n_groups, k, rem = _hybrid_split(cfg)
    stacked = params["layers"]
    grouped = jax.tree_util.tree_map(
        lambda t: t[: n_groups * k].reshape((n_groups, k) + t.shape[1:]),
        stacked)
    remainder = jax.tree_util.tree_map(lambda t: t[n_groups * k:], stacked)
    shared = params["shared"]

    def group_body(h, gp):
        def inner(hh, p):
            return _ssm_block(p, hh, cfg), None
        h, _ = _rscan(inner, h, gp)
        h, _ = _dense_block(shared, h, positions, cfg)   # shared weights
        return h, None

    x, _ = _rscan(group_body, x, grouped)
    if rem:
        def inner(hh, p):
            return _ssm_block(p, hh, cfg), None
        x, _ = _rscan(inner, x, remainder)
    return x


# ===================================================================== #
# Loss                                                                  #
# ===================================================================== #
def _c_spec(x, mesh, spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def fused_logits_xent(x, table, labels, mesh, dp_axes, *,
                      z_loss: float = 0.0):
    """Fused final-projection + cross-entropy under shard_map.

    Layout: x (dp, model@S, D), table (·, model@D), labels (dp, model@S).
    Inside the shard every step is local: the table is all-gathered in bf16
    once (the only collective besides the final psum), the (B_l, S_l, V)
    fp32 logits exist only as a per-device transient, and the label gather
    is a LOCAL take_along_axis.  This removes the three pathologies GSPMD
    produced for the global formulation (fp32 table all-gather, replicated
    (V, D) gradient, one-hot broadcast chains) — measured in EXPERIMENTS.md
    §Perf.  ``jax.checkpoint`` recomputes the gathered table in backward
    instead of holding 1.7 GB live across the whole backward pass.
    """
    if mesh is None:
        lg = L.logits(x, table)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if z_loss > 0:
            nll = nll + z_loss * lse ** 2
        return jnp.mean(nll)

    dp_axes = tuple(a for a in dp_axes if a != "model") or ("data",)
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    all_axes = tuple(dp_axes) + ("model",)
    n_tokens = labels.shape[0] * labels.shape[1]
    V = table.shape[0]
    # Vocab chunks: bound every transient to ≲0.5 GB/device.  The online
    # logsumexp over chunks is the vocabulary analogue of flash attention;
    # the chunk body is checkpointed so backward recomputes each chunk's
    # logits instead of keeping them, and the table cotangent accumulates
    # chunk-by-chunk at (Vc, D/16) shard size — never a full (V, D) fp32.
    n_chunks = max(1, min(8, V // 16_384))
    while V % n_chunks:
        n_chunks -= 1
    Vc = V // n_chunks

    def f(x_loc, tab_loc, lab_loc):
        Bl, Sl, D = x_loc.shape
        tab_chunks = tab_loc.reshape(n_chunks, Vc, tab_loc.shape[-1])

        @jax.checkpoint
        def body(carry, inp):
            m, l, gold, ci = carry
            tab_c = inp                                   # (Vc, D/16) f32
            tab_g = jax.lax.all_gather(tab_c.astype(x_loc.dtype), "model",
                                       axis=1, tiled=True)  # (Vc, D) bf16
            lg = jax.lax.dot_general(
                x_loc, tab_g, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (B_l, S_l, Vc)
            m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
            l = l * jnp.exp(m - m_new) + jnp.sum(
                jnp.exp(lg - m_new[..., None]), axis=-1)
            lab_rel = lab_loc - ci * Vc
            in_chunk = (lab_rel >= 0) & (lab_rel < Vc)
            safe = jnp.clip(lab_rel, 0, Vc - 1)
            g = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
            gold = gold + jnp.where(in_chunk, g, 0.0)
            return (m_new, l, gold, ci + 1), None

        m0 = jnp.full((Bl, Sl), -1e30, jnp.float32)
        l0 = jnp.zeros((Bl, Sl), jnp.float32)
        g0 = jnp.zeros((Bl, Sl), jnp.float32)
        (m, l, gold, _), _ = jax.lax.scan(
            body, (m0, l0, g0, jnp.asarray(0, jnp.int32)), tab_chunks)
        lse = m + jnp.log(l)
        nll = lse - gold
        if z_loss > 0:
            nll = nll + z_loss * lse ** 2
        return jax.lax.psum(jnp.sum(nll), all_axes)

    total = shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, "model", None), P(None, "model"), P(dp, "model")),
        out_specs=P(),
        check_vma=False,
    )(x, table, labels)
    return total / n_tokens


def loss_fn(cfg: ModelConfig, params, batch, mesh=None, dp_axes=("data",)):
    x, aux = forward_hidden(cfg, params, batch, mesh=mesh, dp_axes=dp_axes)
    loss = fused_logits_xent(x, lm_head_table(cfg, params),
                             batch["labels"], mesh, dp_axes)
    return loss + AUX_WEIGHT * aux, {"xent": loss, "aux": aux}


# ===================================================================== #
# Prefill: forward + KV/state cache construction                        #
# ===================================================================== #
def prefill(cfg: ModelConfig, params, batch, mesh=None, dp_axes=("data",)):
    """Returns (last-position fp32 logits (B, V), cache dict)."""
    dt = _dtype(cfg)
    fam = cfg.family
    tokens = batch["tokens"]
    B, S = tokens.shape
    _install_con(mesh, dp_axes)
    x = _c(_embed(tokens, params["embed"], dt, mesh, dp_axes), mesh, dp_axes)
    if cfg.use_mrope:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    cache = {}
    if fam in ("dense", "vlm", "moe"):
        def body(h, p):
            if fam == "moe":
                h, _, kv = _moe_block(p, h, positions, cfg, mesh, dp_axes,
                                      collect_kv=True)
            else:
                h, kv = _dense_block(p, h, positions, cfg, collect_kv=True)
            return h, kv
        x, (K, V) = _rscan(body, x, params["layers"])
        cache = {"k": K, "v": V}            # (L, B, Hkv, S, dh)

    elif fam == "ssm":
        def body(h, p):
            hn = L.rms_norm(h, p["ln1"], cfg.norm_eps)
            out, entry = _ssm_prefill_layer(p["ssm"], hn, cfg)
            return h + out, entry
        x, entries = _rscan(body, x, params["layers"])
        cache = entries                      # {"conv": (L,...), "ssm": ...}

    elif fam == "hybrid":
        x, cache = _hybrid_prefill(cfg, params, x, positions)

    elif fam == "encdec":
        enc = batch["enc_embeds"].astype(dt)
        epos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None, :],
                                (B, enc.shape[1]))

        def ebody(h, p):
            h, _ = _dense_block(p, h, epos, cfg, causal=False)
            return h, None
        enc_out, _ = _rscan(ebody, enc,
                                  params["enc_layers"])

        def dbody(h, p):
            h, kv = _dec_block(p, h, positions, enc_out, cfg,
                               collect_kv=True)
            ck, cv = ATT.encoder_kv(p["cross_attn"], enc_out, cfg)
            return h, (kv[0], kv[1], ck, cv)
        x, (K, V, CK, CV) = _rscan(dbody, x,
                                         params["dec_layers"])
        cache = {"self_k": K, "self_v": V, "cross_k": CK, "cross_v": CV}
    else:
        raise ValueError(fam)

    x = L.rms_norm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    lg = L.logits(x, lm_head_table(cfg, params))[:, 0, :]
    return lg, cache


def _ssm_prefill_layer(p, hn, cfg):
    """SSD layer that also returns its decode cache entry."""
    dtp = hn.dtype
    B_, S, _ = hn.shape
    din, N = cfg.d_inner, cfg.ssm_state
    proj = hn @ p["w_in"].astype(dtp)
    z, xBC, dt_raw = SSM._split_proj(cfg, proj)
    conv_tail = xBC[:, S - (cfg.ssm_conv_width - 1):, :]
    xBC = SSM._causal_conv(xBC, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = (xBC[..., :din], xBC[..., din: din + N],
                  xBC[..., din + N:])
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32)
                          + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(B_, S, cfg.ssm_nheads, cfg.ssm_headdim)
    from repro.kernels import ops as kops
    y, h_final = kops.ssd_scan(xh, dtv, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh.astype(y.dtype)
    y = y.reshape(B_, S, din)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = y @ p["w_out"].astype(dtp)
    return out, {"conv": conv_tail, "ssm": h_final}


def _hybrid_prefill(cfg, params, x, positions):
    n_groups, k, rem = _hybrid_split(cfg)
    stacked = params["layers"]
    grouped = jax.tree_util.tree_map(
        lambda t: t[: n_groups * k].reshape((n_groups, k) + t.shape[1:]),
        stacked)
    remainder = jax.tree_util.tree_map(lambda t: t[n_groups * k:], stacked)
    shared = params["shared"]

    def group_body(h, gp):
        def inner(hh, p):
            hn = L.rms_norm(hh, p["ln1"], cfg.norm_eps)
            out, entry = _ssm_prefill_layer(p["ssm"], hn, cfg)
            return hh + out, entry
        h, entries = _rscan(inner, h, gp)
        h, kv = _dense_block(shared, h, positions, cfg, collect_kv=True)
        return h, (entries, kv)

    x, (m_entries, (K, V)) = _rscan(group_body, x,
                                          grouped)
    # m_entries leaves: (n_groups, k, B, ...) → flatten to (n_groups·k, ...)
    m_entries = jax.tree_util.tree_map(
        lambda t: t.reshape((-1,) + t.shape[2:]), m_entries)
    if rem:
        def inner(hh, p):
            hn = L.rms_norm(hh, p["ln1"], cfg.norm_eps)
            out, entry = _ssm_prefill_layer(p["ssm"], hn, cfg)
            return hh + out, entry
        x, rem_entries = _rscan(inner, x, remainder)
        m_entries = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            m_entries, rem_entries)
    cache = {"conv": m_entries["conv"], "ssm": m_entries["ssm"],
             "attn_k": K, "attn_v": V}     # attn caches: (n_groups, ...)
    return x, cache


# ===================================================================== #
# Decode: one token against the cache                                   #
# ===================================================================== #
def decode_step(cfg: ModelConfig, params, token, cache, pos, mesh=None,
                dp_axes=("data",)):
    """token: (B, 1) int32; ``pos``: scalar count of valid cache entries.

    Returns (fp32 logits (B, V), updated cache).
    """
    dt = _dtype(cfg)
    fam = cfg.family
    _install_con(mesh, dp_axes)
    x = _embed(token, params["embed"], dt, mesh, dp_axes)
    new_cache = dict(cache)

    if fam in ("dense", "vlm", "moe"):
        # The stacked KV cache is CARRIED and updated in place (dynamic-
        # update-slice at layer l): a scan that passes cache layers as xs
        # and re-stacks them as ys holds input+output copies live inside
        # the loop (2× the cache, +6.4 GB/device measured on deepseek).
        def body(carry, p):
            h, K, V, l = carry
            k_l = jax.lax.dynamic_index_in_dim(K, l, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(V, l, 0, keepdims=False)
            a, k_n, v_n = ATT.attention_decode(
                p["attn"], L.rms_norm(h, p["ln1"], cfg.norm_eps),
                k_l, v_l, pos, cfg)
            K = jax.lax.dynamic_update_index_in_dim(K, k_n, l, 0)
            V = jax.lax.dynamic_update_index_in_dim(V, v_n, l, 0)
            h = h + a
            if fam == "moe":
                y, _ = MOE.moe_layer(
                    p["moe"], L.rms_norm(h, p["ln2"], cfg.norm_eps), cfg,
                    mesh=mesh, dp_axes=dp_axes)
                h = h + y
            else:
                h = h + L.swiglu(L.rms_norm(h, p["ln2"], cfg.norm_eps),
                                 p["mlp"]["w1"], p["mlp"]["w3"],
                                 p["mlp"]["w2"])
            return (h, K, V, l + 1), None
        (x, K, V, _), _ = _pscan(
            body, (x, cache["k"], cache["v"], jnp.asarray(0, jnp.int32)),
            params["layers"])
        new_cache = {"k": K, "v": V}

    elif fam == "ssm":
        def body(h, inp):
            p, entry = inp
            out, new_entry = SSM.ssm_decode(
                p["ssm"], L.rms_norm(h, p["ln1"], cfg.norm_eps), entry, cfg)
            return h + out, new_entry
        x, new_cache = _pscan(body, x, (params["layers"],
                      {"conv": cache["conv"], "ssm": cache["ssm"]}))

    elif fam == "hybrid":
        x, new_cache = _hybrid_decode(cfg, params, x, cache, pos)

    elif fam == "encdec":
        def body(carry, inp):
            h, K, V, l = carry
            p, ck_l, cv_l = inp              # cross-cache is read-only: xs
            k_l = jax.lax.dynamic_index_in_dim(K, l, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(V, l, 0, keepdims=False)
            a, k_n, v_n = ATT.attention_decode(
                p["self_attn"], L.rms_norm(h, p["ln1"], cfg.norm_eps),
                k_l, v_l, pos, cfg)
            K = jax.lax.dynamic_update_index_in_dim(K, k_n, l, 0)
            V = jax.lax.dynamic_update_index_in_dim(V, v_n, l, 0)
            h = h + a
            h = h + ATT.cross_attention_layer(
                p["cross_attn"], L.rms_norm(h, p["ln2"], cfg.norm_eps),
                (ck_l, cv_l), cfg)
            h = h + L.swiglu(L.rms_norm(h, p["ln3"], cfg.norm_eps),
                             p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"])
            return (h, K, V, l + 1), None
        (x, K, V, _), _ = _pscan(
            body,
            (x, cache["self_k"], cache["self_v"], jnp.asarray(0, jnp.int32)),
            (params["dec_layers"], cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, self_k=K, self_v=V)
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    lg = L.logits(x, lm_head_table(cfg, params))[:, 0, :]
    return lg, new_cache


def _hybrid_decode(cfg, params, x, cache, pos):
    n_groups, k, rem = _hybrid_split(cfg)
    stacked = params["layers"]
    shared = params["shared"]
    mcache = {"conv": cache["conv"], "ssm": cache["ssm"]}
    grouped_p = jax.tree_util.tree_map(
        lambda t: t[: n_groups * k].reshape((n_groups, k) + t.shape[1:]),
        stacked)
    grouped_c = jax.tree_util.tree_map(
        lambda t: t[: n_groups * k].reshape((n_groups, k) + t.shape[1:]),
        mcache)
    rem_p = jax.tree_util.tree_map(lambda t: t[n_groups * k:], stacked)
    rem_c = jax.tree_util.tree_map(lambda t: t[n_groups * k:], mcache)

    def group_body(h, inp):
        gp, gc, k_l, v_l = inp

        def inner(hh, inner_inp):
            p, entry = inner_inp
            out, new_entry = SSM.ssm_decode(
                p["ssm"], L.rms_norm(hh, p["ln1"], cfg.norm_eps), entry, cfg)
            return hh + out, new_entry
        h, new_gc = _pscan(inner, h, (gp, gc))
        a, k_n, v_n = ATT.attention_decode(
            shared["attn"], L.rms_norm(h, shared["ln1"], cfg.norm_eps),
            k_l, v_l, pos, cfg)
        h = h + a
        h = h + L.swiglu(L.rms_norm(h, shared["ln2"], cfg.norm_eps),
                         shared["mlp"]["w1"], shared["mlp"]["w3"],
                         shared["mlp"]["w2"])
        return h, (new_gc, k_n, v_n)

    x, (new_gc, K, V) = _pscan(group_body, x, (grouped_p, grouped_c, cache["attn_k"],
                        cache["attn_v"]))
    new_m = jax.tree_util.tree_map(
        lambda t: t.reshape((-1,) + t.shape[2:]), new_gc)
    if rem:
        def inner(hh, inner_inp):
            p, entry = inner_inp
            out, new_entry = SSM.ssm_decode(
                p["ssm"], L.rms_norm(hh, p["ln1"], cfg.norm_eps), entry, cfg)
            return hh + out, new_entry
        x, new_rem = _pscan(inner, x, (rem_p, rem_c))
        new_m = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), new_m, new_rem)
    return x, {"conv": new_m["conv"], "ssm": new_m["ssm"],
               "attn_k": K, "attn_v": V}
