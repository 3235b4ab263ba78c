"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each ``*_ref`` is the semantic definition: simple, obviously-correct jnp.
The Pallas kernels in this package must match these within dtype tolerance
(asserted by the per-kernel sweep tests), and the CPU execution path of the
framework dispatches here (``ops.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ #
# Sparse-design products                                              #
# ------------------------------------------------------------------ #
def blocked_product_ref(values, gidx, sidx, table, n_out):
    """out[sidx[k]] += values[k]·table[gidx[k]] over every stored entry
    k: A·x with (gidx, sidx) = (cols, rows), Aᵀ·r with (rows, cols)."""
    return jax.ops.segment_sum(values * table[gidx], sidx,
                               num_segments=n_out)


# ------------------------------------------------------------------ #
# FLEXA fused prox (the paper's hot spot)                             #
# ------------------------------------------------------------------ #
def flexa_best_response_ref(x, g, d, c):
    """Best response + squared error norm for one block tensor.

    z  = prox_{(c/d)·‖·‖₁}(x − g/d)  = soft-threshold,
    e2 = Σ (z − x)²   (the squared error bound Eᵢ²).

    ``d`` is a positive scalar or a tensor broadcastable to x (diag Q case);
    ``c = 0`` disables the ℓ1 term (plain scaled gradient step).
    Computation in fp32 regardless of input dtype (optimizer precision).
    """
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    w = xf - gf / d
    t = c / d
    z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)
    e2 = jnp.sum((z - xf) ** 2)
    return z, e2


def flexa_apply_ref(x, g, d, c, gamma, mask):
    """Fused damped masked update:  x ← x + γ·mask·(x̂(x) − x).

    Recomputes the best response in-register (cheaper than materializing it:
    the op is memory-bound, see kernels/flexa_prox.py).
    """
    z, _ = flexa_best_response_ref(x, g, d, c)
    xf = x.astype(jnp.float32)
    return (xf + gamma * mask * (z - xf)).astype(x.dtype)


def _per_instance(v, B):
    """() or (B,) → (B,) fp32 (batched-oracle scalar normalization)."""
    v = jnp.asarray(v, jnp.float32)
    return jnp.broadcast_to(v, (B,))


def flexa_best_response_batched_ref(x, g, d, c):
    """Batched oracle: x, g (B, ...); d ()/(B,)/dense; c ()/(B,).

    Returns (z (B, ...) fp32, e2 (B,)) — one error bound per instance.
    """
    B = x.shape[0]
    c = _per_instance(c, B)
    if jnp.ndim(d) <= 1:
        d = _per_instance(d, B)
    return jax.vmap(flexa_best_response_ref)(x, g, d, c)


def flexa_apply_batched_ref(x, g, d, c, gamma_mask):
    """Batched oracle of the fused update; ``gamma_mask`` is ()/(B,)."""
    B = x.shape[0]
    c = _per_instance(c, B)
    gamma_mask = _per_instance(gamma_mask, B)
    if jnp.ndim(d) <= 1:
        d = _per_instance(d, B)
    ones = jnp.asarray(1.0, jnp.float32)
    return jax.vmap(
        lambda xi, gi, di, ci, gmi: flexa_apply_ref(xi, gi, di, ci, gmi,
                                                    ones))(
        x, g, d, c, gamma_mask)


# ------------------------------------------------------------------ #
# Compacted active-set gather/scatter (capacity-bucketed screening)   #
# ------------------------------------------------------------------ #
def gather_rows_ref(src, idx):
    """out[k] = src[idx[k]] for idx[k] ≥ 0, zeros for −1 padding.

    The pack half of the compaction permutation; fp32 output like the
    Pallas kernel (optimizer precision).
    """
    idx = jnp.asarray(idx, jnp.int32)
    taken = jnp.take(src.astype(jnp.float32), jnp.maximum(idx, 0), axis=0)
    return jnp.where((idx >= 0)[:, None], taken, 0.0)


def scatter_rows_ref(vals, inv, base):
    """out[i] = vals[inv[i]] where inv[i] ≥ 0, else base[i].

    The unpack half: a gather of the inverse permutation, so each output
    row is written exactly once (no collision semantics to define).
    """
    inv = jnp.asarray(inv, jnp.int32)
    taken = jnp.take(vals, jnp.maximum(inv, 0), axis=0).astype(base.dtype)
    return jnp.where((inv >= 0)[:, None], taken, base)


def compact_best_response_ref(x, g, d, c, idx):
    """Fused gather + best response over the active rows only.

    Semantics: gather x/g (and dense d) through ``idx``, then the plain
    best response.  Padded rows (idx = −1) gather zeros ⇒ z = 0 and
    contribute nothing to e2; their d is replaced by 1.0 to keep the
    division well-defined.
    """
    xc = gather_rows_ref(x, idx)
    gc = gather_rows_ref(g, idx)
    if jnp.ndim(d) == 0:
        dc = d
    else:
        idx = jnp.asarray(idx, jnp.int32)
        taken = jnp.take(d.astype(jnp.float32), jnp.maximum(idx, 0),
                         axis=0)
        dc = jnp.where((idx >= 0)[:, None], taken, 1.0)
    return flexa_best_response_ref(xc, gc, dc, c)


# ------------------------------------------------------------------ #
# Flash attention (causal, GQA)                                      #
# ------------------------------------------------------------------ #
def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """Naive O(S²) masked softmax attention — the oracle.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) with Hq % Hkv == 0.
    Softmax in fp32; output cast back to q.dtype.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    kf = jnp.repeat(k, rep, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, rep, axis=1).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        # Query positions are aligned to the *end* of the kv sequence
        # (covers both square prefill and prefix-cache decode layouts).
        offset = Skv - Sq
        qpos = jnp.arange(Sq)[:, None] + offset
        kpos = jnp.arange(Skv)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(q.dtype)


# ------------------------------------------------------------------ #
# Mamba2 SSD chunked scan                                            #
# ------------------------------------------------------------------ #
def ssd_scan_ref(x, dt, A, B, C, *, chunk: int = 64, h0=None):
    """State-space dual (SSD) recurrence, chunked — the oracle + CPU path.

    Recurrence per head (state N, head dim P):
        h_t = exp(dt_t·A)·h_{t−1} + dt_t·(B_t ⊗ x_t)
        y_t = C_tᵀ h_t

    Shapes:
        x : (Bt, S, H, P)    dt: (Bt, S, H)    A: (H,) (negative)
        B : (Bt, S, N)       C : (Bt, S, N)    (single B/C group)
    Returns y: (Bt, S, H, P) and final state h: (Bt, H, N, P).

    Chunked evaluation (matmul-friendly — the TPU adaptation of SSD):
      within a chunk of length L, with log-decay cumsum s_t = Σ_{u≤t} dt_u·A:
        intra:  y_t += Σ_{u≤t} (C_tᵀB_u)·exp(s_t−s_u)·dt_u·x_u
        carry:  h    = exp(s_L)·h_prev + Σ_u exp(s_L−s_u)·dt_u·(B_u ⊗ x_u)
        inter:  y_t += exp(s_t)·C_tᵀ h_prev
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    ncnk = S // chunk

    xf = x.astype(jnp.float32).reshape(Bt, ncnk, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bt, ncnk, chunk, H)
    Bf = B.astype(jnp.float32).reshape(Bt, ncnk, chunk, N)
    Cf = C.astype(jnp.float32).reshape(Bt, ncnk, chunk, N)
    Af = A.astype(jnp.float32)

    # log decay per step: (Bt, ncnk, L, H)
    la = dtf * Af[None, None, None, :]
    s = jnp.cumsum(la, axis=2)                      # inclusive cumsum
    s_last = s[:, :, -1:, :]                        # (Bt, ncnk, 1, H)

    # Intra-chunk ("attention-like") term.
    G = jnp.einsum("bctn,bcun->bctu", Cf, Bf)       # (Bt,ncnk,L,L)
    # decay mask M_{tu} = exp(s_t − s_u) for u ≤ t else 0  (per head)
    st = s[:, :, :, None, :]                        # (Bt,ncnk,L,1,H)
    su = s[:, :, None, :, :]                        # (Bt,ncnk,1,L,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    M = jnp.exp(st - su) * tri[None, None, :, :, None]
    W = G[:, :, :, :, None] * M * dtf[:, :, None, :, :]   # (Bt,ncnk,L,L,H)
    y_intra = jnp.einsum("bctuh,bcuhp->bcthp", W, xf)

    # Chunk state contribution:  (Bt,ncnk,H,N,P)
    decay_u = jnp.exp(s_last - s)                   # exp(s_L − s_u)
    Hc = jnp.einsum("bcuh,bcun,bcuhp->bchnp", decay_u * dtf, Bf, xf)

    # Inter-chunk scan over the carry h.
    chunk_decay = jnp.exp(s_last[:, :, 0, :])       # (Bt,ncnk,H)

    def scan_body(h, inputs):
        hc, cd = inputs                              # (Bt,H,N,P), (Bt,H)
        h_new = cd[:, :, None, None] * h + hc
        return h_new, h                              # emit state *before* chunk

    if h0 is None:
        h0 = jnp.zeros((Bt, H, N, P), jnp.float32)
    hc_seq = jnp.moveaxis(Hc, 1, 0)                 # (ncnk, Bt,H,N,P)
    cd_seq = jnp.moveaxis(chunk_decay, 1, 0)        # (ncnk, Bt,H)
    h_final, h_prevs = jax.lax.scan(scan_body, h0, (hc_seq, cd_seq))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)           # (Bt,ncnk,H,N,P)

    y_inter = jnp.einsum("bctn,bchnp->bcthp", Cf, h_prevs)
    y_inter = y_inter * jnp.exp(s)[..., None]       # decay from chunk start
    y = (y_intra + y_inter).reshape(Bt, S, H, P)
    return y.astype(x.dtype), h_final


def ssd_decode_ref(x_t, dt_t, A, B_t, C_t, h):
    """Single-token SSD update (serving path).

    x_t: (Bt, H, P); dt_t: (Bt, H); B_t, C_t: (Bt, N); h: (Bt, H, N, P).
    Returns y_t: (Bt, H, P), h_new.
    """
    a = jnp.exp(dt_t.astype(jnp.float32) * A[None, :])          # (Bt,H)
    upd = jnp.einsum("bn,bhp->bhnp", B_t.astype(jnp.float32),
                     x_t.astype(jnp.float32) * dt_t[..., None])
    h_new = a[:, :, None, None] * h + upd
    y = jnp.einsum("bn,bhnp->bhp", C_t.astype(jnp.float32), h_new)
    return y.astype(x_t.dtype), h_new
