"""Per-kernel correctness sweeps: Pallas (interpret mode) vs jnp oracle,
across shapes and dtypes, plus hypothesis fuzzing of the FLEXA prox."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

# hypothesis is an optional test extra (`pip install -e .[test]`); without it
# the fuzz test falls back to a fixed set of representative examples so the
# rest of this module still runs (the seed suite died at collection here).
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test extra
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref  # noqa: E402

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------ #
# flexa_prox                                                         #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("shape", [(8,), (130,), (33, 7), (4, 5, 6),
                                   (1024,), (257, 3),
                                   (700, 900)])    # ragged multi-tile grid
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("c", [0.0, 0.3])
def test_flexa_best_response_sweep(shape, dtype, c):
    x = jnp.asarray(RNG.standard_normal(shape), dtype)
    g = jnp.asarray(RNG.standard_normal(shape), dtype)
    z_r, e_r = ref.flexa_best_response_ref(x, g, 2.0, c)
    z_k, e_k = ops.flexa_best_response(x, g, 2.0, c, force="interpret")
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r),
                               atol=2e-5, rtol=2e-5)
    assert abs(float(e_k) - float(e_r)) < 1e-3 * max(1.0, float(e_r))


@pytest.mark.parametrize("shape", [(3, 64), (2, 37, 19), (4, 600)])
@pytest.mark.parametrize("d_kind", ["scalar", "per_instance", "dense"])
@pytest.mark.parametrize("c_kind", ["scalar", "per_instance"])
def test_flexa_batched_best_response_sweep(shape, d_kind, c_kind):
    """Leading-batch-dim kernel == vmapped oracle, incl. per-instance c/d."""
    B = shape[0]
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    d = {"scalar": 2.0,
         "per_instance": jnp.asarray(RNG.uniform(0.5, 3, (B,)), jnp.float32),
         "dense": jnp.asarray(RNG.uniform(0.5, 3, shape), jnp.float32),
         }[d_kind]
    c = 0.3 if c_kind == "scalar" else \
        jnp.asarray(RNG.uniform(0, 1, (B,)), jnp.float32)
    z_r, e_r = ref.flexa_best_response_batched_ref(x, g, d, c)
    z_k, e_k = ops.flexa_best_response_batched(x, g, d, c,
                                               force="interpret")
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r),
                               atol=2e-5, rtol=2e-5)
    assert e_k.shape == (B,)
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r),
                               atol=1e-3, rtol=1e-3)


def test_flexa_batched_apply_per_instance_gamma():
    """Each instance in the bucket applies its own γ·mask damping."""
    B, n = 3, 200
    x = jnp.asarray(RNG.standard_normal((B, n)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((B, n)), jnp.float32)
    gm = jnp.asarray([0.0, 0.5, 1.0], jnp.float32)
    a_r = ref.flexa_apply_batched_ref(x, g, 1.7, 0.2, gm)
    a_k = ops.flexa_apply_batched(x, g, 1.7, 0.2, gm, force="interpret")
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), atol=2e-6)
    # γ=0 instance must be exactly unchanged
    np.testing.assert_array_equal(np.asarray(a_k[0]), np.asarray(x[0]))


@pytest.mark.parametrize("scalar_d", [True, False])
def test_flexa_apply_sweep(scalar_d):
    shape = (37, 19)
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    d = 1.7 if scalar_d else jnp.asarray(
        RNG.uniform(0.5, 3.0, shape), jnp.float32)
    a_r = ref.flexa_apply_ref(x, g, d, 0.2, 0.9, 1.0)
    a_k = ops.flexa_apply(x, g, d, 0.2, jnp.float32(0.9),
                          force="interpret")
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), atol=2e-6)


def _check_prox_fuzz(n, d, c):
    x = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    z_r, e_r = ref.flexa_best_response_ref(x, g, d, c)
    z_k, e_k = ops.flexa_best_response(x, g, d, c, force="interpret")
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), atol=1e-5,
                               rtol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 600), st.floats(0.1, 10), st.floats(0, 2))
    def test_flexa_prox_fuzz(n, d, c):
        _check_prox_fuzz(n, d, c)
else:
    @pytest.mark.parametrize("n,d,c", [
        (1, 0.1, 0.0), (37, 1.3, 0.5), (600, 10.0, 2.0), (128, 0.5, 1.0)])
    def test_flexa_prox_fuzz(n, d, c):
        _check_prox_fuzz(n, d, c)


# ------------------------------------------------------------------ #
# flash attention                                                    #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("B,Hq,Hkv,S,D,bq,bk", [
    (1, 2, 2, 64, 16, 32, 32),      # MHA square
    (2, 4, 2, 64, 16, 16, 64),      # GQA, uneven blocks
    (1, 8, 1, 128, 32, 64, 32),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, S, D, bq, bk, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Hq, S, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), dtype)
    o_r = ref.flash_attention_ref(q, k, v, causal=True)
    o_k = ops.flash_attention(q, k, v, causal=True, force="interpret",
                              block_q=bq, block_k=bk)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32), atol=tol)


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    o_r = ref.flash_attention_ref(q, k, v, causal=False)
    o_k = ops.flash_attention(q, k, v, causal=False, force="interpret",
                              block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)


def test_chunked_attention_matches_ref():
    """The jnp flash path used by the models == oracle (incl. decode
    offset alignment)."""
    from repro.models.attention import chunked_attention
    q = jnp.asarray(RNG.standard_normal((2, 4, 8, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 2, 32, 16)), jnp.float32)
    o_r = ref.flash_attention_ref(q, k, v, causal=True)   # offset = 24
    o_c = chunked_attention(q, k, v, causal=True, block=8)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r), atol=2e-5)


# ------------------------------------------------------------------ #
# SSD scan                                                           #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("Bt,S,H,P,N,chunk", [
    (1, 32, 2, 8, 8, 8),
    (2, 64, 3, 16, 8, 16),
    (1, 48, 1, 8, 16, 16),          # S not a chunk multiple after pad test
])
def test_ssd_scan_sweep(Bt, S, H, P, N, chunk):
    x = jnp.asarray(RNG.standard_normal((Bt, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, (Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((Bt, S, N)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((Bt, S, N)), jnp.float32)
    y_r, h_r = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, force="ref")
    y_k, h_k = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, force="interpret")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=2e-4)


def test_ssd_scan_matches_sequential_recurrence():
    """Chunked == step-by-step recurrence (the semantic ground truth)."""
    Bt, S, H, P, N = 1, 24, 2, 4, 6
    x = jnp.asarray(RNG.standard_normal((Bt, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, (Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((Bt, S, N)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((Bt, S, N)), jnp.float32)
    h = jnp.zeros((Bt, H, N, P))
    ys = []
    for t in range(S):
        y, h = ref.ssd_decode_ref(x[:, t], dt[:, t], A, B[:, t], C[:, t], h)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    y_c, h_c = ops.ssd_scan(x, dt, A, B, C, chunk=8, force="ref")
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_seq),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h), atol=2e-5)


# ------------------------------------------------------------------ #
# Compacted active-set gather/scatter                                #
# ------------------------------------------------------------------ #
def _plan_arrays(n_rows, k_active, seed):
    """Random (src, idx, inv) triple: idx packs k active rows (−1 pad),
    inv is the inverse permutation (−1 for screened rows)."""
    rng = np.random.default_rng(seed)
    act = rng.choice(n_rows, size=k_active, replace=False)
    act.sort()
    cap = max(1, 1 << (max(k_active, 1) - 1).bit_length())
    idx = np.full(cap, -1, np.int32)
    idx[:k_active] = act
    inv = np.full(n_rows, -1, np.int32)
    inv[act] = np.arange(k_active, dtype=np.int32)
    return idx, inv


@pytest.mark.parametrize("n_rows,k,C", [
    (16, 5, 64),
    (16, 5, 200),                   # ragged C (pad-to-128 path)
    (8, 8, 37),                     # everything active, tiny ragged C
    (12, 1, 128),
    (37, 11, 300),                  # rows and capacity off the 8-row tile
    (1000, 700, 600),               # several column tiles, ragged edge
])
def test_gather_scatter_blocks_sweep(n_rows, k, C):
    idx, inv = _plan_arrays(n_rows, k, seed=n_rows + k + C)
    src = jnp.asarray(RNG.standard_normal((n_rows, C)), jnp.float32)
    g_r = ref.gather_rows_ref(src, jnp.asarray(idx))
    g_k = ops.gather_blocks(src, jnp.asarray(idx), force="interpret")
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r), atol=0)
    # pad rows (idx == -1) come back exactly zero
    np.testing.assert_array_equal(np.asarray(g_k)[idx < 0], 0.0)
    # scatter round-trips onto an untouched base
    base = jnp.asarray(RNG.standard_normal((n_rows, C)), jnp.float32)
    s_r = ref.scatter_rows_ref(g_r[: idx.size], jnp.asarray(inv), base)
    s_k = ops.scatter_blocks(g_k[: idx.size], jnp.asarray(inv), base,
                             force="interpret")
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=0)
    np.testing.assert_array_equal(np.asarray(s_k)[inv >= 0],
                                  np.asarray(src)[inv >= 0])
    np.testing.assert_array_equal(np.asarray(s_k)[inv < 0],
                                  np.asarray(base)[inv < 0])


def test_gather_blocks_all_screened():
    """idx all −1 (support vanished): the packed tile is all zeros and a
    scatter writes nothing over the base."""
    n_rows, C = 8, 96
    idx = np.full(4, -1, np.int32)
    inv = np.full(n_rows, -1, np.int32)
    src = jnp.asarray(RNG.standard_normal((n_rows, C)), jnp.float32)
    base = jnp.asarray(RNG.standard_normal((n_rows, C)), jnp.float32)
    g = ops.gather_blocks(src, jnp.asarray(idx), force="interpret")
    np.testing.assert_array_equal(np.asarray(g), 0.0)
    s = ops.scatter_blocks(jnp.zeros((4, C), jnp.float32)[:n_rows],
                           jnp.asarray(inv), base, force="interpret")
    np.testing.assert_array_equal(np.asarray(s), np.asarray(base))


@pytest.mark.parametrize("C", [64, 200])
@pytest.mark.parametrize("scalar_d", [True, False])
def test_compact_best_response_sweep(C, scalar_d):
    """Fused gather+prox == gather-then-dense-prox oracle."""
    n_rows, k = 16, 6
    idx, _ = _plan_arrays(n_rows, k, seed=C)
    x = jnp.asarray(RNG.standard_normal((n_rows, C)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((n_rows, C)), jnp.float32)
    d = 2.0 if scalar_d else \
        jnp.asarray(RNG.uniform(0.5, 3, (n_rows, C)), jnp.float32)
    z_r, e_r = ref.compact_best_response_ref(x, g, d, 0.3, jnp.asarray(idx))
    z_k, e_k = ops.compact_best_response(x, g, d, 0.3, jnp.asarray(idx),
                                         force="interpret")
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r),
                               atol=2e-5, rtol=2e-5)
    assert abs(float(e_k) - float(e_r)) < 1e-3 * max(1.0, float(e_r))
    # pad rows contribute nothing: z there is exactly zero
    np.testing.assert_array_equal(np.asarray(z_k)[idx < 0], 0.0)
