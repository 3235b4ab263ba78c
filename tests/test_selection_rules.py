"""Property tests for the Step-S.3 selection rules (repro.core.selection).

The Theorem-1 convergence condition is that Sᵏ contains at least one block
with ``Eᵢ ≥ ρ·maxⱼ Eⱼ``.  The deterministic greedy-family rules (greedy,
southwell, topk, full) must satisfy it for every E; the arXiv:1407.4504
randomized rules (random, hybrid) are **exempt** — their convergence is
almost-sure (hybrid satisfies the condition relative to its sketch, which
is asserted instead) — and the essentially-cyclic rule is exempt via its
own guarantee (every block selected once per cycle, asserted too).

Properties run under hypothesis when the optional test extra is installed;
otherwise over a fixed grid of representative E vectors (same pattern as
``test_prox_properties``), so the suite is meaningful on a bare container.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test extra
    HAVE_HYPOTHESIS = False

from repro.core import selection
from repro.config.base import SolverConfig

# Deterministic fallback E vectors: ties, near-ties, spikes, constants.
E_CASES = [
    [1.0, 1.0, 1.0, 1.0],                       # all tied
    [0.0, 0.0, 5.0, 0.0],                       # single spike
    [3.0, 3.0, 3.0, 0.1, 0.2],                  # tied max group
    list(np.linspace(0.01, 1.0, 32)),           # smooth ramp
    list(np.random.default_rng(0).uniform(0, 1, 64)),
    list(np.random.default_rng(1).exponential(1.0, 48)),
    [1e-6, 2e-6, 1.5e-6],                       # tiny scale
]
RHOS = (0.1, 0.5, 1.0)
SEEDS = (0, 1, 2)


def _es():
    if HAVE_HYPOTHESIS:
        strategies = given(
            st.lists(st.floats(0, 100, allow_nan=False), min_size=2,
                     max_size=64),
            st.sampled_from(RHOS), st.sampled_from(SEEDS))
        return lambda f: settings(max_examples=40, deadline=None)(
            strategies(f))
    return pytest.mark.parametrize(
        "vals,rho,seed",
        [(e, r, s) for e in E_CASES for r in RHOS for s in SEEDS[:1]])


def _theorem1_holds(E, mask, rho):
    """Sᵏ contains a block with Eᵢ ≥ ρ·max Eⱼ."""
    E, mask = np.asarray(E), np.asarray(mask)
    sel = mask > 0
    return sel.any() and (E[sel] >= rho * E.max() - 1e-7 * E.max()).any()


def _check_binary(mask, n):
    m = np.asarray(mask)
    assert m.shape == (n,)
    assert np.isin(m, (0.0, 1.0)).all()


@_es()
def test_deterministic_rules_satisfy_theorem1(vals, rho, seed):
    """greedy/southwell/topk/full all contain a ρ-max block for any E."""
    del seed
    E = jnp.asarray(vals, jnp.float32)
    n = E.shape[0]
    for mask in (selection.greedy_mask(E, rho),
                 selection.southwell_mask(E),
                 selection.topk_mask(E, max(1, n // 2)),
                 selection.full_mask(E)):
        _check_binary(mask, n)
        assert _theorem1_holds(E, mask, rho)
    # greedy additionally selects *exactly* the ρ-max set
    g = np.asarray(selection.greedy_mask(E, rho)) > 0
    assert (np.asarray(E)[g] >= rho * float(E.max()) - 1e-6).all()


@_es()
def test_topk_exact_count_under_ties(vals, rho, seed):
    """topk returns exactly k ones even when E has ties at the threshold."""
    del rho, seed
    E = jnp.asarray(vals, jnp.float32)
    n = E.shape[0]
    for k in (1, max(1, n // 3), n, n + 5):
        mask = selection.topk_mask(E, k)
        _check_binary(mask, n)
        assert int(np.asarray(mask).sum()) == min(k, n)
    # hard tie case: every entry equal
    tied = jnp.full((n,), 1.0, jnp.float32)
    for k in (1, max(1, n - 1)):
        assert int(np.asarray(selection.topk_mask(tied, k)).sum()) == k


@_es()
def test_random_mask_is_binary_and_nonempty(vals, rho, seed):
    """The random rule (Theorem-1 exempt: a.s. convergence per
    arXiv:1407.4504) still always returns a usable nonempty {0,1} mask."""
    del rho
    E = jnp.asarray(vals, jnp.float32)
    key = jax.random.PRNGKey(seed)
    for p in (0.01, 0.25, 0.9):
        mask = selection.random_mask(E, p, key)
        _check_binary(mask, E.shape[0])
        assert np.asarray(mask).sum() >= 1          # empty-draw fallback


@_es()
def test_hybrid_contains_sketch_argmax(vals, rho, seed):
    """hybrid ⊆ its sketch and satisfies the greedy condition *relative to
    the sketch* (contains the sketch argmax) — the rule's Theorem-1
    surrogate; globally it is random-rule exempt."""
    E = jnp.asarray(vals, jnp.float32)
    key = jax.random.PRNGKey(seed)
    mask = np.asarray(selection.hybrid_mask(E, rho, 0.5, key))
    # same key ⇒ the very sketch hybrid_mask drew internally
    sketch = np.asarray(selection.random_mask(E, 0.5, key))
    _check_binary(mask, E.shape[0])
    assert (mask <= sketch).all()                   # subset of the sketch
    En = np.asarray(E) * sketch
    if En.max() > 0:
        assert mask[int(En.argmax())] == 1          # sketch argmax kept
        assert (En[mask > 0] >= rho * En.max() - 1e-6 * En.max()).all()


@_es()
def test_cyclic_rule_covers_every_block_each_cycle(vals, rho, seed):
    """cyclic (Theorem-1 exempt: essentially-cyclic convergence): chunks
    are disjoint, balanced to within one block, and their union over one
    cycle is all of 𝒩."""
    del rho
    n = len(vals)
    chunks = min(4, n)
    key = jax.random.PRNGKey(seed)
    masks = [np.asarray(selection.cyclic_shuffle_mask(n, k, chunks, key))
             for k in range(chunks)]
    for m in masks:
        _check_binary(m, n)
    total = np.stack(masks).sum(axis=0)
    assert (total == 1).all()                       # disjoint AND covering
    sizes = [m.sum() for m in masks]
    assert max(sizes) - min(sizes) <= 1             # balanced round-robin
    # iteration k and k + chunks select the same chunk (a true cycle)
    np.testing.assert_array_equal(
        masks[0], np.asarray(selection.cyclic_shuffle_mask(
            n, chunks, chunks, key)))


def test_cyclic_clamps_when_chunks_exceed_blocks():
    """n_chunks > n_blocks must never produce an empty Sᵏ (which would
    burn iterations — x unchanged while γ decays): the cycle length is
    clamped to the block count."""
    key = jax.random.PRNGKey(0)
    n = 3
    for k in range(8):
        m = np.asarray(selection.cyclic_shuffle_mask(n, k, 10, key))
        assert m.sum() == 1                     # clamped to n chunks of 1
    union = sum(np.asarray(selection.cyclic_shuffle_mask(n, k, 10, key))
                for k in range(n))
    assert (union == 1).all()


def test_masks_shape_stable_under_vmap():
    """Every rule vmaps over a batch of E (and keys) to a (B, n) {0,1}
    mask — the property the batched multi-instance engine relies on."""
    B, n = 5, 33
    E = jnp.asarray(np.random.default_rng(3).uniform(0, 1, (B, n)),
                    jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B))
    outs = {
        "greedy": jax.vmap(lambda e: selection.greedy_mask(e, 0.5))(E),
        "southwell": jax.vmap(selection.southwell_mask)(E),
        "topk": jax.vmap(lambda e: selection.topk_mask(e, 7))(E),
        "full": jax.vmap(selection.full_mask)(E),
        "random": jax.vmap(
            lambda e, k: selection.random_mask(e, 0.3, k))(E, keys),
        "hybrid": jax.vmap(
            lambda e, k: selection.hybrid_mask(e, 0.5, 0.3, k))(E, keys),
        "cyclic": jax.vmap(
            lambda k: selection.cyclic_shuffle_mask(
                n, k, 4, jax.random.PRNGKey(0)))(jnp.arange(B)),
    }
    for name, m in outs.items():
        m = np.asarray(m)
        assert m.shape == (B, n), name
        assert np.isin(m, (0.0, 1.0)).all(), name
        assert (m.sum(axis=-1) >= 1).all(), name
    # per-instance keys ⇒ not all random rows identical
    assert not (np.asarray(outs["random"]) ==
                np.asarray(outs["random"])[0]).all()


def test_random_mask_hits_requested_density():
    """E[|Sᵏ|]/N ≈ p (sanity on the sketch probability knob)."""
    E = jnp.ones((200,), jnp.float32)
    fracs = [float(np.asarray(
        selection.random_mask(E, 0.25, jax.random.PRNGKey(s))).mean())
        for s in range(30)]
    assert abs(np.mean(fracs) - 0.25) < 0.05


def test_make_mask_dispatch_and_unknown_rule():
    E = jnp.asarray([0.1, 0.9, 0.5], jnp.float32)
    key = jax.random.PRNGKey(0)
    for rule in ("greedy", "full", "southwell", "topk", "random",
                 "hybrid", "cyclic"):
        cfg = SolverConfig(selection=rule, sel_k=2)
        m = selection.make_mask(E, cfg, key, 0)
        _check_binary(m, 3)
    # back-compat: jacobi flag overrides to the full rule
    m = selection.make_mask(E, SolverConfig(selection="greedy", jacobi=True),
                            key, 0)
    assert np.asarray(m).sum() == 3
    with pytest.raises(ValueError, match="unknown selection rule"):
        selection.make_mask(E, SolverConfig(selection="best"), key, 0)
