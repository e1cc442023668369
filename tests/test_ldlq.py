"""LDLQ unit tests: decomposition, OPTQ equivalence (Thm 6), optimality
(Thm 1 empirics), the finite-grid counterexample (Sec. 5.2), and the blocked
schedule equivalence used by the production path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_hessian, make_weights

from repro.core.ldlq import (
    ldl_decomposition,
    ldlq,
    ldlq_blocked,
    optq_reference,
    quantize_nearest,
    quantize_stoch,
)
from repro.core.proxy import proxy_loss


def test_ldl_decomposition_reconstructs():
    H = make_hessian(96, seed=0)
    Udot, D = ldl_decomposition(H)
    n = H.shape[0]
    rec = (Udot + jnp.eye(n)) @ jnp.diag(D) @ (Udot + jnp.eye(n)).T
    np.testing.assert_allclose(np.asarray(rec), np.asarray(H), rtol=1e-4, atol=1e-5)
    # strictly upper triangular
    assert float(jnp.max(jnp.abs(jnp.tril(Udot)))) == 0.0
    assert float(jnp.min(D)) > 0.0


def test_trD_less_than_trH():
    """tr(D) < tr(H) for non-diagonal H (the LDLQ-vs-near optimality gap)."""
    H = make_hessian(128, seed=1)
    _, D = ldl_decomposition(H)
    assert float(jnp.sum(D)) < float(jnp.trace(H))


@pytest.mark.parametrize("bits", [2, 3, 4])
def test_optq_equals_ldlq_bit_exact(bits):
    """Theorem 6: OPTQ's iterative algorithm == LDLQ, exactly.

    Mirrors the paper's Supplement C.2 empirical verification with
    W ~ Unif[0,1] (scaled to the grid).  Run in float64: the two
    implementations are algebraically identical but follow different fp op
    orders, so fp32 can flip ties at a rounding boundary and the feedback
    then legitimately amplifies the flip downstream (measured below)."""
    maxq = 2**bits - 1
    with jax.enable_x64(True):
        W = (
            jax.random.uniform(jax.random.PRNGKey(0), (100, 100)) * maxq
        ).astype(jnp.float64)
        H = make_hessian(100, seed=2).astype(jnp.float64)
        Udot, _ = ldl_decomposition(H)
        a = ldlq(W, Udot, maxq)
        b = optq_reference(W, H, maxq)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_optq_equals_ldlq_fp32_tie_noise_bounded():
    """At fp32 the two equivalent paths may flip rare rounding ties; the
    disagreement must stay a small fraction of entries."""
    maxq = 3
    W = jax.random.uniform(jax.random.PRNGKey(0), (100, 100)) * maxq
    H = make_hessian(100, seed=2)
    Udot, _ = ldl_decomposition(H)
    a = ldlq(W, Udot, maxq)
    b = optq_reference(W, H, maxq)
    frac = float(jnp.mean((a != b).astype(jnp.float32)))
    assert frac < 0.02, f"fp32 tie disagreement too large: {frac}"


@pytest.mark.parametrize("block", [16, 32, 100])
def test_blocked_ldlq_matches_sequential(block):
    n = 100 if block == 100 else 128
    W = jax.random.uniform(jax.random.PRNGKey(1), (48, n)) * 3
    H = make_hessian(n, seed=4)
    Udot, _ = ldl_decomposition(H)
    a = ldlq(W, Udot, 3)
    b = ldlq_blocked(W, Udot, 3, block=block)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ldlq_beats_nearest_on_proxy():
    """Theorem 1 consequence: LDLQ <= Near on the proxy loss (integers)."""
    W = make_weights(64, 128, seed=5)
    H = make_hessian(128, seed=5)
    Udot, _ = ldl_decomposition(H)
    # generous grid so clamping never binds (the Thm-1 setting)
    scale = 50.0
    Wg = W * scale + 128
    maxq = 255
    l_ldlq = proxy_loss(ldlq(Wg, Udot, maxq) / scale, Wg / scale, H)
    l_near = proxy_loss(quantize_nearest(Wg, maxq) / scale, Wg / scale, H)
    assert float(l_ldlq) <= float(l_near) * 1.001


def test_stochastic_rounding_unbiased():
    z = jnp.full((20000,), 0.3)
    keys = jax.random.PRNGKey(11)
    q = quantize_stoch(z, 7, keys)
    assert abs(float(jnp.mean(q)) - 0.3) < 0.02
    assert set(np.unique(np.asarray(q))) <= {0.0, 1.0}


def test_finite_grid_counterexample():
    """Sec. 5.2 / Supplement C.3: clamped LDLQ can lose to nearest on a
    crafted (W, H) — the reason Theorem 7's Algorithm 5 exists."""
    n, d, c = 64, 16, 0.01
    H = np.ones((n, n)) + np.eye(n)
    H[n - 1, n - 1] = 1.0
    H[0, 1 : n - 1] += 2 * c
    H[1 : n - 1, 0] += 2 * c
    H[0, n - 1] += c
    H[n - 1, 0] += c
    H[0, 0] += 4 * c + n * c**2
    W = 0.499 * np.ones((d, n)) + 0.002 * (np.arange(n) % 2)
    H = jnp.asarray(H, jnp.float32)
    # W stays near 0.5 on the [0, 15] grid: the construction relies on the
    # grid boundary clamping LDLQ's large accumulated correction (Fig. 4)
    Wg = jnp.asarray(W, jnp.float32)
    Udot, _ = ldl_decomposition(H)
    l_ldlq = proxy_loss(ldlq(Wg, Udot, 15), Wg, H)
    l_near = proxy_loss(quantize_nearest(Wg, 15), Wg, H)
    assert float(l_ldlq) > float(l_near), (
        "counterexample should make clamped LDLQ worse than nearest"
    )


def test_ldlq_worst_case_identity_hessian():
    """With H = I the feedback vanishes: LDLQ == nearest rounding."""
    W = jax.random.uniform(jax.random.PRNGKey(3), (32, 64)) * 7
    H = jnp.eye(64)
    Udot, D = ldl_decomposition(H)
    assert float(jnp.max(jnp.abs(Udot))) == 0.0
    np.testing.assert_array_equal(
        np.asarray(ldlq(W, Udot, 7)), np.asarray(quantize_nearest(W, 7))
    )
