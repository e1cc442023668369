"""Shared fixtures/helpers for the QuIP repro test suite.

The CPU backend is split into 8 virtual devices before any backend starts,
so the tensor-parallel serving tests (test_distributed.py, the TP cases of
test_chaos.py) build real meshes.  Single-device code is unaffected: it
places everything on ``jax.devices()[0]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_num_cpu_devices", 8)


def make_weights(
    m: int,
    n: int,
    seed: int = 0,
    *,
    outliers: float = 0.005,
    outlier_scale: float = 0.5,
    base_scale: float = 0.02,
) -> jax.Array:
    """LLM-like weight matrix: small gaussian bulk + sparse large outliers."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    W = base_scale * jax.random.normal(k1, (m, n))
    if outliers > 0:
        mask = jax.random.bernoulli(k2, outliers, (m, n))
        W = W + mask * outlier_scale * jax.random.normal(k3, (m, n))
    return W


def make_hessian(
    n: int,
    seed: int = 0,
    *,
    rank: int | None = None,
    damp: float = 1e-3,
    outlier_channel: bool = True,
    tokens: int = 2048,
) -> jax.Array:
    """Approximately low-rank SPD proxy Hessian H = E[x x^T] (paper Fig. 1)."""
    rank = rank or max(4, n // 8)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 7))
    A = jax.random.normal(k1, (n, rank))
    X = jax.random.normal(k2, (tokens, rank)) @ A.T
    if outlier_channel:
        X = X.at[:, 0].mul(10.0)  # a dominant activation channel (LLM-like)
    return X.T @ X / tokens + damp * jnp.eye(n)


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(scope="session")
def small_wh():
    """A (W, H) pair shared by cheap tests."""
    return make_weights(64, 128, seed=3), make_hessian(128, seed=3)
