"""Incoherence processing (QuIP Sec. 4): Algorithms 1 and 2.

Pre-processing conjugates (W, H) by seeded random orthogonal matrices built
as Kronecker products of two small factors (Lemma 5), with a random
permutation folded in (Table 5 ablation), after an optional diagonal rescale
(Sec. B.1).  Post-processing reverts everything.  The quantization range is
spectrum-based: ``s = rho * ||W||_F / sqrt(mn)`` (Sec. 4.2), not max-abs.

A "transform" here is a pair of structured orthogonal operators (one for the
m side, one for the n side) that are never materialized as dense matrices:
multiplication is O(n(p+q)) for the Kronecker family and O(n log n) for the
randomized-Hadamard family (beyond-paper option, cf. DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Literal, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "kron_factors",
    "random_orthogonal",
    "OrthogonalTransform",
    "make_transform",
    "apply_transform",
    "diag_rescale",
    "quant_range",
    "to_grid",
    "from_grid",
    "incoherence_preprocess",
    "incoherence_postprocess",
    "mu_weight",
    "mu_hessian",
    "PreprocessState",
]

TransformKind = Literal["kronecker", "hadamard", "none"]


def kron_factors(n: int) -> tuple[int, int]:
    """Factor n = p*q with p <= q and p the largest divisor <= sqrt(n)."""
    p = 1
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            p = d
    return p, n // p


def random_orthogonal(key: jax.Array, n: int, dtype=jnp.float32) -> jax.Array:
    """Haar-distributed random orthogonal matrix (QR with sign fix)."""
    g = jax.random.normal(key, (n, n), dtype=jnp.float32)
    q, r = jnp.linalg.qr(g)
    q = q * jnp.sign(jnp.diagonal(r))[None, :]
    return q.astype(dtype)


def _pow2_split(n: int) -> tuple[int, int]:
    """n = odd * 2^k; returns (odd, 2^k)."""
    k = 0
    while n % 2 == 0:
        n //= 2
        k += 1
    return n, 1 << k


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OrthogonalTransform:
    """A seeded structured orthogonal operator on R^n.

    kind = "kronecker": y = (A ⊗ B) P x  (A: p×p, B: q×q Haar factors,
        P a random permutation — the Table-5 heuristic).
    kind = "hadamard":  y = (Q_odd ⊗ H_{2^k} S) P x with S random signs and
        H the normalized Walsh–Hadamard matrix (beyond-paper; QuIP#-style).
    kind = "none":      identity.

    Only factors/signs/permutation are stored — O(p² + q² + n), regenerable
    from ``seed`` alone, which is what makes shipping quantized checkpoints
    nearly free (Sec. 4.1).

    A pytree: the factor arrays are leaves and ``kind``/``n``/``seed`` are
    static, so a jitted program takes transforms as arguments.
    """

    kind: TransformKind = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    seed: int = dataclasses.field(metadata=dict(static=True))
    A: Optional[jax.Array]  # (p, p) or None
    B: Optional[jax.Array]  # (q, q) or None
    signs: Optional[jax.Array]  # (q,) ±1 for hadamard
    perm: Optional[jax.Array]  # (n,) int32
    inv_perm: Optional[jax.Array]

    @property
    def p(self) -> int:
        return 1 if self.A is None else self.A.shape[0]

    @property
    def q(self) -> int:
        return self.n // self.p


def make_transform(
    kind: TransformKind,
    n: int,
    seed: int,
    *,
    permute: bool = True,
    dtype=jnp.float32,
) -> OrthogonalTransform:
    if kind == "none":
        return OrthogonalTransform(kind, n, seed, None, None, None, None, None)
    key = jax.random.PRNGKey(seed)
    k_a, k_b, k_p, k_s = jax.random.split(key, 4)
    perm = jax.random.permutation(k_p, n) if permute else None
    inv_perm = jnp.argsort(perm) if permute else None
    if kind == "kronecker":
        p, q = kron_factors(n)
        A = random_orthogonal(k_a, p, dtype) if p > 1 else None
        B = random_orthogonal(k_b, q, dtype)
        return OrthogonalTransform(kind, n, seed, A, B, None, perm, inv_perm)
    if kind == "hadamard":
        odd, pow2 = _pow2_split(n)
        if pow2 == 1:
            raise ValueError(f"hadamard transform needs an even dim, got {n}")
        A = random_orthogonal(k_a, odd, dtype) if odd > 1 else None
        signs = jax.random.rademacher(k_s, (pow2,), dtype=dtype)
        return OrthogonalTransform(kind, n, seed, A, None, signs, perm, inv_perm)
    raise ValueError(f"unknown transform kind: {kind}")


def _fwht(x: jax.Array) -> jax.Array:
    """Normalized fast Walsh–Hadamard transform along the last axis (pow2)."""
    n = x.shape[-1]
    stages = n.bit_length() - 1
    shape = x.shape
    y = x.reshape(-1, n)
    for _ in range(stages):
        y = y.reshape(y.shape[0], -1, 2)
        a, b = y[..., 0], y[..., 1]
        y = jnp.concatenate([a + b, a - b], axis=-1)
    return (y * (n ** -0.5)).reshape(shape)


def apply_transform(
    t: OrthogonalTransform, x: jax.Array, *, inverse: bool = False
) -> jax.Array:
    """Apply y = T x (or T^T x) along the last axis of ``x``.

    ``inverse=True`` applies the transpose (= inverse, T is orthogonal).
    """
    if t.kind == "none":
        return x
    if t.kind == "kronecker":
        p, q = t.p, t.q
        if not inverse:
            if t.perm is not None:
                x = jnp.take(x, t.perm, axis=-1)
            xm = x.reshape(*x.shape[:-1], p, q)
            if t.A is not None:
                xm = jnp.einsum("ij,...jq->...iq", t.A, xm)
            xm = jnp.einsum("...pq,kq->...pk", xm, t.B)
            return xm.reshape(*x.shape[:-1], t.n)
        xm = x.reshape(*x.shape[:-1], p, q)
        if t.A is not None:
            xm = jnp.einsum("ji,...jq->...iq", t.A, xm)
        xm = jnp.einsum("...pq,qk->...pk", xm, t.B)  # B^T on the right
        y = xm.reshape(*x.shape[:-1], t.n)
        if t.inv_perm is not None:
            y = jnp.take(y, t.inv_perm, axis=-1)
        return y
    # hadamard: T = (A_odd ⊗ H S) P
    odd = 1 if t.A is None else t.A.shape[0]
    pow2 = t.n // odd
    if not inverse:
        if t.perm is not None:
            x = jnp.take(x, t.perm, axis=-1)
        xm = x.reshape(*x.shape[:-1], odd, pow2)
        xm = xm * t.signs  # S
        xm = _fwht(xm)  # H (symmetric)
        if t.A is not None:
            xm = jnp.einsum("ij,...jq->...iq", t.A, xm)
        return xm.reshape(*x.shape[:-1], t.n)
    xm = x.reshape(*x.shape[:-1], odd, pow2)
    if t.A is not None:
        xm = jnp.einsum("ji,...jq->...iq", t.A, xm)
    xm = _fwht(xm)
    xm = xm * t.signs  # S^T = S
    y = xm.reshape(*x.shape[:-1], t.n)
    if t.inv_perm is not None:
        y = jnp.take(y, t.inv_perm, axis=-1)
    return y


# ---------------------------------------------------------------------------
# Algorithm 1 / 2 pieces
# ---------------------------------------------------------------------------


def diag_rescale(W: jax.Array, H: jax.Array, eps: float = 1e-12):
    """Sec. B.1 diagonal rescale minimizing tr(D^-1 H D^-1) ||W D||_F^2.

    Stationarity gives D_i ∝ H_ii^{1/4} / ||W_{:,i}||^{1/2} (the paper's
    text writes sqrt(H_ii / ||W_i||), same scale family).  Returns
    (W D, D^-1 H D^-1, D).
    """
    col_norm = jnp.sqrt(jnp.sum(W * W, axis=0) + eps)
    D = (jnp.diagonal(H) + eps) ** 0.25 / jnp.sqrt(col_norm)
    Wr = W * D[None, :]
    Hr = H / (D[:, None] * D[None, :])
    return Wr, Hr, D


def quant_range(W: jax.Array, rho: float) -> jax.Array:
    """Spectrum-based symmetric quantization range s = rho*||W||_F/sqrt(mn)."""
    m, n = W.shape
    return rho * jnp.linalg.norm(W) / math.sqrt(m * n)


def to_grid(W: jax.Array, s: jax.Array, maxq: int) -> jax.Array:
    """Map [-s, s] -> [0, maxq] (continuous; rounding happens in LDLQ)."""
    return (W / s + 1.0) * (maxq / 2.0)


def from_grid(Wq: jax.Array, s: jax.Array, maxq: int) -> jax.Array:
    """Alg. 2 line 2: W <- s * ((Wq / maxq) * 2 - 1)."""
    return s * (Wq * (2.0 / maxq) - 1.0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PreprocessState:
    """Everything needed to revert Algorithm 1 (and to run inference)."""

    U: OrthogonalTransform  # m side
    V: OrthogonalTransform  # n side
    D: Optional[jax.Array]  # (n,) diagonal rescale, or None
    s: jax.Array  # scalar quantization range
    maxq: int = dataclasses.field(metadata=dict(static=True))


def incoherence_preprocess(
    W: jax.Array,
    H: jax.Array,
    *,
    bits: int,
    seed: int,
    rho: float = 2.4,
    alpha: float = 0.01,
    kind: TransformKind = "kronecker",
    rescale: bool = True,
    permute: bool = True,
    spectrum_range: bool = True,
):
    """Algorithm 1.  Returns (W_grid, H_tilde, state).

    W_grid lives on the continuous grid domain [0, maxq]; H_tilde is the
    conjugated Hessian to feed LDLQ.
    """
    m, n = W.shape
    maxq = 2**bits - 1
    # line: H <- H + alpha mean(diag H) I   (OPTQ damping, kept under IncP)
    H = H + alpha * jnp.mean(jnp.diagonal(H)) * jnp.eye(n, dtype=H.dtype)
    D = None
    if rescale:
        W, H, D = diag_rescale(W, H)
    U = make_transform(kind, m, seed * 2 + 1, permute=permute, dtype=W.dtype)
    V = make_transform(kind, n, seed * 2 + 2, permute=permute, dtype=W.dtype)
    # W <- U W V^T ; H <- V H V^T, all via structured ops (never dense n×n
    # transform matrices).
    W = apply_transform(V, W)  # rows: W V^T
    W = apply_transform(U, W.T).T  # cols: U W
    H = apply_transform(V, H)  # H V^T
    H = apply_transform(V, H.T).T  # V H V^T
    H = (H + H.T) * 0.5  # re-symmetrize fp error
    if spectrum_range:
        s = quant_range(W, rho)
    else:
        s = jnp.max(jnp.abs(W))
    Wg = to_grid(W, s, maxq)
    return Wg, H, PreprocessState(U=U, V=V, D=D, s=s, maxq=maxq)


def incoherence_postprocess(Wq: jax.Array, state: PreprocessState) -> jax.Array:
    """Algorithm 2: revert grid scale, transforms and diagonal rescale."""
    W = from_grid(Wq, state.s, state.maxq)
    W = apply_transform(state.U, W.T, inverse=True).T  # U^T W
    W = apply_transform(state.V, W, inverse=True)  # W V
    if state.D is not None:
        W = W / state.D[None, :]
    return W


# ---------------------------------------------------------------------------
# Incoherence measurement (Figures 2/3)
# ---------------------------------------------------------------------------


def mu_weight(W: jax.Array) -> jax.Array:
    """µ_W such that max|W_ij| = µ ||W||_F / sqrt(mn) (Def. 1)."""
    m, n = W.shape
    return jnp.max(jnp.abs(W)) * math.sqrt(m * n) / jnp.linalg.norm(W)


def mu_hessian(H: jax.Array) -> jax.Array:
    """µ_H such that max|Q_ij| = µ/sqrt(n) for eigvecs Q of H (Def. 1)."""
    n = H.shape[0]
    _, Q = jnp.linalg.eigh(H)
    return jnp.max(jnp.abs(Q)) * math.sqrt(n)
