"""Pallas TPU kernel: randomized Hadamard transform y = H_n (s ⊙ x).

TPU adaptation (DESIGN.md §3/§6): a log-depth butterfly network is
VPU-hostile (strided shuffles across lanes); instead we use the Kronecker
identity  H_{a·b} = H_a ⊗ H_b  and evaluate the transform as TWO dense MXU
matmuls with small Hadamard factor matrices (a, b ≤ 128):

    X = reshape(s ⊙ x, (a, b));   Y = H_a · X · H_bᵀ / sqrt(n)

This turns the O(n log n) butterfly into O(n(a+b)) systolic work that the
MXU does at full rate — on TPU the matmul form beats the "fast" transform
for every n that fits a 2-factor split (n ≤ 16384; 3-factor splits cover
the rest).  Factors are built host-side (Sylvester) and stay in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def sylvester(n: int) -> np.ndarray:
    """Unnormalized H_n (n a power of two) via Sylvester's construction."""
    if n <= 0 or n & (n - 1):
        raise ValueError(f"Hadamard order must be a power of two, got {n}")
    H = np.ones((1, 1), np.float32)
    while H.shape[0] < n:
        H = np.block([[H, H], [H, -H]])
    return H


def _had_kernel(x_ref, s_ref, ha_ref, hb_ref, o_ref, *, a: int, b: int):
    bB = x_ref.shape[0]
    x = x_ref[...] * s_ref[...]  # sign flip (broadcast over rows)
    X = x.reshape(bB, a, b)
    Ha = ha_ref[...]
    Hb = hb_ref[...]
    T = jax.lax.dot_general(
        X, Hb, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bB, a, b)
    Y = jax.lax.dot_general(
        T, Ha, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bB, b, a)
    Y = jnp.swapaxes(Y, 1, 2) * (1.0 / np.sqrt(a * b))
    o_ref[...] = Y.reshape(bB, a * b).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("a", "b", "bB", "interpret"))
def hadamard_kernel(
    x: jax.Array,
    signs: jax.Array,
    Ha: jax.Array,
    Hb: jax.Array,
    *,
    a: int,
    b: int,
    bB: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x: (N, a*b); signs: (a*b,); H factors unnormalized Sylvester."""
    N, n = x.shape
    if n != a * b:
        raise ValueError(
            f"x feature dim {n} != a*b = {a}*{b} = {a * b}"
        )
    if N % bB:
        raise ValueError(
            f"row count N={N} must be a multiple of the batch tile bB={bB}"
        )
    return pl.pallas_call(
        functools.partial(_had_kernel, a=a, b=b),
        grid=(N // bB,),
        in_specs=[
            pl.BlockSpec((bB, n), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((a, a), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bB, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, signs, Ha, Hb)
