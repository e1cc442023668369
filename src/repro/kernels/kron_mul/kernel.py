"""Pallas TPU kernel: fused Kronecker-product transform y = (A ⊗ B) x.

The QuIP incoherence transform (Sec. 4.1) multiplies activations by
U = U_L ⊗ U_R.  Materializing U is O(n²) memory and flops; the fused form

    Y[b] = A · X[b] · Bᵀ,   X[b] = reshape(x[b], (p, q))

is two MXU matmuls of tiny factors.  Both factors (p, q ≈ √n ≤ ~192, i.e.
≤ 150 KiB fp32 each) live entirely in VMEM for every grid step; the batch
dim is gridded.  Per step the kernel does

    T = X ⋅ Bᵀ   ((bB·p, q) x (q, q)  — MXU)
    Y = A ⋅ T    (batched over bB via dot_general — MXU)

so the HBM traffic is exactly x in + y out: arithmetic intensity
~ (p + q) flops/byte vs ~2 for the unfused pair of einsums with an
intermediate round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kron_kernel(x_ref, a_ref, b_ref, o_ref, *, p: int, q: int):
    bB = x_ref.shape[0]
    X = x_ref[...].reshape(bB, p, q)
    A = a_ref[...]
    B = b_ref[...]
    # T[b,i,k] = sum_q X[b,i,q] * B[k,q]
    T = jax.lax.dot_general(
        X, B, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    # Y[b,j,k] = sum_i A[j,i] * T[b,i,k]
    Y = jax.lax.dot_general(
        T, A, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bB, k, j) with k from T, j from A -> need (bB, j, k)
    Y = jnp.swapaxes(Y, 1, 2)
    o_ref[...] = Y.reshape(bB, p * q).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("p", "q", "bB", "interpret"))
def kron_mul_kernel(
    x: jax.Array,
    A: jax.Array,
    B: jax.Array,
    *,
    p: int,
    q: int,
    bB: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x: (N, p*q); A: (p, p); B: (q, q) -> (N, p*q).  N % bB == 0."""
    N, n = x.shape
    if n != p * q:
        raise ValueError(
            f"x feature dim {n} != p*q = {p}*{q} = {p * q}"
        )
    if N % bB:
        raise ValueError(
            f"row count N={N} must be a multiple of the batch tile bB={bB}"
        )
    grid = (N // bB,)
    return pl.pallas_call(
        functools.partial(_kron_kernel, p=p, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, n), lambda i: (i, 0)),
            pl.BlockSpec((p, p), lambda i: (0, 0)),
            pl.BlockSpec((q, q), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bB, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, A, B)
