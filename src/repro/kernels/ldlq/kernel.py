"""Pallas TPU kernel: in-block sequential LDLQ rounding.

The LDLQ column recurrence is sequential in n but embarrassingly parallel
in m (rows/neurons quantize independently — Eq. 1 is per-row).  The blocked
schedule (GPTQ-style, kernels mirror `core.ldlq.ldlq_blocked`):

  outer (XLA):  base = Err_prev @ U_panel  — one big MXU matmul
  inner (THIS): for k in range(nb):        — nb = 128 columns
                    val = W[:, k] + base[:, k] + E @ U_blk[:, k]
                    q   = clamp(round(val)); E[:, k] = W[:, k] + base[:,k] - q

The kernel grids over ROW blocks (bM x nb panels in VMEM); the inner
fori_loop does nb (bM,)·(nb,) mat-vecs on the VPU with the error matrix E
resident in VMEM — the sequential part never touches HBM.  nb = 128
matches the VREG lane width and MXU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ldlq_kernel(w_ref, b_ref, u_ref, q_ref, e_ref, *, nb: int, maxq: int):
    W = w_ref[...].astype(jnp.float32)  # (bM, nb) raw block weights
    base = b_ref[...].astype(jnp.float32)  # (bM, nb) cross-block feedback
    U = u_ref[...].astype(jnp.float32)  # (nb, nb) strictly upper block

    def body(k, carry):
        Q, E = carry
        corr = E @ jax.lax.dynamic_slice(U, (0, k), (nb, 1))  # (bM, 1)
        wk = jax.lax.dynamic_slice(W, (0, k), (W.shape[0], 1))
        bk = jax.lax.dynamic_slice(base, (0, k), (W.shape[0], 1))
        q = jnp.clip(jnp.round(wk + bk + corr), 0.0, float(maxq))
        # the recurrence feeds back (W - What), NOT (W + base - What)
        Q = jax.lax.dynamic_update_slice(Q, q, (0, k))
        E = jax.lax.dynamic_update_slice(E, wk - q, (0, k))
        return Q, E

    Q0 = jnp.zeros_like(W)
    E0 = jnp.zeros_like(W)
    Q, E = jax.lax.fori_loop(0, nb, body, (Q0, E0))
    q_ref[...] = Q.astype(q_ref.dtype)
    e_ref[...] = E.astype(e_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nb", "bM", "maxq", "interpret"))
def ldlq_block_kernel(
    Wb: jax.Array,
    base: jax.Array,
    Ub: jax.Array,
    *,
    nb: int,
    bM: int = 256,
    maxq: int = 3,
    interpret: bool = False,
):
    """Wb, base: (M, nb); Ub: (nb, nb).  M % bM == 0.

    Returns (Q, E): quantized block and its true error (W_block - Q)."""
    M, n = Wb.shape
    if n != nb:
        raise ValueError(
            f"W block has {n} columns but the kernel was asked for nb={nb}"
        )
    if M % bM:
        raise ValueError(
            f"row count M={M} must be a multiple of the row tile bM={bM}"
        )
    grid = (M // bM,)
    return pl.pallas_call(
        functools.partial(_ldlq_kernel, nb=nb, maxq=maxq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bM, nb), lambda i: (i, 0)),
            pl.BlockSpec((bM, nb), lambda i: (i, 0)),
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bM, nb), lambda i: (i, 0)),
            pl.BlockSpec((bM, nb), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, nb), jnp.float32),
            jax.ShapeDtypeStruct((M, nb), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(Wb, base, Ub)
