"""Pallas TPU kernel: packed low-bit weight × activation matmul.

Computes ``acc[b, j] = Σ_k x[b, k] · unpack(packed)[k, j]`` where ``packed``
holds b-bit integer grid values packed along the reduction dim
(``repro.core.packing`` layout: value ``k = kp*vals + v`` lives in bits
``[bits*v, bits*(v+1))`` of word ``packed[kp, j]``).

TPU mapping
-----------
* 3D grid ``(B/bB, M/bM, K/bK)``; K innermost ("arbitrary") so the f32
  output tile stays resident in VMEM and is revisited as an accumulator.
* Per step the kernel unpacks a ``(bK/vals, bM)`` int32 word tile into a
  ``(bK, bM)`` operand on the VPU (shift+mask, one reshape across the
  sublane axis) and feeds the MXU via ``jnp.dot`` with fp32 accumulation.
* Packing along K means the unpacked tile is already in (K, M) operand
  layout — no in-VMEM transpose.
* Arithmetic intensity vs a bf16 weight matmul rises ~16/bits×: at 2 bits a
  d_model=8192 decode matvec moves 16× fewer weight bytes, which is what
  makes 2-bit decode compute- rather than HBM-bound (DESIGN.md §3).

The affine dequant ``w = (2s/maxq)·q − s`` is applied *outside* (ops.py):
``z = (2s/maxq)·acc − s·Σ_k x[b,k]`` — keeping the kernel a pure integer-
grid matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(x_ref, p_ref, o_ref, *, bits: int, n_k_tiles: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = 32 // bits
    words = p_ref[...]  # (bKp, bM) int32
    bkp, bm = words.shape
    # unpack in int32: the TPU compiler has no uint32 -> float32 cast, and a
    # logical shift keeps the sign bit out of the top field
    shifts = jax.lax.broadcasted_iota(jnp.int32, (bkp, vals, bm), 1) * bits
    fields = jax.lax.shift_right_logical(
        jnp.broadcast_to(words[:, None, :], (bkp, vals, bm)), shifts
    )
    w = (fields & (2**bits - 1)).astype(jnp.float32)
    w = w.reshape(bkp * vals, bm)  # (bK, bM) grid values, K-major
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit, static_argnames=("bits", "bB", "bM", "bK", "interpret")
)
def quant_matmul_kernel(
    x: jax.Array,
    packed: jax.Array,
    *,
    bits: int,
    bB: int = 128,
    bM: int = 128,
    bK: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x: (B, K) fp; packed: (K/vals, M) int32 → (B, M) f32 grid-matmul.

    B, M, K must be multiples of the respective tiles (ops.py pads).
    bK must be a multiple of ``vals = 32 // bits``.
    """
    B, K = x.shape
    vals = 32 // bits
    Kp, M = packed.shape
    if Kp * vals != K:
        raise ValueError(
            f"packed rows {Kp} x {vals} vals/word = {Kp * vals} does not "
            f"cover the reduction dim K={K} of x {x.shape} at {bits} bits"
        )
    if B % bB or M % bM or K % bK:
        raise ValueError(
            f"dims (B={B}, M={M}, K={K}) must be multiples of tiles "
            f"(bB={bB}, bM={bM}, bK={bK}) — pad via ops.quant_matmul"
        )
    if bK % vals:
        raise ValueError(
            f"K tile bK={bK} must be a multiple of vals-per-word {vals} "
            f"({bits}-bit packing)"
        )
    grid = (B // bB, M // bM, K // bK)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, bits=bits, n_k_tiles=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bK), lambda i, j, k: (i, k)),
            pl.BlockSpec((bK // vals, bM), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bB, bM), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="quant_matmul",
    )(x, packed)
