"""The XLA scatter that writes new K/V into the page pool: the oracle for
the Pallas write kernels, and the path the serving engine takes off-TPU
(ops.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def write_pages_ref(phys: jax.Array, pages: jax.Array, offs: jax.Array,
                    vals: jax.Array) -> jax.Array:
    """Store ``vals`` (L, *idx, KV[, hd]) at the physical (page, offset)
    addresses ``pages``/``offs`` (each of shape idx) of a head-major pool
    (L, P, KV, ps[, hd]): page tensors and their scales alike."""
    return phys.at[:, pages, :, offs].set(
        jnp.moveaxis(vals, 0, pages.ndim).astype(phys.dtype)
    )
