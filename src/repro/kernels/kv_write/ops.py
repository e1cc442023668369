"""Public wrapper around the pool-write Pallas kernels.

``write_pages`` is what every paged serving program calls once per
dispatch, through ``serve.kv_cache.write_kv``: the Pallas kernels on TPU
(or under ``interpret``, for tests), the XLA scatter of
``ref.py`` elsewhere, exactly like
``kernels.paged_attention.ops``.
"""
from __future__ import annotations

import jax

from repro.kernels.kv_write.kernel import (
    paged_kv_write,
    paged_kv_write_scales,
)
from repro.kernels.kv_write.ref import write_pages_ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def write_pages(arrays: tuple, pages: jax.Array, offs: jax.Array,
                vals: tuple, *, interpret: bool = False) -> tuple:
    """Store ``vals[i]`` (L, *idx, KV[, hd]) at the physical (page, offset)
    addresses ``pages``/``offs`` (each of shape idx) of ``arrays[i]``, a
    head-major page pool (L, P, KV, ps, hd) or its scales (L, P, KV, ps).
    Returns the updated arrays, written in place where they are donated.
    """
    if not (on_tpu() or interpret):
        return tuple(
            write_pages_ref(a, pages, offs, v) for a, v in zip(arrays, vals)
        )
    out = list(arrays)
    for rank, kernel in ((5, paged_kv_write), (4, paged_kv_write_scales)):
        idx = [i for i, a in enumerate(arrays) if a.ndim == rank]
        if idx:
            new = kernel(
                tuple(arrays[i] for i in idx), pages, offs,
                tuple(vals[i] for i in idx), interpret=interpret,
            )
            for i, a in zip(idx, new):
                out[i] = a
    return tuple(out)
