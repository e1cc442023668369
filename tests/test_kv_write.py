"""Pool-write kernel parity: the interpret-mode Pallas write
(``kernels.kv_write``) against the XLA scatter it replaces, through
``serve.kv_cache.write_kv`` as the serving programs call it.  The pools
must come out bit-identical, int8 values and scales included, and every
page no token addresses must be untouched."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.kv_cache import write_kv

L, P, PS, HD = 2, 200, 16, 128  # 200 pages span two 128-page scale blocks


def _spans(rng, lanes, width, lens):
    """Engine-style addresses: lane b writes ``lens[b]`` consecutive
    positions from a random start through its own pages (crossing page
    boundaries); its padded tail and empty lanes point at scratch page 0."""
    free = list(rng.permutation(np.arange(1, P)))
    pages = np.zeros((lanes, width), np.int32)
    offs = np.zeros((lanes, width), np.int32)
    for b, n in enumerate(lens):
        start = int(rng.integers(0, 3 * PS))
        own = [free.pop() for _ in range((start + width) // PS + 1)]
        for t in range(n):
            pos = start + t
            pages[b, t], offs[b, t] = own[pos // PS], pos % PS
    return pages, offs


def _decode(rng):
    """16 lanes on 16 distinct pages, lane 0 at the last offset of its
    page, lane 1 on the last page (in a partial 128-page scale block),
    lanes 13-15 padding."""
    pages = rng.permutation(np.arange(1, P - 1))[:16].astype(np.int32)
    offs = rng.integers(0, PS, 16).astype(np.int32)
    offs[0], pages[1] = PS - 1, P - 1
    pages[13:], offs[13:] = 0, 0
    return pages, offs


ADDRESSES = {
    "decode": _decode,
    "prefill": lambda rng: _spans(rng, 3, 40, [40, 23, 0]),
    "verify": lambda rng: _spans(rng, 4, 5, [5, 3, 5, 1]),  # K + 1 = 5
}


@pytest.mark.parametrize("kv", [8, 2], ids=["KV8", "KV2"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("shape", list(ADDRESSES))
def test_kernel_write_matches_scatter_bitwise(shape, int8, kv):
    rng = np.random.default_rng(7)
    pages, offs = ADDRESSES[shape](rng)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    dims = (L, P, kv, PS, HD)
    if int8:
        pool = (
            jax.random.randint(ks[0], dims, -127, 128, jnp.int8),
            jax.random.randint(ks[1], dims, -127, 128, jnp.int8),
            jax.random.uniform(ks[2], dims[:-1], minval=1e-3, maxval=0.1),
            jax.random.uniform(ks[3], dims[:-1], minval=1e-3, maxval=0.1),
        )
    else:
        pool = (
            jax.random.normal(ks[0], dims, jnp.bfloat16),
            jax.random.normal(ks[1], dims, jnp.bfloat16),
            None, None,
        )
    new = (L, *pages.shape, kv, HD)
    kn = jax.random.normal(ks[4], new, jnp.bfloat16)
    vn = jax.random.normal(ks[5], new, jnp.bfloat16) * 3
    pg, of = jnp.asarray(pages), jnp.asarray(offs)
    want = write_kv(*pool, pg, of, kn, vn)
    got = write_kv(*pool, pg, of, kn, vn, True)
    untouched = np.setdiff1d(np.arange(P), pages)
    for before, w, g in zip(pool, want, got):
        if before is None:
            assert w is None and g is None
            continue
        w, g = np.asarray(w), np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))
        np.testing.assert_array_equal(
            g[:, untouched].view(np.uint8),
            np.asarray(before)[:, untouched].view(np.uint8),
        )
