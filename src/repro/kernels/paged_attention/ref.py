"""Pure-jnp oracles for paged GQA attention (decode + chunked prefill).

Gathers exactly the attended pages of one layer from the physical pool
(advanced indexing — never the whole allocation, never all layers),
concatenates the new token's/chunk's own K/V, and runs a plain masked
softmax.  This mirrors the gather-dense adapter math, so it doubles as
BOTH the parity oracle for the Pallas kernels (tests) and the fast CPU
path the serving engine dispatches to off-TPU (ops.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _gather_layer(pages, scale, layer, block_tables):
    """(L, P, KV, ps, hd)[layer, bt] -> (B, Pa*ps, KV, hd) fp32."""
    g = pages[layer, block_tables].astype(jnp.float32)  # (B, Pa, KV, ps, hd)
    if scale is not None:
        g = g * scale[layer, block_tables][..., None]
    B, _, KV, _, hd = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, hd)


def paged_gqa_decode_ref(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    ctx_len: jax.Array,
    *,
    layer: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """One-token GQA attention vs paged context + the token itself.

    q (B, H, hd); k_new/v_new (B, KV, hd) — the token's own (post-RoPE) K/V,
    NOT yet in the pool; k/v_pages (L, P, KV, ps, hd); block_tables (B, Pa);
    ctx_len (B,).  Returns (B, H, hd) in q.dtype.
    """
    B, H, hd = q.shape
    KV = k_new.shape[1]
    G = H // KV
    kc = _gather_layer(k_pages, k_scale, layer, block_tables)
    vc = _gather_layer(v_pages, v_scale, layer, block_tables)
    S = kc.shape[1]
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s_ctx = jnp.einsum("bkgd,bskd->bkgs", qg, kc) * (hd**-0.5)
    valid = jnp.arange(S)[None, :] < ctx_len[:, None]
    s_ctx = jnp.where(
        valid[:, None, None], s_ctx, jnp.finfo(s_ctx.dtype).min
    )
    s_self = jnp.einsum(
        "bkgd,bkd->bkg", qg, k_new.astype(jnp.float32)
    ) * (hd**-0.5)
    s = jnp.concatenate([s_ctx, s_self[..., None]], axis=-1)
    probs = jax.nn.softmax(s, axis=-1)
    v_all = jnp.concatenate(
        [vc, v_new.astype(jnp.float32)[:, None]], axis=1
    )
    o = jnp.einsum("bkgs,bskd->bkgd", probs, v_all)
    return o.reshape(B, H, hd).astype(q.dtype)


def paged_gqa_prefill_ref(
    q: jax.Array,
    k_chunk: jax.Array,
    v_chunk: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    ctx_len: jax.Array,
    *,
    layer: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    k_self: jax.Array | None = None,
    v_self: jax.Array | None = None,
) -> jax.Array:
    """Chunked-prefill GQA attention vs paged prior context + the chunk.

    q (B, C, H, hd) post-RoPE chunk queries; k_chunk/v_chunk (B, C, KV, hd)
    the chunk's own (post-RoPE) K/V, NOT yet in the pool; k/v_pages
    (L, P, KV, ps, hd); block_tables (B, Pa); ctx_len (B,) prior-context
    tokens per lane.  Chunk token t of lane b attends context positions
    ``< ctx_len[b]`` plus chunk positions ``<= t``.  -> (B, C, H, hd).

    ``k_self``/``v_self`` (B, C, KV, hd), when given, override the
    DIAGONAL of the intra-chunk block: token t's attention to itself uses
    ``k_self[:, t]``/``v_self[:, t]`` instead of the chunk arrays.  The
    speculative verifier over int8 pools passes the pre-quantization fp
    K/V here while ``k_chunk``/``v_chunk`` carry the int8 round-trip, so
    every score matches what one-token decode computes: prior tokens as
    the pool would return them, self as the analytic fp fold.
    """
    B, C, H, hd = q.shape
    KV = k_chunk.shape[2]
    G = H // KV
    kc = _gather_layer(k_pages, k_scale, layer, block_tables)
    vc = _gather_layer(v_pages, v_scale, layer, block_tables)
    S = kc.shape[1]
    neg = jnp.finfo(jnp.float32).min
    qg = q.reshape(B, C, KV, G, hd).astype(jnp.float32)
    s_ctx = jnp.einsum("bckgd,bskd->bkgcs", qg, kc) * (hd**-0.5)
    valid = jnp.arange(S)[None, :] < ctx_len[:, None]  # (B, S)
    s_ctx = jnp.where(valid[:, None, None, None], s_ctx, neg)
    s_new = jnp.einsum(
        "bckgd,btkd->bkgct", qg, k_chunk.astype(jnp.float32)
    ) * (hd**-0.5)  # (B, KV, G, C, C)
    eye = jnp.eye(C, dtype=bool)
    if k_self is not None:
        s_diag = jnp.einsum(
            "bckgd,bckd->bkgc", qg, k_self.astype(jnp.float32)
        ) * (hd**-0.5)
        s_new = jnp.where(eye, s_diag[..., None], s_new)
    causal = jnp.tril(jnp.ones((C, C), bool))
    s_new = jnp.where(causal, s_new, neg)
    s = jnp.concatenate([s_ctx, s_new], axis=-1)
    probs = jax.nn.softmax(s, axis=-1)
    v_all = jnp.concatenate([vc, v_chunk.astype(jnp.float32)], axis=1)
    o = jnp.einsum("bkgcs,bskd->bkgcd", probs, v_all)
    if v_self is not None:
        # swap the diagonal's value contribution to the override
        dp = jnp.where(eye, probs[..., S:], 0.0)  # (B, KV, G, C, C)
        vd = v_self.astype(jnp.float32) - v_chunk.astype(jnp.float32)
        o = o + jnp.einsum("bkgct,btkd->bkgcd", dp, vd)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)
