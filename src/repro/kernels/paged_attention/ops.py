"""Public wrappers around the paged-attention Pallas kernels.

``paged_gqa_decode`` is what the serving adapter's fast path calls once per
layer per decode step; ``paged_gqa_prefill`` is its chunked-prefill
sibling, called once per layer per batched prefill dispatch.  Both handle:

* backend dispatch — the Pallas kernel on TPU (or under ``interpret``/
  ``force_kernel`` for tests), the jnp oracle elsewhere (this CPU
  container), exactly like ``kernels.quant_matmul.ops``;
* for decode, the **self-token merge**: the kernel accumulates only over
  context pages and returns ``(o, m, l)``; the new token's own (K, V) —
  which is never read back from the pool — is folded in analytically:

      m' = max(m, s_self);  o' = o·e^{m−m'} + v_self·e^{s_self−m'}
      l' = l·e^{m−m'} + e^{s_self−m'};      out = o' / l'

  which equals softmax over [context, self] up to fp reassociation, so the
  fast path needs neither a pre-attention scatter nor a KV concat.  The
  prefill kernel folds its intra-chunk causal block in as one extra grid
  step and normalizes in place, so its wrapper only reshapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    paged_attention_kernel,
    paged_prefill_kernel,
)
from repro.kernels.paged_attention.ref import (
    paged_gqa_decode_ref,
    paged_gqa_prefill_ref,
)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_gqa_decode(
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
    interpret: bool = False,
    force_kernel: bool = False,
) -> jax.Array:
    """One-token GQA decode attention against the physical page pool.

    q (B, H, hd) post-RoPE queries; k_new/v_new (B, KV, hd) the token's own
    post-RoPE K/V (not yet scattered); k/v_pages the full (L, P, KV, ps, hd)
    pool (+ per-(token, head) scales for int8 pages); block_tables (B, Pa)
    bucketed to the attended prefix; ctx_len (B,).  -> (B, H, hd) q.dtype.
    """
    if not (on_tpu() or interpret or force_kernel):
        return paged_gqa_decode_ref(
            q, k_new, v_new, k_pages, v_pages, block_tables, ctx_len,
            layer=layer, k_scale=k_scale, v_scale=v_scale,
        )

    B, H, hd = q.shape
    KV = k_new.shape[1]
    if H % KV:
        raise ValueError(
            f"n_heads {H} must be a multiple of n_kv_heads {KV}"
        )
    qg = q.reshape(B, KV, H // KV, hd)
    o, m, l = paged_attention_kernel(
        qg, k_pages, v_pages, block_tables, ctx_len,
        layer=layer, k_scale=k_scale, v_scale=v_scale, interpret=interpret,
    )
    qf = qg.astype(jnp.float32)
    s_self = jnp.einsum(
        "bkgd,bkd->bkg", qf, k_new.astype(jnp.float32)
    ) * (hd**-0.5)
    m0, l0 = m[..., 0], l[..., 0]
    m_tot = jnp.maximum(m0, s_self)
    a_ctx = jnp.exp(m0 - m_tot)
    a_self = jnp.exp(s_self - m_tot)
    num = o * a_ctx[..., None] + (
        v_new.astype(jnp.float32)[:, :, None, :] * a_self[..., None]
    )
    den = l0 * a_ctx + a_self
    out = num / den[..., None]
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_gqa_prefill(
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
    interpret: bool = False,
    force_kernel: bool = False,
) -> jax.Array:
    """Chunk-batch causal prefill attention against the physical page pool.

    q (B, C, H, hd) post-RoPE chunk queries (lane b's token t at absolute
    position ``ctx_len[b] + t``); k_chunk/v_chunk (B, C, KV, hd) the
    chunk's own post-RoPE K/V (not yet scattered); k/v_pages the full
    (L, P, KV, ps, hd) pool (+ per-(head, token) scales for int8 pages);
    block_tables (B, Pa) bucketed to the longest prior context; ctx_len
    (B,) ragged prior-context lengths; k/v_self optional (B, C, KV, hd)
    DIAGONAL override — each token's attention to itself uses these
    instead of the chunk arrays (the speculative verifier's int8-exactness
    hook; see ``paged_gqa_verify``).  -> (B, C, H, hd) q.dtype.
    """
    if not (on_tpu() or interpret or force_kernel):
        return paged_gqa_prefill_ref(
            q, k_chunk, v_chunk, k_pages, v_pages, block_tables, ctx_len,
            layer=layer, k_scale=k_scale, v_scale=v_scale,
            k_self=k_self, v_self=v_self,
        )

    B, C, H, hd = q.shape
    KV = k_chunk.shape[2]
    if H % KV:
        raise ValueError(
            f"n_heads {H} must be a multiple of n_kv_heads {KV}"
        )
    G = H // KV
    qg = q.reshape(B, C, KV, G, hd).transpose(0, 2, 3, 1, 4)
    o = paged_prefill_kernel(
        qg, k_chunk, v_chunk, k_pages, v_pages, block_tables, ctx_len,
        layer=layer, k_scale=k_scale, v_scale=v_scale,
        k_self=k_self, v_self=v_self, interpret=interpret,
    )  # (B, KV, G, C, hd) normalized fp32
    return o.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)


def paged_gqa_verify(
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
    interpret: bool = False,
    force_kernel: bool = False,
) -> jax.Array:
    """Speculative-verify attention: the chunked-prefill kernel reused.

    A draft-and-verify tick IS a chunked prefill over the drafted tokens:
    lane b carries ``[last_emitted, d_1, ..., d_K]`` at absolute positions
    ``ctx_len[b] .. ctx_len[b] + K``, each token attends the lane's paged
    prior context plus the causal prefix of the chunk itself, and the
    chunk width is ``K + 1`` instead of ``prefill_chunk``.  The grid, the
    index-map clamp, the int8 page handling, and the one trailing
    intra-chunk causal step are untouched — so the verifier inherits the
    prefill kernel's whole parity surface (tests/test_paged_attention.py)
    and any future kernel speedup for free.

    Exactness vs the one-token decode path: for int8 pools the caller
    passes the int8 ROUND-TRIP of the chunk K/V as ``k/v_chunk`` (what the
    pool will return for these tokens once scattered) and the fp original
    as ``k/v_self`` (what one-token decode folds in analytically for the
    self position) — every score then matches the sequential path exactly.
    Kept as a named entry so the serving adapter's verify dispatch states
    its intent, and so a verify-specific kernel schedule (e.g. a
    K+1-specialized grid) can slot in later without touching the adapter.
    """
    if q.shape[1] < 1:
        raise ValueError(
            f"verify chunk needs >= 1 token (the last emitted token), "
            f"got width {q.shape[1]}"
        )
    return paged_gqa_prefill(
        q, k_chunk, v_chunk, k_pages, v_pages, block_tables, ctx_len,
        layer=layer, k_scale=k_scale, v_scale=v_scale,
        k_self=k_self, v_self=v_self, interpret=interpret,
        force_kernel=force_kernel,
    )
