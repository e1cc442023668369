"""Pallas TPU kernels: GQA attention directly against the paged KV pool.

Two entry points, each with its own grid:

* :func:`paged_attention_kernel` — decode: one query token per lane.  Grid
  ``(lane, page_block)``: one step covers a block of ``ppb`` pages of one
  lane for all KV heads, fetched by hand;
* :func:`paged_prefill_kernel` — chunked prefill: a ``(B, C)`` token chunk
  batch per dispatch.  Grid ``(lane, kv_head, page + 1)``: one page of one
  head per step through BlockSpecs, and one trailing "arbitrary" step
  that folds the chunk's own K/V in with an intra-chunk causal mask, so a
  whole cross-request prefill chunk batch attends its paged prior context
  plus itself in a single pass (DESIGN.md §9).  Its ``G·C``-row q tile
  wants per-head blocks; decode's few rows per head want the opposite.

Both read the **physical page pool in place** rather than a dense
``(L, B, Pmax*ps, KV, hd)`` gather of every context page (DESIGN.md §7).
The pool is head-major, ``(L, P, KV, ps, hd)``: one page of one head is a
whole trailing ``(ps, hd)`` tile, and one page of all heads a contiguous
``(KV, ps, hd)`` slab.  Per-lane **block tables** and **context lengths**
ride in scalar prefetch (SMEM).  Neither kernel slices the pool in XLA:
prefill bakes the layer index into its index maps, and decode takes it
in scalar prefetch too, so one program's layer calls share one kernel.

Decode:

* the pool stays in HBM; for each page of a block the kernel copies the
  slab ``pool.at[layer, bt[lane, i]]`` into a ``(2, ppb, KV, ps, hd)``
  VMEM double buffer, and starts block ``j+1``'s copies before it
  computes block ``j``;
* ``ppb`` is the largest power of two ≤ 16 that divides the block-table
  width (:func:`_pages_per_block`);
* scores are one head-batched product over the block's ``ppb·ps``
  positions, and the fp32 ``(KV, G, ·)`` output blocks are revisited
  across blocks as an online-softmax accumulator (running max ``m``,
  normalizer ``l``, unnormalized ``o``);
* **dead blocks do nothing**: a block at or past ``ctx_len`` starts no DMA
  and no compute, so it costs a bare grid step.  Inside a lane's last
  block, pages past ``ctx_len`` are not copied and their block-table
  entries are never read — nor is entry 0 of an empty lane, which returns
  ``m = NEG``, ``l = 0``, ``o = 0``.  Positions past ``ctx_len`` are masked
  and their value rows zeroed, so stale buffer slots (NaN included)
  contribute exactly zero;
* int8 pages carry per-(head, token) fp32 scales (``(L, P, KV, ps)``).
  Mosaic refuses a hand DMA of a page's ``(KV, ps)`` scale tile (an fp32
  minor dim narrower than 128 lanes), so the wrapper gathers the attended
  pages' scales and they ride a pipelined BlockSpec, one block per step;
  K and V are dequantized in fp32 before the products.

The new token's own K/V never touches the pool here: the wrapper (ops.py)
folds the self-attention term into the accumulator analytically and
normalizes, so decode needs no concat and no pre-scatter.  Outputs are the
*unnormalized* accumulator plus ``(m, l)`` statistics for that merge.

Prefill's BlockSpec index maps clamp the page index to the lane's last
valid page (:func:`_pool_specs`), so steps past a lane's context re-ask
for the resident page and Mosaic skips the DMA; an empty lane's steps all
read entry 0, which must hold a real page id (the engine zero-fills block
tables, and physical page 0 is the reserved scratch page).  int8 prefill
steps read the page's whole ``(KV, ps)`` scale tile and pick their head's
row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = float(jnp.finfo(jnp.float32).min)


def _online_update(s, valid, v, o_ref, m_ref, l_ref, v_row=None):
    """One online-softmax accumulation step over a key block.

    s (R, S) scores already NEG-filled outside ``valid``; v (S, hd);
    ``v_row`` optional (1, S) per-key value scale (int8 pages).
    ``pmat`` is gated explicitly so a fully-masked block contributes
    exactly zero (``exp(NEG - NEG) == 1`` would poison the accumulator).
    """
    m_prev = m_ref[0, 0]  # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pmat = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (R, S)
    l_ref[0, 0] = alpha * l_ref[0, 0] + jnp.sum(pmat, -1, keepdims=True)
    pv = pmat if v_row is None else pmat * v_row
    o_ref[0, 0] = o_ref[0, 0] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[0, 0] = m_new


def _head_row(sc_ref, h):
    """Row ``h`` of a page's (KV, ps) scale tile as a (1, ps) vector — a
    masked sublane reduction, which lowers for any KV."""
    sc = sc_ref[0, 0]  # (KV, ps)
    rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    return jnp.sum(jnp.where(rows == h, sc, 0.0), axis=0, keepdims=True)


def _page_scores(q, k_ref, v_ref, ks_ref, vs_ref, h, int8_pages):
    """Scores (R, ps) of queries q (R, hd) against one page of head h,
    plus that page's values (ps, hd) and optional (1, ps) value scale."""
    k = k_ref[0, 0, 0].astype(jnp.float32)  # (ps, hd)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    if not int8_pages:
        return s, v, None
    return s * _head_row(ks_ref, h), v, _head_row(vs_ref, h)


_MAX_PAGES_PER_BLOCK = 16


def _pages_per_block(pa: int) -> int:
    """Pages one decode grid step covers: the largest power of two no
    larger than ``_MAX_PAGES_PER_BLOCK`` that divides the block-table
    width ``pa`` (16 for every bucket from 16 up, and for a 320 cap)."""
    ppb = _MAX_PAGES_PER_BLOCK
    while pa % ppb:
        ppb //= 2
    return ppb


def _decode_kernel(
    layer_ref,  # (1,) int32 scalar-prefetch layer index
    bt_ref,  # (B, Pa) int32 scalar-prefetch block table
    cl_ref,  # (B,)    int32 scalar-prefetch context lengths
    q_ref,  # (1, KV, G, hd)
    k_hbm,  # (L, P, KV, ps, hd) whole pool, left in HBM
    v_hbm,
    *refs,  # [ks_ref, vs_ref (1, ppb, KV, ps)], o_ref, m_ref, l_ref,
    #         k_buf, v_buf (2, ppb, KV, ps, hd), k_sem, v_sem (2,)
    int8_pages: bool,
):
    layer = layer_ref[0]
    if int8_pages:
        ks_ref, vs_ref, *refs = refs
    o_ref, m_ref, l_ref, k_buf, v_buf, k_sem, v_sem = refs
    copies = ((k_hbm, k_buf, k_sem), (v_hbm, v_buf, v_sem))
    b, j = pl.program_id(0), pl.program_id(1)
    _, ppb, KV, ps, hd = k_buf.shape
    T = ppb * ps  # token positions per block
    ctx = cl_ref[b]

    def dma(blk, slot, start: bool):
        """Start (or wait for) the copies of block ``blk``'s live pages
        into buffer ``slot``.  A page at or past ``ctx`` is skipped, and
        its block-table entry is never read."""
        def one_page(i, carry):
            p = blk * ppb + i

            @pl.when(p * ps < ctx)
            def _page():
                page = bt_ref[b, p]
                for src, dst, sem in copies:
                    cp = pltpu.make_async_copy(
                        src.at[layer, page], dst.at[slot, i], sem.at[slot]
                    )
                    if start:
                        cp.start()
                    else:
                        cp.wait()

            return carry

        jax.lax.fori_loop(0, ppb, one_page, 0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * T < ctx)
    def _live_block():
        slot = j % 2

        @pl.when(j == 0)
        def _first():
            dma(0, 0, start=True)

        @pl.when(
            jnp.logical_and(j + 1 < pl.num_programs(1), (j + 1) * T < ctx)
        )
        def _prefetch_next():
            dma(j + 1, 1 - slot, start=True)

        dma(j, slot, start=False)

        q = q_ref[0].astype(jnp.float32)  # (KV, G, hd)
        k = k_buf[slot].astype(jnp.float32)  # (ppb, KV, ps, hd)
        v = v_buf[slot].astype(jnp.float32)
        if int8_pages:  # a per-(page, head, token) row scale
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        k = jnp.swapaxes(k, 0, 1).reshape(KV, T, hd)
        v = jnp.swapaxes(v, 0, 1).reshape(KV, T, hd)
        # slots of pages past ctx hold whatever an earlier block left
        # there: mask their scores and zero their values (0 * NaN is NaN)
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
        valid = j * T + iota((1, 1, T), 2) < ctx  # key columns
        rows = j * T + iota((1, T, 1), 1) < ctx  # value rows
        v = jnp.where(rows, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * (hd**-0.5)  # (KV, G, T)
        s = jnp.where(valid, s, _NEG)
        m_prev = m_ref[0]  # (KV, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pmat = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[0] = alpha * l_ref[0] + jnp.sum(pmat, -1, keepdims=True)
        o_ref[0] = o_ref[0] * alpha + jax.lax.dot_general(
            pmat, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[0] = m_new


def _check_operands(q, k_pages, v_pages, block_tables, ctx_len, layer,
                    k_scale, v_scale):
    if q.ndim != 4:
        raise ValueError(
            f"q must be (B, KV, G, hd) grouped queries, got shape {q.shape}"
        )
    B, KV, G, hd = q.shape
    if k_pages.ndim != 5 or v_pages.shape != k_pages.shape:
        raise ValueError(
            "k_pages/v_pages must both be (L, n_pages, KV, page_size, hd); "
            f"got k_pages {k_pages.shape}, v_pages {v_pages.shape}"
        )
    L, P, KVp, ps, hdp = k_pages.shape
    if (KVp, hdp) != (KV, hd):
        raise ValueError(
            f"page pool carries (KV={KVp}, hd={hdp}) but queries expect "
            f"(KV={KV}, hd={hd})"
        )
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} out of range for {L}-layer pool")
    if block_tables.ndim != 2 or block_tables.shape[0] != B:
        raise ValueError(
            f"block_tables must be (B={B}, pages_attended), got "
            f"{block_tables.shape}"
        )
    if ctx_len.shape != (B,):
        raise ValueError(f"ctx_len must be (B={B},), got {ctx_len.shape}")
    int8_pages = k_pages.dtype == jnp.int8
    if int8_pages:
        if k_scale is None or v_scale is None:
            raise ValueError("int8 pages require k_scale and v_scale")
        if k_scale.shape != (L, P, KV, ps) or v_scale.shape != (L, P, KV, ps):
            raise ValueError(
                f"page scales must be (L, P, KV, ps)={(L, P, KV, ps)}, got "
                f"k_scale {k_scale.shape}, v_scale {v_scale.shape}"
            )
    elif k_scale is not None or v_scale is not None:
        raise ValueError("page scales only apply to int8 pages")
    return int8_pages


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def paged_attention_kernel(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    ctx_len: jax.Array,
    *,
    layer: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax decode attention of layer ``layer`` against the pool.

    q            (B, KV, G, hd) — grouped post-RoPE queries, one token/lane;
    k/v_pages    (L, P, KV, ps, hd) physical pool (fp, or int8 + scales);
    block_tables (B, Pa) int32 physical page per logical page (Pa is the
                 *attended* prefix of the lane's allocation, bucketed by the
                 caller; blocks past a lane's context cost a bare grid step,
                 entries past its last page are never read);
    ctx_len      (B,) int32 valid context tokens per lane.

    Returns ``(o, m, l)``: unnormalized accumulator (B, KV, G, hd) and the
    running max / normalizer (B, KV, G, 1), all fp32 — see ops.py for the
    self-token merge + normalization.
    """
    int8_pages = _check_operands(
        q, k_pages, v_pages, block_tables, ctx_len, layer, k_scale, v_scale
    )
    if int8_pages:
        # Mosaic refuses a hand DMA of a page's (KV, ps) fp32 scale tile
        # (its minor dim is narrower than one 128-lane tile), so the
        # attended pages' scales are gathered here, with entries past a
        # lane's last page read as the scratch page 0, and ride the
        # pipelined BlockSpec path one block at a time.
        Pa, ps = block_tables.shape[1], k_pages.shape[3]
        live = jnp.arange(Pa)[None, :] * ps < ctx_len[:, None]
        bt_live = jnp.where(live, block_tables, 0)
        k_scale, v_scale = k_scale[layer][bt_live], v_scale[layer][bt_live]
    # the layer index rides in scalar prefetch, so every layer's call of
    # one program shares one traced and lowered kernel: lowering a kernel
    # per layer cost about 45 s of set-up per qwen3-14b decode program
    return _decode_call(
        jnp.full((1,), layer, jnp.int32), q, k_pages, v_pages, block_tables,
        ctx_len, k_scale, v_scale, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(layer, q, k_pages, v_pages, block_tables, ctx_len,
                 k_scale, v_scale, *, interpret):
    """The decode ``pallas_call``; ``k/v_scale`` are None or the gathered
    (B, Pa, KV, ps) scales of the attended pages."""
    B, KV, G, hd = q.shape
    Pa = block_tables.shape[1]
    ppb = _pages_per_block(Pa)
    ps = k_pages.shape[3]
    lane = lambda b, j, lyr, bt, cl: (b, 0, 0, 0)
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, KV, G, hd), lane), pool, pool]
    operands = [q, k_pages, v_pages]
    int8_pages = k_scale is not None
    if int8_pages:
        # a dead block re-asks for the lane's last live block: no fetch
        T = ppb * ps
        sc_spec = pl.BlockSpec(
            (1, ppb, KV, ps),
            lambda b, j, lyr, bt, cl: (
                b, jnp.minimum(j, jnp.maximum(pl.cdiv(cl[b], T) - 1, 0)),
                0, 0,
            ),
        )
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]
    scratch = [pltpu.VMEM((2, ppb, KV, ps, hd), k_pages.dtype)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Pa // ppb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, KV, G, hd), lane),
            pl.BlockSpec((1, KV, G, 1), lane),
            pl.BlockSpec((1, KV, G, 1), lane),
        ],
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, int8_pages=int8_pages),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(layer, block_tables, ctx_len, *operands)


def _pool_specs(pool_shape, layer):
    """BlockSpecs of one (head, page) step over the head-major pool and of
    the page's whole (KV, ps) scale tile.  The page index is clamped to
    the lane's last valid page: grid steps past a short lane's context
    re-DMA the block already in VMEM (Mosaic elides the copy), instead of
    streaming dead/scratch pages to mask them.  An EMPTY lane (cl == 0)
    clamps to entry 0, which must be a valid page id (engine convention:
    zero-fill -> scratch page 0)."""
    _, _, KV, ps, hd = pool_shape

    def page(bt, cl, b, p):
        last = jnp.maximum(pl.cdiv(cl[b], ps) - 1, 0)
        return bt[b, jnp.minimum(p, last)]

    kv_spec = pl.BlockSpec(
        (1, 1, 1, ps, hd),
        lambda b, h, p, bt, cl: (layer, page(bt, cl, b, p), h, 0, 0),
    )
    sc_spec = pl.BlockSpec(
        (1, 1, KV, ps),
        lambda b, h, p, bt, cl: (layer, page(bt, cl, b, p), 0, 0),
    )
    return kv_spec, sc_spec


def _prefill_kernel(
    bt_ref,  # (B, Pa) int32 scalar-prefetch block table
    cl_ref,  # (B,)    int32 scalar-prefetch PRIOR-context lengths
    q_ref,  # (1, 1, G*C, hd) chunk queries, rows G-major / chunk-pos-minor
    k_ref,  # (1, 1, 1, ps, hd) one physical context page of one head
    v_ref,  # (1, 1, 1, ps, hd)
    kc_ref,  # (1, 1, C, hd) the chunk's own K (not yet in the pool)
    vc_ref,  # (1, 1, C, hd)
    *refs,  # [kself_ref, vself_ref (1,1,C,hd)], [ks_ref, vs_ref
    #         (1,1,KV,ps)], o_ref, m_ref, l_ref
    page_size: int,
    chunk: int,
    int8_pages: bool,
    has_self: bool,
):
    refs = list(refs)
    kself_ref, vself_ref = (refs.pop(0), refs.pop(0)) if has_self else (None, None)
    if int8_pages:
        ks_ref, vs_ref, o_ref, m_ref, l_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref = refs
    b, h, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_ctx = pl.num_programs(2) - 1  # trailing step is the chunk block

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G*C, hd)
    scale = q.shape[-1] ** -0.5

    @pl.when(p < n_ctx)
    def _ctx_page():
        # every chunk token attends all prior-context positions < cl, so
        # the whole (G*C)-row tile shares one page mask.
        s, v, v_row = _page_scores(
            q, k_ref, v_ref, ks_ref, vs_ref, h, int8_pages
        )  # (G*C, ps)
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        valid = pos < cl_ref[b]
        s = jnp.where(valid, s, _NEG)
        _online_update(s, valid, v, o_ref, m_ref, l_ref, v_row)

    @pl.when(p == n_ctx)
    def _chunk_block():
        # the chunk attends itself causally: row r is chunk position
        # r % chunk (G-major row layout), key column t valid iff t <= pos.
        # Every row keeps its self column, so the merged softmax is finite
        # even for ctx_len == 0 lanes and padded tail tokens.
        kc = kc_ref[0, 0].astype(jnp.float32)  # (C, hd)
        vc = vc_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (G*C, C)
        rows = s.shape[0]
        row_pos = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0) % chunk
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        causal = col <= row_pos
        diag = col == row_pos
        if has_self:
            # diagonal override (speculative verify): each token's score
            # to ITSELF comes from the fp self K, not the chunk array
            kself = kself_ref[0, 0].astype(jnp.float32)  # (C, hd)
            s_self = jax.lax.dot_general(
                q, kself, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(diag, s_self, s)
        s = jnp.where(causal, s, _NEG)
        if not has_self:
            _online_update(s, causal, vc, o_ref, m_ref, l_ref)
        else:
            # _online_update with one extra term: the diagonal's value
            # contribution swaps from vc to the override
            m_prev = m_ref[0, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pmat = jnp.where(causal, jnp.exp(s - m_new), 0.0)  # (G*C, C)
            l_ref[0, 0] = alpha * l_ref[0, 0] + jnp.sum(
                pmat, -1, keepdims=True
            )
            acc = jax.lax.dot_general(
                pmat, vc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            vd = vself_ref[0, 0].astype(jnp.float32) - vc
            acc = acc + jax.lax.dot_general(
                jnp.where(diag, pmat, 0.0), vd, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            o_ref[0, 0] = o_ref[0, 0] * alpha + acc
            m_ref[0, 0] = m_new
        o_ref[0, 0] = o_ref[0, 0] / l_ref[0, 0]  # normalize in place


def _check_prefill_operands(q, k_chunk, v_chunk, k_pages, v_pages,
                            block_tables, ctx_len, layer, k_scale, v_scale,
                            k_self=None, v_self=None):
    if q.ndim != 5:
        raise ValueError(
            f"q must be (B, KV, G, C, hd) grouped chunk queries, got shape "
            f"{q.shape}"
        )
    B, KV, G, C, hd = q.shape
    if k_chunk.shape != (B, C, KV, hd) or v_chunk.shape != k_chunk.shape:
        raise ValueError(
            f"k_chunk/v_chunk must both be (B={B}, C={C}, KV={KV}, hd={hd}); "
            f"got k_chunk {k_chunk.shape}, v_chunk {v_chunk.shape}"
        )
    if (k_self is None) != (v_self is None):
        raise ValueError("k_self and v_self must be given together")
    if k_self is not None and (
        k_self.shape != k_chunk.shape or v_self.shape != v_chunk.shape
    ):
        raise ValueError(
            f"k_self/v_self must match k_chunk {k_chunk.shape}; got "
            f"k_self {k_self.shape}, v_self {v_self.shape}"
        )
    # pool/table/scale checks are shared with the decode entry; a
    # single-chunk-position view of q has its (B, KV, G, hd) shape
    return _check_operands(
        q[:, :, :, 0], k_pages, v_pages, block_tables, ctx_len, layer,
        k_scale, v_scale,
    )


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def paged_prefill_kernel(
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
) -> jax.Array:
    """Causal chunked-prefill attention of layer ``layer`` against the pool.

    q            (B, KV, G, C, hd) grouped post-RoPE chunk queries — lane b's
                 chunk token t sits at absolute position ``ctx_len[b] + t``;
    k/v_chunk    (B, C, KV, hd) the chunk's own post-RoPE K/V (NOT yet
                 scattered into the pool);
    k/v_pages    (L, P, KV, ps, hd) physical pool (fp, or int8 + scales);
    block_tables (B, Pa) int32, bucketed to the longest PRIOR context;
    ctx_len      (B,) int32 valid prior-context tokens per lane (the chunk's
                 start position) — ragged, 0 for fresh admissions;
    k/v_self     optional (B, C, KV, hd) diagonal override: token t's
                 attention to ITSELF uses these instead of k/v_chunk (the
                 speculative verifier passes the fp pre-quantization K/V
                 here while the chunk arrays carry the int8 round-trip).

    Grid is ``(lane, kv_head, page+1)``: the context pages stream through
    a per-page online-softmax step (index-map clamp included), and the one
    extra trailing step folds in the intra-chunk causal block and
    normalizes.  Returns the normalized output (B, KV, G, C, hd) fp32.
    """
    int8_pages = _check_prefill_operands(
        q, k_chunk, v_chunk, k_pages, v_pages, block_tables, ctx_len, layer,
        k_scale, v_scale, k_self, v_self,
    )
    has_self = k_self is not None
    B, KV, G, C, hd = q.shape
    Pa = block_tables.shape[1]
    qf = q.reshape(B, KV, G * C, hd)
    # steps at/past a lane's last valid page (including the whole trailing
    # chunk step) re-ask for the resident page
    kv_spec, sc_spec = _pool_specs(k_pages.shape, layer)
    # chunk K/V go head-major too, so each step reads one (C, hd) tile
    heads_major = lambda x: x.transpose(0, 2, 1, 3)  # (B, KV, C, hd)
    chunk_spec = pl.BlockSpec(
        (1, 1, C, hd), lambda b, h, p, bt, cl: (b, h, 0, 0)
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, G * C, hd), lambda b, h, p, bt, cl: (b, h, 0, 0)
        ),
        kv_spec,
        kv_spec,
        chunk_spec,
        chunk_spec,
    ]
    operands = [qf, k_pages, v_pages, heads_major(k_chunk),
                heads_major(v_chunk)]
    if has_self:
        in_specs += [chunk_spec, chunk_spec]
        operands += [heads_major(k_self), heads_major(v_self)]
    if int8_pages:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, Pa + 1),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, 1, G * C, hd), lambda b, h, p, bt, cl: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, G * C, 1), lambda b, h, p, bt, cl: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, G * C, 1), lambda b, h, p, bt, cl: (b, h, 0, 0)
            ),
        ],
    )
    o, _, _ = pl.pallas_call(
        functools.partial(
            _prefill_kernel, page_size=k_pages.shape[3], chunk=C, int8_pages=int8_pages,
            has_self=has_self,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G * C, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G * C, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G * C, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_tables, ctx_len, *operands)
    return o.reshape(B, KV, G, C, hd)
