"""Pallas TPU kernels: write new K/V into the page pool in place.

The pool is head-major, ``(L, P, KV, ps, hd)``, and one token's K (or V)
of one layer is a row ``[page, :, off, :]`` that lies inside the packed
``(16, 128)`` bf16 (``(32, 128)`` int8) tile of each head's page.  XLA's
scatter cannot write such a sub-tile row in place: layout assignment
moves the whole pool into a layout where the row is contiguous, scatters
there and moves it back, two pool-sized copies per array (DESIGN.md §7).
These kernels alias the pool in and out (``input_output_aliases``), so
the compiled program updates the donated buffer where it lies.

Both kernels take the addresses sorted by page (a stable sort, so tokens
keep their order within a page and a later write to the same row wins,
as in the scatter).  A **run** is the tokens of one page; no two runs of
a call share a page, so runs may be read, patched and written back in
any overlap without a hazard.

* :func:`paged_kv_write` — page tensors.  Grid ``(layer,)``.  The layer's
  new rows ride in a VMEM block; the pool stays in HBM.  Each run's page
  ``pool[layer, page]`` (all heads, ``(KV, ps, hd)``) is copied into a
  ring of VMEM slots ``_AHEAD`` runs ahead, its tokens' rows are selected
  in (an iota compare: Mosaic cannot store one row of a packed tile at a
  dynamic offset), and it is copied back.  A slot is refilled only after
  its last write-back is waited for.  Every page array of the call (K and
  V) shares the run table and moves in the same steps.
* :func:`paged_kv_write_scales` — int8 pools' per-(token, head) fp32
  scales ``(L, P, KV, ps)``.  Their device layout keeps the page axis
  minor (``(L, KV, ps, P)`` in memory, which wastes no lanes on a 16-wide
  page), and Mosaic refuses a hand copy of a ``(KV, ps)`` tile narrower
  than 128 lanes.  So the kernel takes the transposed view, which is
  that layout and costs no copy, and updates it through BlockSpecs of
  ``W = 128`` pages: grid ``(layer, block)`` over the distinct blocks the
  tokens touch, at most ``ceil(P / 128)``.  Grid steps past the last
  touched block re-ask for it, so they move nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_AHEAD = 8  # page reads in flight ahead of the run being patched
_SLOTS = 16  # VMEM page slots per array: reads ahead + writes in flight
_LANES = 128  # pages per scale block


def _runs(pages: jax.Array, offs: jax.Array, group: int = 1):
    """Sort token addresses by ``page // group``.  Returns ``(n, first,
    order, page, off)``: the number of distinct groups ``n`` (1,), the
    sorted index ``first[j]`` (N + 1,) at which group ``j`` starts (N
    past the last), each sorted token's original index ``order``, and
    its page and offset."""
    flat_p, flat_o = pages.reshape(-1), offs.reshape(-1)
    N = flat_p.shape[0]
    order = jnp.argsort(flat_p, stable=True).astype(jnp.int32)
    page, off = flat_p[order], flat_o[order]
    key = page // group
    new = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    first = jnp.nonzero(new, size=N + 1, fill_value=N)[0].astype(jnp.int32)
    n = jnp.sum(new, dtype=jnp.int32).reshape(1)
    return n, first, order, page, off


def _page_kernel(n_ref, first_ref, order_ref, page_ref, off_ref, *refs,
                 n_arrays: int):
    vals = refs[:n_arrays]  # (1, N, KV, hd) the layer's new rows
    pools = refs[2 * n_arrays:3 * n_arrays]  # (L, P, KV, ps, hd) in HBM
    bufs = refs[3 * n_arrays:4 * n_arrays]  # (_SLOTS, KV, ps, hd)
    rsem, wsem = refs[4 * n_arrays:]  # (n_arrays, _SLOTS) each
    layer, runs = pl.program_id(0), n_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, bufs[0].shape[1:], 1)

    def copy(j, read: bool, start: bool):
        """Start (or wait for) run ``j``'s page copies: into its slot
        (``read``) or back to the pool."""
        slot, page = j % _SLOTS, page_ref[first_ref[j]]
        for i, (pool, buf) in enumerate(zip(pools, bufs)):
            hbm, vmem = pool.at[layer, page], buf.at[slot]
            cp = (pltpu.make_async_copy(hbm, vmem, rsem.at[i, slot]) if read
                  else pltpu.make_async_copy(vmem, hbm, wsem.at[i, slot]))
            if start:
                cp.start()
            else:
                cp.wait()

    for j in range(min(_AHEAD, order_ref.shape[0])):  # N tokens
        @pl.when(j < runs)
        def _prologue():
            copy(j, read=True, start=True)

    def run(j, carry):
        ahead = j + _AHEAD

        @pl.when(ahead < runs)
        def _prefetch():
            @pl.when(ahead >= _SLOTS)
            def _free_slot():
                copy(ahead - _SLOTS, read=False, start=False)

            copy(ahead, read=True, start=True)

        copy(j, read=True, start=False)
        slot = j % _SLOTS

        def token(t, pages):
            hit = rows == off_ref[t]
            tok = order_ref[t]
            return tuple(
                jnp.where(hit, v[0, tok][:, None, :], p)
                for v, p in zip(vals, pages)
            )

        patched = jax.lax.fori_loop(
            first_ref[j], first_ref[j + 1], token,
            tuple(buf[slot] for buf in bufs),
        )
        for buf, p in zip(bufs, patched):
            buf[slot] = p
        copy(j, read=False, start=True)
        return carry

    jax.lax.fori_loop(0, runs, run, 0)

    def drain(j, carry):
        copy(j, read=False, start=False)
        return carry

    jax.lax.fori_loop(jnp.maximum(runs - _SLOTS, 0), runs, drain, 0)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM array once its two minor dims fill native tiles."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // item)
    *major, r, c = shape
    n = -(-r // sub) * sub * (-(-c // 128) * 128) * item
    for d in major:
        n *= d
    return n


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(pools: tuple, pages: jax.Array, offs: jax.Array,
                   vals: tuple, *, interpret: bool = False) -> tuple:
    """Write ``vals[i]`` (L, *idx, KV, hd) into page pool ``pools[i]``
    (L, P, KV, ps, hd) at the addresses ``pages``/``offs`` (idx), in
    place.  All pools share one geometry and one run table."""
    n = len(pools)
    L, _, KV, ps, hd = pools[0].shape
    N = pages.size
    vals = tuple(
        v.reshape(L, N, KV, hd).astype(p.dtype) for v, p in zip(vals, pools)
    )
    val_spec = pl.BlockSpec((1, N, KV, hd), lambda l, *_: (l, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    page_shape = (_SLOTS, KV, ps, hd)
    vmem = sum(
        2 * _vmem_bytes((N, KV, hd), p.dtype)
        + _vmem_bytes(page_shape, p.dtype) for p in pools
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(L,),
        in_specs=[val_spec] * n + [hbm] * n,
        out_specs=[hbm] * n,
        scratch_shapes=[pltpu.VMEM(page_shape, p.dtype) for p in pools]
        + [pltpu.SemaphoreType.DMA((n, _SLOTS))] * 2,
    )
    out = pl.pallas_call(
        functools.partial(_page_kernel, n_arrays=n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={5 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (16 << 20),
        ),
        interpret=interpret,
        name="paged_kv_write",
    )(*_runs(pages, offs), *vals, *pools)
    return tuple(out)


def _scale_kernel(n_ref, blk_ref, first_ref, order_ref, page_ref, off_ref,
                  *refs, n_arrays: int):
    vals = refs[:n_arrays]  # (1, N, KV) the layer's new scales
    ins = refs[n_arrays:2 * n_arrays]  # (1, KV, ps, W) a block of pages
    outs = refs[2 * n_arrays:]
    j = pl.program_id(1)

    # past the last touched block the output block is the one patched
    # last, still resident: leave it be
    @pl.when(j < n_ref[0])
    def _patch():
        _, KV, ps, W = ins[0].shape
        rows = jax.lax.broadcasted_iota(jnp.int32, (ps, W), 0)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (ps, W), 1)
        heads = jax.lax.broadcasted_iota(jnp.int32, (1, KV), 1)

        def token(t, planes):
            hit = (rows == off_ref[t]) & (lanes == page_ref[t] % W)
            out = []
            for v, per_head in zip(vals, planes):
                s = v[0, pl.ds(order_ref[t], 1), :]  # (1, KV)
                out.append(tuple(
                    jnp.where(
                        hit,
                        jnp.sum(jnp.where(heads == h, s, 0.0), axis=1,
                                keepdims=True),
                        per_head[h],
                    )
                    for h in range(KV)
                ))
            return tuple(out)

        planes = jax.lax.fori_loop(
            first_ref[j], first_ref[j + 1], token,
            tuple(tuple(r[0, h] for h in range(KV)) for r in ins),
        )
        for o, per_head in zip(outs, planes):
            for h in range(KV):
                o[0, h] = per_head[h]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write_scales(scales: tuple, pages: jax.Array, offs: jax.Array,
                          vals: tuple, *, interpret: bool = False) -> tuple:
    """Write ``vals[i]`` (L, *idx, KV) into scale pool ``scales[i]``
    (L, P, KV, ps) at the addresses ``pages``/``offs`` (idx), in place."""
    n = len(scales)
    L, P, KV, ps = scales[0].shape
    N = pages.size
    W = min(_LANES, P)
    n_blk = min(N, pl.cdiv(P, W))
    runs, first, order, page, off = _runs(pages, offs, W)
    # block of each grid step; steps past the last block repeat it
    blk = (page // W)[jnp.minimum(first[:n_blk], N - 1)]
    vals = tuple(v.reshape(L, N, KV).astype(jnp.float32) for v in vals)
    blk_spec = pl.BlockSpec(
        (1, KV, ps, W), lambda l, j, nr, b, *_: (l, 0, 0, b[j])
    )
    val_spec = pl.BlockSpec((1, N, KV), lambda l, j, *_: (l, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(L, n_blk),
        in_specs=[val_spec] * n + [blk_spec] * n,
        out_specs=[blk_spec] * n,
    )
    views = tuple(jnp.transpose(s, (0, 2, 3, 1)) for s in scales)
    out = pl.pallas_call(
        functools.partial(_scale_kernel, n_arrays=n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in views],
        input_output_aliases={6 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_kv_write_scales",
    )(runs, blk, first, order, page, off, *vals, *views)
    return tuple(jnp.transpose(o, (0, 3, 1, 2)) for o in out)
