"""Tensor-parallel distributed serving runtime (DESIGN.md §8).

Layers the single-device continuous-batching engine over a
``(data, model)`` device mesh.  Three things shard over the model axis:

  * **packed quantized weights** — each ``QuantizedLinear``'s 2-bit codes
    get a :class:`NamedSharding` resolved through the rule machinery in
    ``runtime/sharding.py``: column-parallel for QKV/up projections (the
    packed ``(packed_rows(n), m)`` tensor splits on its output dim ``m``)
    and row-parallel for O/down (splits on the packed reduction rows), so
    per-device HBM holds ~1/mp of every block's codes.  The small
    data-dependent factors (``s``, the diagonal rescale ``D``) replicate;
    the orthogonal incoherence transforms regenerate from seeds and ride
    along as replicated jit constants.  GSPMD partitions the projection
    matmuls accordingly — the cross-device reduction for a block lands as
    one psum after each row-parallel matmul (the Kronecker ``Uᵀ`` factor
    that follows mixes the output dim, so the sum cannot be deferred past
    it; see DESIGN.md §8);
  * **the physical KV page pool** — ``(L, P, KV, ps, hd)`` splits on the
    KV-head axis, NEVER on pages: every device owns the full page range
    for its local heads, so block-table indexing resolves locally and
    decode attention moves zero cross-device KV bytes;
  * **the paged-attention dispatches** — decode, batched chunked
    prefill, AND the speculative verify tick all run under ``shard_map``
    over the model axis: each device attends its local KV-head slice of
    the pool with its local query-head group, and the donated in-place
    K/V scatters in the same jitted steps write only local pages.  The
    verify/sampling dispatches are re-jitted with pinned
    ``out_shardings`` in :meth:`DistributedCachedDecoder.make_pool`,
    exactly like decode/prefill, so speculative TP serving moves zero
    cross-device KV bytes.

Everything degrades gracefully: a 1-wide model axis, or an architecture
whose KV-head count does not divide it, falls back to the replicated
single-device math (the divisibility fallback in ``logical_to_pspec``),
so the same engine code serves any mesh.

CPU testing: ``jax.config.update("jax_num_cpu_devices", 8)`` before the
backend starts provides a multi-device host mesh (tests/conftest.py);
tests assert token-identical output vs the single-device engine
(tests/test_distributed.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.quantizer import QuantizedLinear
from repro.kernels.paged_attention.ops import (
    paged_gqa_decode,
    paged_gqa_prefill,
    paged_gqa_verify,
)
from repro.runtime.sharding import MeshContext, serving_rules
from repro.serve.adapter import CachedDecoder
from repro.serve.kv_cache import PagedKVPool

__all__ = [
    "DistributedCachedDecoder",
    "make_serving_mesh",
    "shard_quantized_model",
    "PACKED_AXES",
    "POOL_AXES",
]

# Logical axes of each QuantizedLinear's packed codes, shaped
# (packed_rows(n), m): axis 0 walks the packed reduction rows (the
# layer's INPUT dim), axis 1 the output features.  Column-parallel
# projections shard the output dim; row-parallel shard the reduction —
# the classic Megatron split, expressed through the same rule table the
# training mesh uses (heads/kv_heads/ff -> 'model' under serving_rules).
PACKED_AXES: dict[str, tuple] = {
    "attn.wq": (None, "heads"),
    "attn.wk": (None, "kv_heads"),
    "attn.wv": (None, "kv_heads"),
    "attn.wo": ("heads", None),
    "mlp.wi": (None, "ff"),
    "mlp.wg": (None, "ff"),
    "mlp.wo": ("ff", None),
}

# Physical page pool (L, P, KV, ps, hd): shard KV heads, never pages.
POOL_AXES: tuple = ("layers", "pages", "kv_heads", None, None)


def make_serving_mesh(dp: int, mp: int) -> Mesh:
    """A (data, model) serving mesh; validates against visible devices."""
    need = dp * mp
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {dp}x{mp} needs {need} devices but only {have} are "
            f"visible (on CPU: jax.config.update('jax_num_cpu_devices', "
            f"{need}) before the backend starts)"
        )
    # Auto axes: GSPMD propagates shardings through the gathers and
    # matmuls (Explicit axes, make_mesh's default, would demand an
    # out-sharding on every op that mixes sharded operands)
    return jax.make_mesh((dp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def _serving_ctx(
    mesh: Mesh, rules: Optional[dict] = None
) -> MeshContext:
    return MeshContext(mesh=mesh, rules=dict(rules or serving_rules()))


def shard_quantized_linear(
    layer: QuantizedLinear, ctx: MeshContext, name: str
) -> QuantizedLinear:
    """Place one linear's packed codes sharded on the model axis.

    The divisibility fallback applies per array: a dim the mesh does not
    divide stays replicated, so odd head counts degrade instead of fail.
    """
    spec = ctx.pspec(PACKED_AXES[name], layer.packed.shape)
    packed = jax.device_put(layer.packed, NamedSharding(ctx.mesh, spec))
    rep = ctx.replicated()
    st = dataclasses.replace(
        layer.state,
        s=jax.device_put(layer.state.s, rep),
        D=(
            None if layer.state.D is None
            else jax.device_put(layer.state.D, rep)
        ),
    )
    return dataclasses.replace(layer, packed=packed, state=st)


def _put_tree(tree, sharding):
    return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)


def shard_quantized_model(qm, ctx: MeshContext):
    """Re-place a ``QuantizedModel``'s arrays onto the mesh: packed codes
    sharded per :data:`PACKED_AXES`, everything else (embed, norms, the
    per-layer factors) replicated.  Returns a new model; the input is
    untouched (tests compare against it)."""
    rep = ctx.replicated()
    blocks = []
    for blk in qm.blocks:
        out = {}
        for name, val in blk.items():
            if isinstance(val, QuantizedLinear):
                out[name] = shard_quantized_linear(val, ctx, name)
            else:
                out[name] = _put_tree(val, rep)
        blocks.append(out)
    return dataclasses.replace(
        qm,
        embed=_put_tree(qm.embed, rep),
        final_norm=_put_tree(qm.final_norm, rep),
        blocks=blocks,
    )


def artifact_placer(ctx: MeshContext):
    """A ``placer`` for ``artifacts.load_quantized``: commits every leaf
    straight from the checkpoint shard to its mesh placement — packed
    codes to their model-axis sharding, the rest replicated — so loading
    a large artifact never materializes an unsharded device copy."""
    rep = ctx.replicated()

    def place(key: str, arr):
        parts = key.split("/")
        if (
            len(parts) == 4
            and parts[0] == "blocks"
            and parts[3] == "packed"
            and parts[2] in PACKED_AXES
        ):
            spec = ctx.pspec(PACKED_AXES[parts[2]], arr.shape)
            return jax.device_put(arr, NamedSharding(ctx.mesh, spec))
        return jax.device_put(arr, rep)

    return place


@dataclasses.dataclass
class DistributedCachedDecoder(CachedDecoder):
    """Tensor-parallel :class:`CachedDecoder` over a (data, model) mesh.

    Drop-in for the engine: the adapter hooks (`make_pool`, `_place`,
    `_paged_attention`) and the jit wrapping carry the distribution, the
    engine's host-side scheduling is untouched.  Build via
    :meth:`from_quantized` / :meth:`from_model` / :meth:`load`.
    """

    ctx: Optional[MeshContext] = None
    # set by make_pool once the pool geometry (and thus the divisibility
    # fallback) is known: whether the KV-head axis actually sharded
    _pool_sharded: bool = dataclasses.field(default=False, repr=False)

    def __post_init__(self):
        if self.ctx is None:
            raise ValueError(
                "DistributedCachedDecoder needs a MeshContext; build via "
                "from_quantized/from_model/load(mesh=...)"
            )
        super().__post_init__()
        self._rep = self.ctx.replicated()

    # ---- constructors ---------------------------------------------------

    @classmethod
    def from_quantized(
        cls, qm, *, mesh: Mesh, rules: Optional[dict] = None, **kw
    ) -> "DistributedCachedDecoder":
        ctx = _serving_ctx(mesh, rules)
        return super().from_quantized(
            shard_quantized_model(qm, ctx), ctx=ctx, **kw
        )

    @classmethod
    def from_model(
        cls, model, params, *, mesh: Mesh, rules: Optional[dict] = None, **kw
    ) -> "DistributedCachedDecoder":
        from repro.models.transformer import decoder_axes
        from repro.runtime.sharding import shard_put

        ctx = _serving_ctx(mesh, rules)
        params = shard_put(ctx, params, decoder_axes(model.cfg))
        return super().from_model(model, params, ctx=ctx, **kw)

    @classmethod
    def load(
        cls,
        directory,
        *,
        mesh: Mesh,
        rules: Optional[dict] = None,
        verify: bool = True,
        load_faults=None,
        **kw,
    ) -> tuple["DistributedCachedDecoder", dict]:
        """Load a persistent quantized artifact directly onto the mesh
        (each checkpoint leaf is committed to its sharding as it streams
        out of the npz shards).  Returns (adapter, manifest meta).
        ``verify``/``load_faults`` pass through to
        :func:`artifacts.load_quantized` (shard-digest checking and the
        corrupt_shard injection hook)."""
        from repro.serve.artifacts import load_quantized

        ctx = _serving_ctx(mesh, rules)
        qm, meta = load_quantized(directory, placer=artifact_placer(ctx),
                                  verify=verify, faults=load_faults)
        adapter = super().from_quantized(qm, ctx=ctx, **kw)
        return adapter, meta

    # ---- engine hooks ----------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        return self.ctx.mesh

    def trace_tags(self) -> dict:
        """Mesh geometry stamped on every span: distributed traces stay
        interpretable after export (which axes existed, was the pool
        sharded over KV heads)."""
        shape = dict(self.mesh.shape)
        return {
            "mesh_data": int(shape.get("data", 1)),
            "mesh_model": int(shape.get("model", 1)),
            "mesh_devices": int(self.mesh.size),
            "pool_sharded": bool(self._pool_sharded),
        }

    def make_pool(self, **kw) -> PagedKVPool:
        """Pool with physical pages sharded over KV heads.

        Also (re)wraps the fused decode AND batched-prefill steps with
        pinned ``out_shardings`` so the donated pool buffers come back
        with the same placement every step — the scatters can never
        silently drift the pool to a different layout between steps.
        """
        pool = PagedKVPool(self.cfg, **kw)
        spec = self.ctx.pspec(POOL_AXES, pool.k.shape)
        kv_sh = NamedSharding(self.mesh, spec)
        sc_sh = NamedSharding(self.mesh, P(*spec[:4])) if pool.is_int8 \
            else None
        shardings = (kv_sh, kv_sh, sc_sh, sc_sh)
        pool.arrays = tuple(
            None if a is None else jax.device_put(a, sh)
            for a, sh in zip(pool.arrays, shardings)
        )
        self._jit_paged_programs(self._rep, shardings)
        self._pool_sharded = spec[2] is not None
        return pool

    def _place(self, x, dtype=None):
        """Small per-step host arrays commit replicated on the mesh."""
        return jax.device_put(jnp.asarray(x, dtype), self._rep)

    def _place_tree(self, arrays: tuple):
        """One batched device_put of a step's host arrays, replicated."""
        return jax.device_put(arrays, self._rep)

    # ---- SPMD projections -----------------------------------------------

    def _proj(self, blk, name, h):
        """A sharded ``QuantizedLinear`` runs its packed matmul per shard
        under ``shard_map``: a Pallas kernel cannot be partitioned by
        GSPMD.  Column-parallel layers (codes split on the output dim)
        return output-sharded ``z``; row-parallel layers (codes split on
        the reduction rows) multiply their slice of ``h`` and ``psum`` in
        float32 — the affine dequant correction is linear in ``h``, so
        the shard sums are exact.  The incoherence transforms around the
        matmul stay replicated (GSPMD gathers ``z`` for ``Uᵀ``)."""
        f = blk[name]
        if not isinstance(f, QuantizedLinear) or name not in PACKED_AXES:
            return super()._proj(blk, name, h)
        rows, cols = self.ctx.pspec(PACKED_AXES[name], f.packed.shape)
        if rows is None and cols is None:
            return super()._proj(blk, name, h)
        from repro.kernels.quant_matmul.ops import quant_matmul

        lead = (None,) * (h.ndim - 1)
        maxq = f.state.maxq
        if cols is not None:
            def mm(hl, pk, s):
                return quant_matmul(hl, pk, f.bits, f.n, s, maxq)

            specs = (P(*lead, None), P(None, cols), P()), P(*lead, cols)
        else:
            axes = (rows,) if isinstance(rows, str) else rows
            n_local = f.n // math.prod(self.mesh.shape[a] for a in axes)

            def mm(hl, pk, s):
                z = quant_matmul(hl.astype(jnp.float32), pk, f.bits,
                                 n_local, s, maxq)
                return jax.lax.psum(z, rows).astype(hl.dtype)

            specs = (P(*lead, rows), P(rows, None), P()), P(*lead, None)
        sharded = jax.shard_map(mm, mesh=self.mesh, in_specs=specs[0],
                                out_specs=specs[1], check_vma=False)
        return f(h, matmul=lambda hh: sharded(hh, f.packed, f.state.s))

    # ---- SPMD paged attention -------------------------------------------

    def _paged_attention(self, q, k_new, v_new, pool_k, pool_v, k_scale,
                         v_scale, block_tables, ctx_len, *, layer):
        """Decode attention under ``shard_map``: each model-axis shard
        attends only its local KV-head slice of the page pool (q rides
        the matching query-head group), so decode moves no KV bytes
        across devices.  Falls back to the replicated path when the pool
        could not shard (1-wide axis / indivisible KV heads)."""
        if not self._pool_sharded:
            return super()._paged_attention(
                q, k_new, v_new, pool_k, pool_v, k_scale, v_scale,
                block_tables, ctx_len, layer=layer,
            )
        h_spec = P(None, "model", None)
        kv_spec = P(None, None, "model", None, None)
        interpret = self.paged_interpret

        if k_scale is None:
            def local(q, kn, vn, kp, vp, bt, cl):
                return paged_gqa_decode(
                    q, kn, vn, kp, vp, bt, cl, layer=layer,
                    interpret=interpret,
                )

            f = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(h_spec, h_spec, h_spec, kv_spec, kv_spec, P(), P()),
                out_specs=h_spec, check_vma=False,
            )
            return f(q, k_new, v_new, pool_k, pool_v, block_tables, ctx_len)

        sc_spec = P(None, None, "model", None)

        def local_q(q, kn, vn, kp, vp, ks, vs, bt, cl):
            return paged_gqa_decode(
                q, kn, vn, kp, vp, bt, cl, layer=layer, k_scale=ks,
                v_scale=vs, interpret=interpret,
            )

        f = jax.shard_map(
            local_q, mesh=self.mesh,
            in_specs=(h_spec, h_spec, h_spec, kv_spec, kv_spec, sc_spec,
                      sc_spec, P(), P()),
            out_specs=h_spec, check_vma=False,
        )
        return f(q, k_new, v_new, pool_k, pool_v, k_scale, v_scale,
                 block_tables, ctx_len)

    def _paged_prefill_attention(self, q, k_new, v_new, pool_k, pool_v,
                                 k_scale, v_scale, block_tables, ctx_len,
                                 *, layer, verify=False, k_self=None,
                                 v_self=None):
        """Chunk-batch prefill attention under ``shard_map``: per shard it
        is the single-device prefill kernel over the local KV-head page
        slice (local chunk queries/K/V ride the matching head group), so
        batched prefill moves no KV bytes across devices — and the
        speculative verifier (``verify=True``, the same kernel, plus its
        int8-exactness diagonal override ``k/v_self``) inherits the exact
        sharding, so a TP verify tick also moves zero cross-device KV
        bytes.  Falls back to the replicated path when the pool could not
        shard."""
        if not self._pool_sharded:
            return super()._paged_prefill_attention(
                q, k_new, v_new, pool_k, pool_v, k_scale, v_scale,
                block_tables, ctx_len, layer=layer, verify=verify,
                k_self=k_self, v_self=v_self,
            )
        op = paged_gqa_verify if verify else paged_gqa_prefill
        h_spec = P(None, None, "model", None)  # (B, C, heads, hd)
        kv_spec = P(None, None, "model", None, None)
        interpret = self.paged_interpret

        if k_scale is None:
            def local(q, kn, vn, kp, vp, bt, cl):
                return op(
                    q, kn, vn, kp, vp, bt, cl, layer=layer,
                    interpret=interpret,
                )

            f = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(h_spec, h_spec, h_spec, kv_spec, kv_spec, P(), P()),
                out_specs=h_spec, check_vma=False,
            )
            return f(q, k_new, v_new, pool_k, pool_v, block_tables, ctx_len)

        sc_spec = P(None, None, "model", None)

        if k_self is not None:
            # the self override shards like the chunk K/V (KV heads)
            def local_qs(q, kn, vn, kp, vp, ks, vs, ksf, vsf, bt, cl):
                return op(
                    q, kn, vn, kp, vp, bt, cl, layer=layer, k_scale=ks,
                    v_scale=vs, k_self=ksf, v_self=vsf,
                    interpret=interpret,
                )

            f = jax.shard_map(
                local_qs, mesh=self.mesh,
                in_specs=(h_spec, h_spec, h_spec, kv_spec, kv_spec, sc_spec,
                          sc_spec, h_spec, h_spec, P(), P()),
                out_specs=h_spec, check_vma=False,
            )
            return f(q, k_new, v_new, pool_k, pool_v, k_scale, v_scale,
                     k_self, v_self, block_tables, ctx_len)

        def local_q(q, kn, vn, kp, vp, ks, vs, bt, cl):
            return op(
                q, kn, vn, kp, vp, bt, cl, layer=layer, k_scale=ks,
                v_scale=vs, interpret=interpret,
            )

        f = jax.shard_map(
            local_q, mesh=self.mesh,
            in_specs=(h_spec, h_spec, h_spec, kv_spec, kv_spec, sc_spec,
                      sc_spec, P(), P()),
            out_specs=h_spec, check_vma=False,
        )
        return f(q, k_new, v_new, pool_k, pool_v, k_scale, v_scale,
                 block_tables, ctx_len)
