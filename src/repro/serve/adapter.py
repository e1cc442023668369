"""Unified cached prefill/decode forward over fp and QuIP-quantized models.

A :class:`CachedDecoder` holds per-layer *blocks*: norm params plus one
callable per linear projection, keyed exactly like
``launch.quantize.QuantizedModel.blocks`` ("attn.wq", ..., "mlp.wo").  For
the fp ``Model`` the callables close over dense params (``layers.apply_w``);
for a ``QuantizedModel`` they ARE the :class:`QuantizedLinear` layers, so
every projection runs the packed ``D⁻¹ → V → quant_matmul → Uᵀ`` structured
path — this replaces the old per-token full-recompute serving loop with a
real KV-cached decode for quantized weights.

Two decode paths share the block structure:

  * **gather-dense (reference oracle)** — :meth:`__call__`: the engine
    gathers every context page into a dense ``(L, B, S, KV, hd)`` window
    and the forward concatenates new K/V.  Handles chunked prefill
    (``tokens (1, C)``) and batched decode (``tokens (B, 1)``).
  * **paged fast path** — :meth:`decode_paged`: one jitted dispatch that
    (1) runs every projection — routing ``QuantizedLinear`` through the
    Pallas ``quant_matmul`` kernel path instead of the XLA unpack
    fallback, (2) computes attention *in place* against the physical page
    pool via ``kernels.paged_attention`` (per-lane block tables + context
    lengths, self-token folded in analytically), and (3) writes the new
    K/V into the donated pool tensors in place (``kernels.kv_write``).
    No per-step dense KV copy exists anywhere in this path.

Prefill has the same split: the gather-dense path runs one ``(1, C)``
chunk per request, while :meth:`prefill_paged` runs a whole padded
cross-request chunk batch ``(B, C)`` as one jitted dispatch — projections
through the ``quant_matmul`` kernel dispatch, causal chunk attention over
the page pool via ``kernels.paged_attention.paged_gqa_prefill`` (ragged
per-lane prior-context lengths), and a donated in-place write of every
chunk token's K/V (padded tails land on the scratch page).

Two further fused entries ride the same trunks:

  * :meth:`verify_paged` — speculative draft-and-verify: a ``(B, K+1)``
    chunk batch ``[last_emitted, d_1 .. d_K]`` per decode lane runs the
    PREFILL trunk (``paged_gqa_verify`` — the chunked-prefill kernel
    reused as the verifier), then selects a token at every chunk position
    ON DEVICE (:func:`sample_tokens`) and counts the longest accepted
    draft prefix — one dispatch emits up to K+1 tokens per lane;
  * :meth:`decode_paged_sample` — the one-token decode dispatch with the
    same on-device selection epilogue fused in, so non-speculative
    serving also never ships logits to the host.

On-device selection is a pure function of (request seed, emission index)
via ``jax.random.fold_in``, so sampled streams are reproducible across
batch composition, scheduling, eviction/replay, and speculative grouping
— and a greedy (temperature-0) lane is the exact argmax, which keeps the
speculative path token-identical to one-token decode.  For int8 pools the
verify trunk round-trips the chunk's own K/V through the page quantizer
before attention, matching what the one-token path would read back from
the pool for already-scattered draft tokens (DESIGN.md §10).

Masking uses the same where-set convention as the quantized recompute path
so cached logits match it bit-for-bit up to matmul reassociation.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.quantizer import QuantizedLinear
from repro.kernels.paged_attention.ops import (
    paged_gqa_decode,
    paged_gqa_prefill,
    paged_gqa_verify,
)
from repro.models import layers as L
from repro.models.transformer import unstack_layers
from repro.serve.faults import NO_FAULTS, FaultPlan
from repro.serve.kv_cache import PagedKVPool, quantize_kv_int8, write_kv
from repro.serve.telemetry import NULL_TRACER, Tracer

__all__ = ["CachedDecoder", "sample_tokens"]


def _poison_lanes(logits, lanes):
    """Overwrite the given batch lanes of ``logits`` with NaN — the
    nan_logits fault: exactly what a rotted artifact or an unstable
    kernel would hand the sampler.  Fault path only (never jitted)."""
    if not lanes:
        return logits
    out = np.asarray(logits).copy()
    out[np.asarray(lanes, np.int32)] = np.nan
    return jnp.asarray(out)


def sample_tokens(logits, temps, top_ps, seeds, draws, greedy_only=False):
    """Fused on-device token selection over a step's logits.

    logits (B, T, V); temps/top_ps (B,) fp32; seeds/draws (B,) int32 —
    ``draws[b]`` is how many tokens lane b has already drawn, so chunk
    position t selects with the per-request key
    ``fold_in(PRNGKey(seeds[b]), draws[b] + t)``: the stream is a pure
    function of (seed, emission index), hence reproducible across batch
    composition, scheduling order, eviction/replay, and speculative
    grouping — a verify tick draws exactly the token sequential decode
    would have drawn at each position.  ``temp == 0`` lanes take the
    exact argmax (the greedy/--check path).  Top-p keeps the smallest
    sorted prefix with mass >= top_p (always at least the head), the same
    rule as the host path.  Returns (B, T) int32.

    ``greedy_only`` (static) compiles out the whole draw: when the caller
    knows every lane is temperature-0 (the common serving case and every
    ``--check``), the dispatch carries only the argmax — the sort/scan/
    PRNG sub-graph would otherwise dominate a smoke-scale verify tick.

    Its ops run under the named scope ``sampler``.
    """
    with jax.named_scope("sampler"):
        T, V = logits.shape[1], logits.shape[2]
        greedy = jnp.argmax(logits, axis=-1)
        if greedy_only:
            return greedy.astype(jnp.int32)

        def draw(lg, temp, top_p, seed, idx):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
            z = lg.astype(jnp.float32) / jnp.where(temp > 0.0, temp, 1.0)
            p = jax.nn.softmax(z)
            order = jnp.argsort(-p)
            ps = p[order]
            csum = jnp.cumsum(ps)
            # nucleus filter; the head always survives (SamplingParams pins
            # top_p > 0, but a degenerate caller must get argmax, not tail)
            keep = (csum - ps < top_p).at[0].set(True)
            ps = jnp.where(keep, ps, 0.0)
            # inverse-CDF, unnormalized
            u = jax.random.uniform(key) * ps.sum()
            pick = jnp.searchsorted(jnp.cumsum(ps), u, side="right")
            return order[jnp.clip(pick, 0, V - 1)]

        sampled = jax.vmap(  # lanes x chunk positions
            lambda lg, tp, pp, sd, d0: jax.vmap(
                lambda l1, t: draw(l1, tp, pp, sd, d0 + t)
            )(lg, jnp.arange(T, dtype=jnp.int32))
        )(logits, temps, top_ps, seeds, draws)
        return jnp.where(temps[:, None] > 0.0, sampled,
                         greedy).astype(jnp.int32)


def _int8_roundtrip(x: jax.Array) -> jax.Array:
    """Quantize-dequantize through the int8 page quantizer: the value a
    later read of this token's K/V would see after the pool scatter."""
    q, s = quantize_kv_int8(x)
    return (q.astype(jnp.float32) * s[..., None]).astype(x.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseLinear:
    """One fp projection ``x @ w (+ b)`` as a pytree, so a jitted program
    takes it as an argument like a :class:`QuantizedLinear`."""

    w: Any
    cfg: ArchConfig = dataclasses.field(metadata=dict(static=True))
    b: Any = None

    def __call__(self, x):
        y = L.apply_w(self.w, x, self.cfg)
        return y if self.b is None else y + self.b


def _fp_blocks(params, cfg: ArchConfig) -> list[dict]:
    blocks = []
    for lp in unstack_layers(params):
        at, mp = lp["attn"], lp["mlp"]
        blk = {
            "ln1": lp["ln1"],
            "ln2": lp["ln2"],
            "attn.wq": DenseLinear(at["wq"], cfg, at.get("bq")),
            "attn.wk": DenseLinear(at["wk"], cfg, at.get("bk")),
            "attn.wv": DenseLinear(at["wv"], cfg, at.get("bv")),
            "attn.wo": DenseLinear(at["wo"], cfg),
            "mlp.wi": DenseLinear(mp["wi"], cfg, mp.get("bi")),
            "mlp.wo": DenseLinear(mp["wo"], cfg, mp.get("bo")),
        }
        if cfg.mlp == "swiglu":
            blk["mlp.wg"] = DenseLinear(mp["wg"], cfg)
        if cfg.qk_norm:
            blk["q_norm"] = at["q_norm"]
            blk["k_norm"] = at["k_norm"]
        blocks.append(blk)
    return blocks


@dataclasses.dataclass
class CachedDecoder:
    """KV-cached forward shared by the fp and quantized serving paths."""

    cfg: ArchConfig
    embed: dict
    final_norm: dict
    blocks: list
    paged: bool = False  # engine default: decode via the paged fast path
    paged_interpret: bool = False  # force the Pallas kernel (interpret) off-TPU
    # span sink for the fused dispatches; Engine.attach_tracer swaps in
    # its live tracer (the NULL_TRACER default costs one no-op call)
    tracer: Tracer = dataclasses.field(default=NULL_TRACER, repr=False)
    # fault-injection plan (serve/faults.py); the engine points this at
    # its own plan and maintains the dispatch context (tick, lane_rids).
    # Hooks on the inert default iterate an empty rule list.
    faults: FaultPlan = dataclasses.field(default=NO_FAULTS, repr=False)

    def __post_init__(self):
        if self.cfg.family != "dense":
            raise ValueError(
                f"serving adapter supports the dense family, got {self.cfg.family}"
            )
        # every program takes the weights as its first argument (a
        # pytree), never as closed-over constants: one compile per shape
        # bucket, shared by every adapter with the same config.
        self._fwd = jax.jit(self._forward)
        # quality probe: dense teacher-forced forward + per-layer
        # activation reductions (serve/quality.py canaries); compiles
        # only if a canary actually runs
        self._fwd_probe = jax.jit(self._forward_probe)
        self._jit_paged_programs()

    def _jit_paged_programs(self, rep=None, kv=None):
        """(Re)build the fused paged programs.  Each takes the pool arrays
        ``(k, v, k_scale, v_scale)`` (scales None for fp pages) as one
        donated argument and returns them updated in place by its trailing
        :meth:`_write_kv`.  ``rep``/``kv`` optionally pin the output
        shardings of replicated results and of the pool (distributed
        adapters)."""
        pin = (lambda *o: {}) if rep is None else (
            lambda *o: {"out_shardings": o}
        )
        # decode: one dispatch per engine decode step
        self._fwd_paged = jax.jit(
            self._forward_paged, donate_argnums=(7,), **pin(rep, kv)
        )
        # batched prefill: one dispatch per engine prefill tick over the
        # whole cross-request chunk batch
        self._fwd_prefill = jax.jit(
            self._forward_prefill_paged, donate_argnums=(7,), **pin(rep, kv)
        )
        # decode + on-device selection (non-speculative fast path); the
        # trailing static bool picks the all-greedy argmax-only graph
        self._fwd_paged_s = jax.jit(
            self._forward_paged_sample, donate_argnums=(11,),
            static_argnums=(12,), **pin(rep, rep, kv),
        )
        # speculative verify: prefill trunk + on-device selection + draft
        # acceptance, one dispatch per engine verify tick
        self._fwd_verify = jax.jit(
            self._forward_verify, donate_argnums=(13,),
            static_argnums=(14,), **pin(rep, rep, rep, kv),
        )
        # the pool's host-side writes (the gather-dense path)
        self._fwd_write = jax.jit(
            self._write_kv, donate_argnums=(0,), **pin(*(kv or ()))
        )

    @property
    def weights(self) -> dict:
        """The pytree every jitted program takes as its first argument."""
        return {"embed": self.embed, "final_norm": self.final_norm,
                "blocks": self.blocks}

    # ---- constructors ---------------------------------------------------

    @classmethod
    def from_model(cls, model, params, **kw) -> "CachedDecoder":
        return cls(
            cfg=model.cfg,
            embed=params["embed"],
            final_norm=params["final_norm"],
            blocks=_fp_blocks(params, model.cfg),
            **kw,
        )

    @classmethod
    def from_quantized(cls, qm, **kw) -> "CachedDecoder":
        # QuantizedModel.blocks already has the expected structure, with
        # QuantizedLinear instances as the projection callables.
        return cls(
            cfg=qm.cfg, embed=qm.embed, final_norm=qm.final_norm,
            blocks=qm.blocks, **kw,
        )

    # ---- engine hooks ----------------------------------------------------

    def trace_tags(self) -> dict:
        """Static tags merged into every span/event this adapter's tracer
        records (Engine.attach_tracer calls this once).  Distributed
        adapters override with mesh geometry."""
        return {}

    def make_pool(self, **kw) -> PagedKVPool:
        """Build the engine's KV pool.  Distributed adapters override this
        to place the physical pages sharded over their mesh."""
        pool = PagedKVPool(self.cfg, **kw)
        pool.writer = self._fwd_write
        return pool

    def _place(self, x, dtype=None):
        """Device placement for small per-step host arrays (tokens, block
        tables, context lengths, page addresses).  Distributed adapters
        override to commit them replicated on the mesh."""
        return jnp.asarray(x, dtype)

    def _place_tree(self, arrays: tuple):
        """Place a whole step's small host arrays in ONE device_put call
        (a tuple pytree) — per-array placement round-trips dominate a
        smoke-scale dispatch.  Distributed adapters override to commit
        the tuple replicated on the mesh."""
        return jax.device_put(arrays)

    # ---- gather-dense reference path ------------------------------------

    def __call__(self, tokens, positions, ctx_k, ctx_v, ctx_len):
        """Cached forward (gather-dense reference).

        tokens    (B, T) int32 — new tokens (decode: T=1; prefill: B=1);
        positions (B, T) int32 — absolute position of each new token;
        ctx_k/v   (L, B, S, KV, hd) — gathered context pages (post-RoPE K);
        ctx_len   (B,) int32 — valid context tokens per lane.

        Returns (logits (B, T, V), k_new (L, B, T, KV, hd), v_new (same)).
        """
        if self.faults.rules:
            self.faults.check_dispatch()
        logits, k_new, v_new = self._fwd(self.weights, tokens, positions,
                                         ctx_k, ctx_v, ctx_len)
        if self.faults.rules:
            logits = _poison_lanes(logits, self.faults.nan_lanes())
        return logits, k_new, v_new

    def _forward(self, w, tokens, positions, ctx_k, ctx_v, ctx_len):
        cfg = self.cfg
        x = L.embed(w["embed"], tokens)
        new_k, new_v = [], []
        for i, blk in enumerate(w["blocks"]):
            x, k, v = self._block(blk, x, positions, ctx_k[i], ctx_v[i], ctx_len)
            new_k.append(k)
            new_v.append(v)
        x = L.norm_apply(w["final_norm"], x, cfg)
        logits = L.lm_logits(w["embed"], x)
        return logits, jnp.stack(new_k), jnp.stack(new_v)

    def _block(self, blk, x, positions, ck, cv, ctx_len):
        cfg = self.cfg
        B, T, _ = x.shape
        S = ck.shape[1]
        h = L.norm_apply(blk["ln1"], x, cfg)
        q, k, v = self._qkv(blk, h, positions)
        k_all = jnp.concatenate([ck.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([cv.astype(v.dtype), v], axis=1)
        s = L._gqa_scores(q, k_all, cfg)  # (B, KV, G, T, S+T)
        # context keys: valid below each lane's ctx_len; new keys: causal
        # within the chunk (their absolute positions are >= every ctx pos).
        mask_ctx = jnp.arange(S)[None, None, :] < ctx_len[:, None, None]
        mask_ctx = jnp.broadcast_to(mask_ctx, (B, T, S))
        mask_new = jnp.broadcast_to(
            jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T)
        )
        mask = jnp.concatenate([mask_ctx, mask_new], axis=-1)
        s = jnp.where(mask[:, None, None], s, jnp.finfo(s.dtype).min)
        probs = jax.nn.softmax(s, axis=-1)
        o = L._gqa_out(probs, v_all, cfg)
        o = o.astype(x.dtype).reshape(B, T, cfg.q_dim)
        x = x + blk["attn.wo"](o)
        return self._mlp(blk, x), k, v

    # ---- quality probe ---------------------------------------------------

    def activation_probe(self, tokens):
        """Teacher-forced causal forward over full sequences with
        per-layer activation reductions fused into the same dispatch
        (serve/quality.py canary probe; DESIGN.md §13).

        tokens (B, S) int32.  Returns ``(logits (B, S, V) float32 np,
        {"absmax": (L+1,), "sat": (L+1,)})`` — entry i is the hidden
        state entering block i (the residual stream the block's linears
        consume), entry L the final pre-norm hidden state; ``sat`` is
        the fraction of elements at or beyond
        :data:`repro.serve.quality.SAT_THRESHOLD` (an fp16-overflow
        early warning).  The sequence is padded to the next power of two
        (causal attention — pad positions cannot influence real ones,
        and are masked out of the reductions), bounding compiles across
        canary/shadow lengths.  Runs the dense reference trunk with an
        empty context window: the KV pool is never touched, so an
        in-flight engine's traffic stays token-identical.
        """
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2:
            raise ValueError(f"tokens must be (B, S), got {tokens.shape}")
        B, S = tokens.shape
        Sp = 1
        while Sp < S:
            Sp <<= 1
        padded = np.zeros((B, Sp), np.int32)
        padded[:, :S] = tokens
        positions = np.tile(np.arange(Sp, dtype=np.int32), (B, 1))
        cfg = self.cfg
        ctx = jnp.zeros(
            (cfg.n_layers, B, 0, cfg.n_kv_heads, cfg.head_dim), jnp.float32
        )
        with self.tracer.span("dispatch:activation_probe",
                              lanes=B, tokens=S):
            logits, absmax, sat = self._fwd_probe(
                self.weights, jnp.asarray(padded), jnp.asarray(positions), ctx, ctx,
                jnp.zeros((B,), jnp.int32), jnp.int32(S),
            )
        return np.asarray(logits[:, :S], np.float32), {
            "absmax": np.asarray(absmax, np.float64),
            "sat": np.asarray(sat, np.float64),
        }

    def _forward_probe(self, w, tokens, positions, ctx_k, ctx_v, ctx_len,
                       n_valid):
        from repro.serve.quality import SAT_THRESHOLD

        cfg = self.cfg
        B, T = tokens.shape
        valid = (jnp.arange(T, dtype=jnp.int32) < n_valid)[None, :, None]
        n_el = jnp.maximum(n_valid * B, 1)
        absmax, sat = [], []

        def reduce(x):
            ax = jnp.abs(x.astype(jnp.float32)) * valid
            absmax.append(jnp.max(ax))
            sat.append(
                jnp.sum(ax >= SAT_THRESHOLD) / (n_el * x.shape[-1])
            )

        x = L.embed(w["embed"], tokens)
        for i, blk in enumerate(w["blocks"]):
            reduce(x)
            x, _, _ = self._block(
                blk, x, positions, ctx_k[i], ctx_v[i], ctx_len
            )
        reduce(x)
        x = L.norm_apply(w["final_norm"], x, cfg)
        logits = L.lm_logits(w["embed"], x)
        return logits, jnp.stack(absmax), jnp.stack(sat)

    # ---- shared block pieces --------------------------------------------

    def _proj(self, blk, name, h):
        """Apply one projection; on the paged fast path QuantizedLinear
        goes through the Pallas quant_matmul kernel dispatch (batched
        decode matvec, affine dequant in the epilogue) instead of the XLA
        unpack fallback."""
        f = blk[name]
        if isinstance(f, QuantizedLinear):
            return f(h, use_kernel=True)
        return f(h)

    def _qkv(self, blk, h, positions, *, kernel_proj: bool = False):
        """(q, k, v) each (B, T, heads, hd), qk-normed + RoPE'd."""
        cfg = self.cfg
        B, T, _ = h.shape
        proj = (lambda n: self._proj(blk, n, h)) if kernel_proj else (
            lambda n: blk[n](h)
        )
        q = proj("attn.wq").reshape(B, T, cfg.n_heads, cfg.head_dim)
        k = proj("attn.wk").reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
        v = proj("attn.wv").reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = L.rms_norm(q, blk["q_norm"], cfg.norm_eps)
            k = L.rms_norm(k, blk["k_norm"], cfg.norm_eps)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        return q, k, v

    def _mlp(self, blk, x, *, kernel_proj: bool = False):
        cfg = self.cfg
        h = L.norm_apply(blk["ln2"], x, cfg)
        proj = (lambda n, z: self._proj(blk, n, z)) if kernel_proj else (
            lambda n, z: blk[n](z)
        )
        up = proj("mlp.wi", h)
        if cfg.mlp == "swiglu":
            up = jax.nn.silu(up) * proj("mlp.wg", h)
        else:
            up = jax.nn.gelu(up)
        return x + proj("mlp.wo", up)

    # ---- paged fast path -------------------------------------------------

    def decode_paged(self, tokens, positions, block_tables, ctx_len,
                     pages, offs, pool):
        """Fused decode step against ``pool`` (PagedKVPool), in place.

        tokens/positions (B, 1) int32; block_tables (B, Pa) int32 bucketed
        to the attended prefix; ctx_len (B,) int32; pages/offs (B,) int32
        physical address of each lane's new token (scratch for pad lanes).

        Mutates ``pool.k``/``pool.v`` (+ scales for int8 pools) via donated
        buffers and returns logits (B, 1, V).  The caller still owns the
        host-side length accounting (``pool.note_written``).
        """
        if self.faults.rules:
            self.faults.check_dispatch()
        toks = np.asarray(tokens, np.int32)
        with self.tracer.span("dispatch:decode_paged", lanes=toks.shape[0]):
            args = self._place_tree((
                toks, np.asarray(positions, np.int32),
                np.asarray(block_tables, np.int32),
                np.asarray(ctx_len, np.int32),
                np.asarray(pages, np.int32), np.asarray(offs, np.int32),
            ))
            logits, kv = self._fwd_paged(self.weights, *args, pool.arrays)
            pool.arrays = kv
        if self.faults.rules:
            logits = _poison_lanes(logits, self.faults.nan_lanes())
        return logits

    def _paged_trunk(self, w, tokens, positions, block_tables, ctx_len, kv):
        """Embed -> blocks (paged attention) -> logits; returns the new
        per-layer K/V stacked (L, B, KV, hd) for the trailing scatter."""
        cfg = self.cfg
        x = L.embed(w["embed"], tokens)  # (B, 1, D)
        new_k, new_v = [], []
        for i, blk in enumerate(w["blocks"]):
            x, k, v = self._block_paged(
                blk, x, positions, i, kv, block_tables, ctx_len,
            )
            new_k.append(k)
            new_v.append(v)
        x = L.norm_apply(w["final_norm"], x, cfg)
        logits = L.lm_logits(w["embed"], x)
        return logits, jnp.stack(new_k), jnp.stack(new_v)

    def _forward_paged(self, w, tokens, positions, block_tables, ctx_len,
                       pages, offs, kv):
        logits, kn, vn = self._paged_trunk(
            w, tokens, positions, block_tables, ctx_len, kv
        )
        return logits, self._write_kv(kv, pages, offs, kn, vn)

    # ---- fused decode + on-device selection -------------------------------

    def decode_paged_sample(self, tokens, positions, block_tables, ctx_len,
                            pages, offs, sampling, pool):
        """:meth:`decode_paged` with the token draw fused into the same
        dispatch: the host never sees logits unless it asks for them.

        ``sampling`` is ``(temps, top_ps, seeds, draws)``, each ``(B,)``
        (see :func:`sample_tokens`).  Returns ``(sel (B, 1) int32,
        logits (B, 1, V))``; mutates the pool via donated buffers.
        """
        if self.faults.rules:
            self.faults.check_dispatch()
        toks = np.asarray(tokens, np.int32)
        with self.tracer.span(
            "dispatch:decode_paged_sample", lanes=toks.shape[0]
        ):
            args = self._place_tree((
                toks, np.asarray(positions, np.int32),
                np.asarray(block_tables, np.int32),
                np.asarray(ctx_len, np.int32),
                np.asarray(pages, np.int32), np.asarray(offs, np.int32),
                *self._np_sampling(sampling),
            ))
            sel, logits, kv = self._fwd_paged_s(
                self.weights, *args, pool.arrays, self._all_greedy(sampling)
            )
            pool.arrays = kv
        if self.faults.rules:
            logits = _poison_lanes(logits, self.faults.nan_lanes())
        return sel, logits

    @staticmethod
    def _np_sampling(sampling):
        temps, top_ps, seeds, draws = sampling
        return (
            np.asarray(temps, np.float32), np.asarray(top_ps, np.float32),
            np.asarray(seeds, np.int32), np.asarray(draws, np.int32),
        )

    @staticmethod
    def _all_greedy(sampling) -> bool:
        """Static all-lanes-greedy flag: lets the jit drop the sampling
        sub-graph entirely (one extra compile, reused every greedy step)."""
        return bool((np.asarray(sampling[0]) == 0.0).all())

    def _forward_paged_sample(self, w, tokens, positions, block_tables,
                              ctx_len, pages, offs, temps, top_ps, seeds,
                              draws, kv, greedy_only=False):
        logits, kn, vn = self._paged_trunk(
            w, tokens, positions, block_tables, ctx_len, kv
        )
        sel = sample_tokens(logits, temps, top_ps, seeds, draws, greedy_only)
        return sel, logits, self._write_kv(kv, pages, offs, kn, vn)

    def _block_paged(self, blk, x, positions, layer, kv, block_tables,
                     ctx_len):
        cfg = self.cfg
        B = x.shape[0]
        h = L.norm_apply(blk["ln1"], x, cfg)
        q, k, v = self._qkv(blk, h, positions, kernel_proj=True)
        o = self._paged_attention(
            q[:, 0], k[:, 0], v[:, 0], *kv, block_tables, ctx_len,
            layer=layer,
        )
        o = o.astype(x.dtype).reshape(B, 1, cfg.q_dim)
        x = x + self._proj(blk, "attn.wo", o)
        return self._mlp(blk, x, kernel_proj=True), k[:, 0], v[:, 0]

    def _paged_attention(self, q, k_new, v_new, pool_k, pool_v, k_scale,
                         v_scale, block_tables, ctx_len, *, layer):
        """One layer of decode attention against the pool.  Distributed
        adapters override this with a ``shard_map`` over the model axis so
        each device attends only its local KV-head page slice."""
        return paged_gqa_decode(
            q, k_new, v_new, pool_k, pool_v, block_tables, ctx_len,
            layer=layer, k_scale=k_scale, v_scale=v_scale,
            interpret=self.paged_interpret,
        )

    def _write_kv(self, kv, pages, offs, k_new, v_new):
        """Write a dispatch's new K/V (L, *idx, KV, hd) into the donated
        pool arrays ``kv`` in place, at the addresses ``pages``/``offs``
        (idx).  Distributed adapters override this with a ``shard_map``
        over the model axis, mirroring :meth:`_paged_attention`."""
        return write_kv(*kv, pages, offs, k_new, v_new, self.paged_interpret)

    # ---- paged batched prefill -------------------------------------------

    def prefill_paged(self, tokens, positions, block_tables, ctx_len,
                      pages, offs, pool):
        """Fused cross-request prefill chunk batch against ``pool``.

        tokens/positions (B, C) int32 — lane b carries one request's chunk
        (front-aligned, zero-padded tail); block_tables (B, Pa) int32
        bucketed to the longest PRIOR context; ctx_len (B,) int32 prior
        context per lane (the chunk start); pages/offs (B, C) int32
        physical address of every chunk token (scratch for padding).

        Mutates ``pool.k``/``pool.v`` (+ scales for int8 pools) via donated
        buffers and returns logits (B, C, V).  The caller owns the host-
        side length accounting (``pool.note_span_written``).
        """
        if self.faults.rules:
            self.faults.check_dispatch()
        toks = np.asarray(tokens, np.int32)
        with self.tracer.span(
            "dispatch:prefill_paged",
            lanes=toks.shape[0], chunk=toks.shape[1],
        ):
            args = self._place_tree((
                toks, np.asarray(positions, np.int32),
                np.asarray(block_tables, np.int32),
                np.asarray(ctx_len, np.int32),
                np.asarray(pages, np.int32), np.asarray(offs, np.int32),
            ))
            logits, kv = self._fwd_prefill(self.weights, *args, pool.arrays)
            pool.arrays = kv
        if self.faults.rules:
            logits = _poison_lanes(logits, self.faults.nan_lanes())
        return logits

    def _prefill_trunk(self, w, tokens, positions, block_tables, ctx_len,
                       kv, verify=False):
        """Embed -> blocks (paged chunk attention) -> logits; returns the
        chunk's per-layer K/V stacked (L, B, C, KV, hd) for the scatter.
        ``verify`` marks the speculative verifier: attention goes through
        ``paged_gqa_verify`` and, over int8 pools, the chunk's own K/V is
        round-tripped through the page quantizer before attention, so
        intra-chunk reads match what one-token decode would read back
        from the pool once the draft tokens are scattered."""
        cfg = self.cfg
        x = L.embed(w["embed"], tokens)  # (B, C, D)
        new_k, new_v = [], []
        for i, blk in enumerate(w["blocks"]):
            x, k, v = self._block_prefill_paged(
                blk, x, positions, i, kv, block_tables, ctx_len,
                verify=verify,
            )
            new_k.append(k)
            new_v.append(v)
        x = L.norm_apply(w["final_norm"], x, cfg)
        logits = L.lm_logits(w["embed"], x)
        return logits, jnp.stack(new_k), jnp.stack(new_v)

    def _forward_prefill_paged(self, w, tokens, positions, block_tables,
                               ctx_len, pages, offs, kv):
        logits, kn, vn = self._prefill_trunk(
            w, tokens, positions, block_tables, ctx_len, kv
        )
        # kn/vn (L, B, C, KV, hd); pages/offs (B, C) address every token
        return logits, self._write_kv(kv, pages, offs, kn, vn)

    # ---- speculative draft-and-verify -------------------------------------

    def verify_paged(self, tokens, positions, block_tables, ctx_len,
                     pages, offs, drafts, n_drafts, sampling, pool):
        """One fused speculative verify tick against ``pool``, in place.

        tokens (B, K+1) int32 — lane b carries ``[last_emitted, d_1 ..
        d_K]`` (zero-padded past its draft count) at absolute positions
        ``ctx_len[b] .. ctx_len[b] + K``; drafts (B, K) int32 the proposed
        tokens; n_drafts (B,) int32 valid drafts per lane; pages/offs
        (B, K+1) physical addresses for every fed token's K/V (scratch
        for padding); ``sampling = (temps, top_ps, seeds, draws)`` per
        :func:`sample_tokens`.

        The dispatch runs the PREFILL trunk over the (B, K+1) chunk batch
        (``paged_gqa_verify`` — the chunked-prefill kernel as verifier),
        selects a token at every chunk position on device, counts each
        lane's longest accepted draft prefix, and scatters ALL fed
        tokens' K/V into the donated pool buffers (the engine rolls back
        the rejected tail via ``pool.truncate``).  Returns
        ``(sel (B, K+1) int32, n_acc (B,) int32, logits (B, K+1, V))`` —
        lane b emits ``sel[b, :n_acc[b] + 1]``.
        """
        if self.faults.rules:
            self.faults.check_dispatch()
        toks = np.asarray(tokens, np.int32)
        with self.tracer.span(
            "dispatch:verify_paged",
            lanes=toks.shape[0], width=toks.shape[1],
        ):
            args = self._place_tree((
                toks, np.asarray(positions, np.int32),
                np.asarray(block_tables, np.int32),
                np.asarray(ctx_len, np.int32),
                np.asarray(pages, np.int32), np.asarray(offs, np.int32),
                np.asarray(drafts, np.int32), np.asarray(n_drafts, np.int32),
                *self._np_sampling(sampling),
            ))
            sel, n_acc, logits, kv = self._fwd_verify(
                self.weights, *args, pool.arrays, self._all_greedy(sampling)
            )
            pool.arrays = kv
        if self.faults.rules:
            logits = _poison_lanes(logits, self.faults.nan_lanes())
        return sel, n_acc, logits

    @staticmethod
    def _accept(sel, drafts, n_drafts):
        """Longest accepted draft prefix per lane: draft i is accepted
        while every draft before it was and the device selection at its
        predicting position drew exactly it — so continuing the chunk is
        indistinguishable from sequential decode having emitted it."""
        K = drafts.shape[1]
        ok = (drafts == sel[:, :K]) & (
            jnp.arange(K, dtype=jnp.int32)[None] < n_drafts[:, None]
        )
        return jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)

    def _forward_verify(self, w, tokens, positions, block_tables, ctx_len,
                        pages, offs, drafts, n_drafts, temps, top_ps,
                        seeds, draws, kv, greedy_only=False):
        logits, kn, vn = self._prefill_trunk(
            w, tokens, positions, block_tables, ctx_len, kv, verify=True,
        )
        sel = sample_tokens(logits, temps, top_ps, seeds, draws, greedy_only)
        n_acc = self._accept(sel, drafts, n_drafts)
        return sel, n_acc, logits, self._write_kv(kv, pages, offs, kn, vn)

    def _block_prefill_paged(self, blk, x, positions, layer, kv,
                             block_tables, ctx_len, verify=False):
        cfg = self.cfg
        B, C, _ = x.shape
        h = L.norm_apply(blk["ln1"], x, cfg)
        q, k, v = self._qkv(blk, h, positions, kernel_proj=True)
        # verify over int8 pools: the chunk's attention view round-trips
        # through the page quantizer (what the pool will return for these
        # tokens once scattered), while the fp original rides along as the
        # DIAGONAL override (what one-token decode folds analytically for
        # the self position) — and is what gets scattered, quantized by
        # the pool exactly as one-token decode would
        rt = verify and kv[2] is not None
        ka, va = (_int8_roundtrip(k), _int8_roundtrip(v)) if rt else (k, v)
        ks, vs = (k, v) if rt else (None, None)
        o = self._paged_prefill_attention(
            q, ka, va, *kv, block_tables, ctx_len, layer=layer,
            verify=verify, k_self=ks, v_self=vs,
        )
        o = o.astype(x.dtype).reshape(B, C, cfg.q_dim)
        x = x + self._proj(blk, "attn.wo", o)
        return self._mlp(blk, x, kernel_proj=True), k, v

    def _paged_prefill_attention(self, q, k_new, v_new, pool_k, pool_v,
                                 k_scale, v_scale, block_tables, ctx_len,
                                 *, layer, verify=False, k_self=None,
                                 v_self=None):
        """One layer of chunk-batch prefill attention against the pool
        (``paged_gqa_verify`` — the same kernel — when the chunk is a
        speculative verify group; ``k/v_self`` is its int8-exactness
        diagonal override).  Distributed adapters override this with a
        ``shard_map`` over the model axis, mirroring
        :meth:`_paged_attention`."""
        op = paged_gqa_verify if verify else paged_gqa_prefill
        return op(
            q, k_new, v_new, pool_k, pool_v, block_tables, ctx_len,
            layer=layer, k_scale=k_scale, v_scale=v_scale,
            k_self=k_self, v_self=v_self,
            interpret=self.paged_interpret,
        )
