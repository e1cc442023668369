"""QuIP post-training quantization driver (paper Sec. 6 "Setup").

Quantization proceeds one transformer block at a time, exactly as the
paper does: (1) run calibration activations through the network quantized
SO FAR to the current block, (2) accumulate per-layer proxy Hessians
H = E[x x^T] at each linear's true input, (3) QuIP-quantize every linear
in the block, (4) the quantized block produces the inputs for the next.

This driver operates on smoke-scale dense models end-to-end on CPU (the
per-layer math is size-agnostic; at cluster scale the same schedule runs
layer-parallel over the model axis — DESIGN.md §3).

Hessian accumulation is **streaming**: each block's forward runs one
calibration segment at a time (batched matmuls are not batch-size-
invariant on every backend, jax 0.9.0's CPU included, so a batched
forward would make H depend on the grouping), and ``--calib-chunk``
segments' activations are held and folded through
``HessianAccumulator.update`` together, so per-block activation memory
is O(chunk · seg_len · d_ff) instead of O(batch · seg_len · d_ff).  The
accumulator's fixed per-segment fold then makes H bit-identical for
every chunk size, including the one-shot path (``--calib-chunk 0``;
tests/test_drivers.py pins it).  The price is B batch-1 forwards per
block instead of B/chunk batched ones; not measured.

    PYTHONPATH=src python -m repro.launch.quantize --arch qwen3-14b --smoke \
        --bits 2 --method ldlq
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.quantizer import QuipConfig, QuantizedLinear, quantize_layer
from repro.data import make_calibration
from repro.models import build_model
from repro.models import layers as L

__all__ = ["quantize_dense_model", "QuantizedModel", "main"]

# the per-block linears we quantize for the dense family, with the params
# path and the activation tap that feeds each one
_DENSE_LINEARS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo", "mlp.wi", "mlp.wg", "mlp.wo")


@dataclasses.dataclass
class QuantizedModel:
    """Dense decoder with every block linear replaced by a QuantizedLinear."""

    cfg: object
    embed: dict
    final_norm: dict
    blocks: list  # per layer: dict name -> QuantizedLinear, plus norms
    stats: list

    def forward_hidden(self, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = L.embed(self.embed, tokens)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        for blk in self.blocks:
            x = _quantized_block_forward(blk, x, cfg, positions)
        return L.norm_apply(self.final_norm, x, cfg)

    def logits(self, tokens: jax.Array) -> jax.Array:
        return L.lm_logits(self.embed, self.forward_hidden(tokens))

    def reference(self) -> "QuantizedModel":
        """This model with every linear on the jnp dequantize-and-matmul
        path: an oracle that never runs the Pallas kernel it checks."""
        blocks = [
            {name: dataclasses.replace(v, use_kernel=False)
             if isinstance(v, QuantizedLinear) else v
             for name, v in blk.items()}
            for blk in self.blocks
        ]
        return dataclasses.replace(self, blocks=blocks)

    def cached_decoder(self):
        """KV-cached prefill/decode path (repro.serve) through the packed
        D^-1 -> V -> quant_matmul -> U^T pipeline — the serving-time
        replacement for per-token ``logits`` recompute."""
        from repro.serve.adapter import CachedDecoder

        return CachedDecoder.from_quantized(self)


def _attn_forward_with_linears(blk, h, cfg, positions):
    """attention_full but routed through QuantizedLinear projections."""
    B, S, _ = h.shape
    q = blk["attn.wq"](h).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = blk["attn.wk"](h).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = blk["attn.wv"](h).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, blk["k_norm"], cfg.norm_eps)
    from repro.models.layers import rope, _gqa_scores, _gqa_out

    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    s = _gqa_scores(q, k, cfg)
    m = positions[:, None] >= positions[None, :]
    s = jnp.where(m[None, None, None], s, -1e30)
    o = _gqa_out(jax.nn.softmax(s, axis=-1), v, cfg)
    o = o.astype(h.dtype).reshape(B, S, cfg.q_dim)
    return blk["attn.wo"](o)


def _quantized_block_forward(blk, x, cfg, positions):
    h = L.norm_apply(blk["ln1"], x, cfg)
    x = x + _attn_forward_with_linears(blk, h, cfg, positions)
    h = L.norm_apply(blk["ln2"], x, cfg)
    up = blk["mlp.wi"](h)
    if cfg.mlp == "swiglu":
        up = jax.nn.silu(up) * blk["mlp.wg"](h)
    else:
        up = jax.nn.gelu(up)
    return x + blk["mlp.wo"](up)


def _block_taps(lp, x, cfg, positions):
    """Run one fp block, returning the activation at each linear's input."""
    taps = {}
    h = L.norm_apply(lp["ln1"], x, cfg)
    taps["attn.wq"] = taps["attn.wk"] = taps["attn.wv"] = h
    a, (k, v) = L.attention_full(
        lp["attn"], h, cfg, positions=positions, causal=True, return_kv=True
    )
    # reconstruct the wo input (pre-projection attention output)
    # cheaper: recompute inside attention; here we tap via a second pass
    q = h @ lp["attn"]["wq"]
    B, S, _ = h.shape
    qh = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        qh = L.rms_norm(qh, lp["attn"]["q_norm"], cfg.norm_eps)
    from repro.models.layers import rope, _gqa_scores, _gqa_out

    qh = rope(qh, positions, cfg.rope_theta)
    s = _gqa_scores(qh, k, cfg)
    m = positions[:, None] >= positions[None, :]
    s = jnp.where(m[None, None, None], s, -1e30)
    o = _gqa_out(jax.nn.softmax(s, -1), v, cfg).astype(h.dtype)
    taps["attn.wo"] = o.reshape(B, S, cfg.q_dim)
    x = x + a
    h2 = L.norm_apply(lp["ln2"], x, cfg)
    taps["mlp.wi"] = taps["mlp.wg"] = h2
    up = h2 @ lp["mlp"]["wi"]
    if cfg.mlp == "swiglu":
        up = jax.nn.silu(up) * (h2 @ lp["mlp"]["wg"])
    else:
        up = jax.nn.gelu(up)
    taps["mlp.wo"] = up
    x = x + up @ lp["mlp"]["wo"]
    return x, taps


def _get_path(tree, path):
    for p in path.split("."):
        tree = tree[p]
    return tree


def _block_linears(cfg) -> tuple[str, ...]:
    return tuple(
        n for n in _DENSE_LINEARS if n != "mlp.wg" or cfg.mlp == "swiglu"
    )


def block_hessians(
    lp, x: jax.Array, cfg, positions: jax.Array, *, chunk: int = 0
) -> dict[str, jax.Array]:
    """Per-linear proxy Hessians for one block, streaming over segments.

    ``x`` (B, S, d) is the calibration activation entering the block;
    the forward runs one segment at a time, and activations at each
    linear's input are held only ``chunk`` segments at a time
    (``chunk <= 0``: the whole batch at once — the one-shot path).  Each
    segment is folded through
    ``HessianAccumulator.update`` individually, so the result is
    bit-identical for every chunk size.
    """
    from repro.core.hessian import HessianAccumulator

    B = x.shape[0]
    chunk = B if chunk <= 0 else min(chunk, B)
    accs: dict[str, HessianAccumulator] = {}
    for i0 in range(0, B, chunk):
        # the forward runs one segment at a time: batched matmuls are not
        # batch-size-invariant on every backend, and H must not depend on
        # how the batch was chunked
        seg_taps = [_block_taps(lp, x[j : j + 1], cfg, positions)[1]
                    for j in range(i0, min(i0 + chunk, B))]
        for name in _block_linears(cfg):
            X = jnp.concatenate([t[name] for t in seg_taps]).astype(
                jnp.float32)
            acc = accs.get(name) or HessianAccumulator.create(X.shape[-1])
            accs[name] = acc.update_segments(X)
    return {name: acc.finalize() for name, acc in accs.items()}


def quantize_dense_model(
    params,
    cfg,
    qcfg: QuipConfig,
    calib_tokens: jax.Array,
    *,
    seed: int = 0,
    verbose: bool = True,
    calib_chunk: int = 8,
) -> QuantizedModel:
    """Block-by-block QuIP over a dense decoder (params from Model.init).

    ``calib_chunk``: calibration segments materialized at once per block
    (streaming Hessians; <= 0 keeps the whole batch resident — the
    one-shot path, bit-identical to any chunking).
    """
    from repro.models.transformer import unstack_layers

    n_layers = cfg.n_layers
    layer_params = unstack_layers(params)
    B = calib_tokens.shape[0]
    chunk = B if calib_chunk <= 0 else min(calib_chunk, B)
    positions = jnp.arange(calib_tokens.shape[1], dtype=jnp.int32)
    x = L.embed(params["embed"], calib_tokens)

    blocks = []
    all_stats = []
    for i, lp in enumerate(layer_params):
        t0 = time.time()
        # Hessians from the quantized-prefix activations (paper: H from the
        # quantized transformer up to this point), streamed chunk by chunk
        hessians = block_hessians(lp, x, cfg, positions, chunk=chunk)
        blk = {
            "ln1": lp["ln1"],
            "ln2": lp["ln2"],
        }
        if cfg.qk_norm:
            blk["q_norm"] = lp["attn"]["q_norm"]
            blk["k_norm"] = lp["attn"]["k_norm"]
        stats_blk = {}
        for name in _block_linears(cfg):
            W = _get_path(lp, name).T  # stored (in, out) -> quantize (out, in)
            # per-layer seed from the STABLE linear index — hash(name) varies
            # with PYTHONHASHSEED across processes, which would make saved
            # artifacts irreproducible (their transforms regenerate by seed)
            layer, st = quantize_layer(
                W, hessians[name], qcfg,
                seed=seed * 1000 + i * 10 + _DENSE_LINEARS.index(name),
            )
            blk[name] = layer
            stats_blk[name] = st
        blocks.append(blk)
        all_stats.append(stats_blk)
        # advance calibration activations through the QUANTIZED block, in
        # the same segment chunks (never the full batch's d_ff activations)
        x = jnp.concatenate([
            _quantized_block_forward(blk, x[i0 : i0 + chunk], cfg, positions)
            for i0 in range(0, B, chunk)
        ])
        if verbose:
            mean_proxy = float(
                np.mean([s["proxy_loss"] for s in stats_blk.values()])
            )
            print(
                f"[quantize] block {i}/{n_layers} proxy={mean_proxy:.4g} "
                f"({time.time()-t0:.1f}s)"
            )
    return QuantizedModel(
        cfg=cfg,
        embed=params["embed"],
        final_norm=params["final_norm"],
        blocks=blocks,
        stats=all_stats,
    )


def perplexity(logits_fn, tokens: jax.Array, batch: int = 8) -> float:
    """Next-token perplexity of a logits(tokens) function."""
    tot, cnt = 0.0, 0
    for i in range(0, tokens.shape[0], batch):
        tb = tokens[i : i + batch]
        logits = logits_fn(tb[:, :-1]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, tb[:, 1:, None], -1)[..., 0]
        tot += float(jnp.sum(nll))
        cnt += nll.size
    return float(np.exp(tot / cnt))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--method", default="ldlq")
    ap.add_argument("--no-incoherence", action="store_true")
    ap.add_argument("--transform", default="kronecker",
                    choices=["kronecker", "hadamard", "none"])
    ap.add_argument("--calib-segments", type=int, default=16)
    ap.add_argument("--calib-len", type=int, default=128)
    ap.add_argument("--calib-chunk", type=int, default=8,
                    help="calibration segments whose activations are held "
                         "and folded into H together per block (the "
                         "forward itself runs one segment at a time; 0 = "
                         "whole batch — bit-identical either way)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--out-dir", default=None,
                    help="persist the quantized model as a serving artifact "
                         "(packed ints + scales + transform seeds); serve "
                         "with launch/serve.py --load-quantized <dir>")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family not in ("dense",):
        raise SystemExit(
            "quantize driver drives the dense family end-to-end; "
            "per-layer quantization for other families goes through "
            "repro.core.quantize_layer directly"
        )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    calib = make_calibration(
        cfg.vocab, n_segments=args.calib_segments, seg_len=args.calib_len,
        seed=args.seed + 7,
    )
    qcfg = QuipConfig(
        bits=args.bits,
        method=args.method,
        incoherence=not args.no_incoherence,
        transform=args.transform,
        use_kernel=False,
    )
    qm = quantize_dense_model(params, cfg, qcfg, calib.tokens, seed=args.seed,
                              calib_chunk=args.calib_chunk)

    if args.out_dir:
        from repro.serve.artifacts import save_quantized
        from repro.serve.quality import build_quality_section

        # the quality section ships INSIDE the manifest, next to the
        # shard digests: the audit describes exactly the weights it
        # travels with (render with launch/quality_report.py)
        quality = build_quality_section(qm.stats)
        path = save_quantized(
            args.out_dir, qm, qcfg,
            extra_meta={"stats": qm.stats, "smoke": args.smoke,
                        "seed": args.seed, "quality": quality},
        )
        agg = quality["aggregate"]
        print(f"[quantize] artifact saved to {path}")
        if agg:
            print(
                f"[quantize] quality: layers={agg['n_layers']} "
                f"total_proxy={agg['total_proxy_loss']:.4g} "
                f"max_proxy_rel={agg['max_proxy_rel']:.4g} "
                f"max_mu_w_post={agg['max_mu_w_post']:.3g} "
                f"max_h_cond={agg['max_h_cond']:.3g}"
            )

    eval_tokens = make_calibration(
        cfg.vocab, n_segments=8, seg_len=args.calib_len, seed=args.seed + 99
    ).tokens
    ppl_fp = perplexity(
        lambda t: model.logits(params, model.forward(params, {"tokens": t})[0]),
        eval_tokens,
    )
    ppl_q = perplexity(qm.logits, eval_tokens)
    rec = {
        "arch": cfg.name, "bits": args.bits, "method": qcfg.label(),
        "ppl_fp16": ppl_fp, "ppl_quant": ppl_q,
        "mean_proxy": float(np.mean([
            s["proxy_loss"] for blk in qm.stats for s in blk.values()
        ])),
    }
    print(json.dumps(rec, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
