"""Decode-step micro-benchmark: paged in-place attention vs the gather-dense
oracle vs the quantized XLA-unpack fallback.

    PYTHONPATH=src python benchmarks/decode_microbench.py --smoke

Three sweeps, emitted as ``BENCH_decode.json``:

  * ``sweep_alloc`` — fixed context, growing per-sequence page *allocation*
    (``max_pages_per_seq``).  The gather-dense path copies the whole
    allocated window ``(L, B, Pmax*ps, KV, hd)`` every step, so its step
    time grows with allocation; the paged path buckets its block table to
    the attended prefix and must stay ~flat — "no per-step full-context
    copy": step time sublinear in allocated-but-unused pages.
  * ``sweep_ctx`` — fixed allocation, growing live context: both paths grow,
    paged from a far lower intercept.
  * ``quant_matvec`` — the QuantizedLinear decode matvec through the
    ``quant_matmul`` kernel dispatch (Pallas on TPU, jnp oracle here) vs
    the XLA unpack fallback that materializes the dequantized matrix.

CPU smoke-scale numbers: trends are what matter, not absolutes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.configs import get_smoke_config
from repro.serve import CachedDecoder, PagedKVPool
from repro.serve.kv_cache import page_bucket, pages_needed


def _time(fn, reps: int) -> float:
    fn()  # warm (jit compile)
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e3  # ms


def bench_step(adapter, cfg, *, ctx: int, alloc_pages: int, page_size: int,
               reps: int) -> dict:
    """One decode lane with ``ctx`` live tokens in an ``alloc_pages``-page
    allocation; time the gather-dense step vs the paged in-place step."""
    pool = PagedKVPool(
        cfg, n_pages=alloc_pages + 2, page_size=page_size, n_slots=1,
        max_pages_per_seq=alloc_pages,
    )
    slot = pool.admit(ctx)
    assert slot is not None
    assert pool.extend(slot, ctx + 1)  # page for the decoded token
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    kv = jax.random.normal(
        jax.random.PRNGKey(0), (L, ctx, KV, hd), pool.k.dtype
    )
    pool.write_span(slot, 0, ctx, kv, kv)

    tokens = np.ones((1, 1), np.int32)
    positions = np.full((1, 1), ctx, np.int32)
    ctx_len = np.full((1,), ctx, np.int32)

    def dense_step():
        ctx_k, ctx_v = pool.gather([slot])
        logits, k_new, v_new = adapter(
            jnp.asarray(tokens), jnp.asarray(positions), ctx_k, ctx_v,
            jnp.asarray(ctx_len),
        )
        # mirror the engine: scatter the new token back into the pool
        pool.write([slot], [ctx], k_new[:, :, 0], v_new[:, :, 0])
        pool._slots[slot].length = ctx  # keep the step stationary
        return logits.block_until_ready()

    bt = pool.block_table([slot])
    nb = page_bucket(pages_needed(ctx, page_size), alloc_pages)
    pages, offs = pool.addresses([slot], [ctx])

    def paged_step():
        logits = adapter.decode_paged(
            tokens, positions, bt[:, :nb], ctx_len, pages, offs, pool
        )
        return logits.block_until_ready()

    return {
        "ctx": ctx,
        "alloc_pages": alloc_pages,
        "attended_pages": nb,
        "dense_ms": round(_time(dense_step, reps), 3),
        "paged_ms": round(_time(paged_step, reps), 3),
    }


def bench_quant_matvec(reps: int, *, m: int = 256, n: int = 256,
                       seed: int = 0) -> list[dict]:
    """QuantizedLinear decode matvec: XLA unpack fallback vs the
    quant_matmul kernel dispatch (jnp oracle off-TPU, Pallas on TPU)."""
    from repro.core.quantizer import QuipConfig, quantize_layer

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    W = 0.02 * jax.random.normal(k1, (m, n))
    X = jax.random.normal(k2, (1024, n))
    H = X.T @ X / X.shape[0] + 1e-3 * jnp.eye(n)
    layer, _ = quantize_layer(
        W, H, QuipConfig(bits=2, method="ldlq"), seed=seed,
        collect_stats=False,
    )
    rows = []
    for B in (1, 8, 32):
        x = jax.random.normal(jax.random.PRNGKey(B), (B, n))
        fall = jax.jit(lambda z: layer(z, use_kernel=False))
        kern = jax.jit(lambda z: layer(z, use_kernel=True))
        rows.append({
            "batch": B, "m": m, "n": n, "bits": 2,
            "xla_unpack_ms": round(
                _time(lambda: fall(x).block_until_ready(), reps), 4
            ),
            "quant_matmul_ms": round(
                _time(lambda: kern(x).block_until_ready(), reps), 4
            ),
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=32,
                    help="live context tokens for the allocation sweep")
    ap.add_argument("--alloc-sweep", type=int, nargs="+",
                    default=[4, 32, 256, 1024])
    ap.add_argument("--ctx-sweep", type=int, nargs="+",
                    default=[16, 64, 256, 1024])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_decode.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    from repro.models import build_model

    cfg = get_smoke_config(args.arch)
    if not args.smoke:
        print("[decode_microbench] full-scale arch on CPU is impractical; "
              "using the smoke config (pass --smoke to silence this)")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    adapter = CachedDecoder.from_model(model, params)

    need = pages_needed(args.ctx + 1, args.page_size)
    allocs = [a for a in args.alloc_sweep if a >= need]
    if not allocs:
        raise SystemExit(
            f"--ctx {args.ctx} needs {need} pages of {args.page_size}; "
            f"every --alloc-sweep value {args.alloc_sweep} is smaller"
        )
    if allocs != args.alloc_sweep:
        print(f"[decode_microbench] dropping allocations < {need} pages "
              f"(ctx {args.ctx} + 1 decoded token @ {args.page_size}/page)")
    sweep_alloc = [
        bench_step(adapter, cfg, ctx=args.ctx, alloc_pages=a,
                   page_size=args.page_size, reps=args.reps)
        for a in allocs
    ]
    max_ctx = max(args.ctx_sweep)
    alloc = max(2, pages_needed(max_ctx + 1, args.page_size))
    sweep_ctx = [
        bench_step(adapter, cfg, ctx=c, alloc_pages=alloc,
                   page_size=args.page_size, reps=args.reps)
        for c in args.ctx_sweep
    ]
    quant = bench_quant_matvec(args.reps, seed=args.seed)

    lo, hi = sweep_alloc[0], sweep_alloc[-1]
    rec = {
        "arch": cfg.name,
        "backend": jax.default_backend(),
        "page_size": args.page_size,
        "sweep_alloc": sweep_alloc,
        "sweep_ctx": sweep_ctx,
        "quant_matvec": quant,
        # allocation grew hi/lo x with context fixed; how did step time move?
        "alloc_growth": {
            "pages_x": round(hi["alloc_pages"] / lo["alloc_pages"], 1),
            "dense_time_x": round(hi["dense_ms"] / max(lo["dense_ms"], 1e-9), 2),
            "paged_time_x": round(hi["paged_ms"] / max(lo["paged_ms"], 1e-9), 2),
        },
    }
    print(json.dumps(rec, indent=1))
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    g = rec["alloc_growth"]
    print(
        f"[decode_microbench] allocation x{g['pages_x']}: dense step time "
        f"x{g['dense_time_x']}, paged step time x{g['paged_time_x']} "
        f"(paged must stay ~flat: no per-step full-allocation copy)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
