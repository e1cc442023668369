"""Prefill micro-benchmark: batched-paged prefill vs the B=1 gather-dense
loop, with and without prefix-cache hits, over fp and int8 pages.

    PYTHONPATH=src python benchmarks/prefill_microbench.py --smoke

Each scenario prefills the same workload — ``--requests`` prompts of
``--prompt-len`` tokens, chunk width ``--prefill-chunk`` — through a real
:class:`repro.serve.Engine` and times prefill-only wall clock (``--gen 1``
keeps decode negligible):

  * ``dense``  — the oracle path: one ``(1, C)`` chunk per request per
    tick, each re-gathering its whole allocated page window;
  * ``paged``  — one fused cross-request ``(B, C)`` dispatch per tick
    reading prior context in place from the pool;
  * ``paged+prefix`` — same, with the prefix cache on and every prompt
    sharing a ``--prefix-frac`` common header: after the first request
    seeds the cache, later admissions map the shared pages and skip the
    recompute entirely (``prefix_hit_tokens`` in the record).

The record lands in ``BENCH_prefill.json``.  CPU smoke-scale numbers:
trends are what matter, not absolutes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

sys.path.insert(0, "src")

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig


def _make_prompts(vocab: int, n: int, length: int, prefix_frac: float,
                  seed: int) -> np.ndarray:
    """n prompts of ``length`` sharing a common leading header of
    ``prefix_frac * length`` tokens (the system-prompt workload)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, int(length * prefix_frac))
    out = np.empty((n, length), np.int32)
    for i in range(n):
        tail = rng.integers(0, vocab, length - len(shared))
        out[i] = np.concatenate([shared, tail])
    return out


def run_workload(adapter, prompts, *, page_size, prefill_chunk, paged,
                 prefix_cache, kv_int8, reps, header=None) -> dict:
    """Time prefill of the whole prompt batch; returns medians + stats.

    With ``prefix_cache`` a seeder request carrying just the shared
    ``header`` runs first (outside the timer), so the measured batch hits
    a warm cache — the steady state of a system-prompt workload."""
    n, S = prompts.shape
    ecfg = EngineConfig(
        max_seq_len=S + 1,
        n_slots=n + 1,  # +1: the cache-seeder request
        page_size=page_size,
        token_budget=max(64, n * prefill_chunk),
        prefill_chunk=prefill_chunk,
        paged_decode=paged,
        paged_prefill=paged,
        prefix_cache=prefix_cache,
        kv_int8=kv_int8,
    )
    times, summary = [], {}
    for _ in range(reps + 1):  # first rep warms the jit caches
        engine = Engine(adapter, ecfg)
        if prefix_cache and header is not None and len(header):
            engine.submit(np.asarray(header), max_new=1)
            engine.run()
            engine.reset_stats()
        for p in prompts:
            engine.submit(np.asarray(p), max_new=1)
        t0 = time.perf_counter()
        engine.run()
        times.append(time.perf_counter() - t0)
        summary = engine.summary()
    return {
        "wall_ms": round(float(np.median(times[1:])) * 1e3, 2),
        "prefill_tokens": summary["prefill_tokens"],
        "prefix_hit_tokens": summary["prefix_hit_tokens"],
        "prefill_batch_size": summary["prefill_batch_size"],
        "cached_pages": summary["cached_pages"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prefix-frac", type=float, default=0.5,
                    help="fraction of every prompt that is a shared header")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_prefill.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    if not args.smoke:
        print("[prefill_microbench] full-scale arch on CPU is impractical; "
              "using the smoke config (pass --smoke to silence this)")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    adapter = CachedDecoder.from_model(model, params)

    rows = []
    for n in args.requests:
        prompts = _make_prompts(
            cfg.vocab, n, args.prompt_len, args.prefix_frac, args.seed
        )
        header = prompts[0, : int(args.prompt_len * args.prefix_frac)]
        for kv_int8 in (False, True):
            kw = dict(
                page_size=args.page_size, prefill_chunk=args.prefill_chunk,
                kv_int8=kv_int8, reps=args.reps,
            )
            dense = run_workload(
                adapter, prompts, paged=False, prefix_cache=False, **kw
            )
            paged = run_workload(
                adapter, prompts, paged=True, prefix_cache=False, **kw
            )
            prefix = run_workload(
                adapter, prompts, paged=True, prefix_cache=True,
                header=header, **kw
            )
            rows.append({
                "requests": n,
                "prompt_len": args.prompt_len,
                "kv_pages": "int8" if kv_int8 else "fp",
                "dense_ms": dense["wall_ms"],
                "paged_ms": paged["wall_ms"],
                "paged_prefix_ms": prefix["wall_ms"],
                "paged_speedup": round(
                    dense["wall_ms"] / max(paged["wall_ms"], 1e-9), 2
                ),
                "prefill_batch_size": paged["prefill_batch_size"],
                # with the cache warm, every later request's shared header
                # is mapped, not recomputed:
                "prefix_hit_tokens": prefix["prefix_hit_tokens"],
                "prefill_tokens_cold": paged["prefill_tokens"],
                "prefill_tokens_prefix": prefix["prefill_tokens"],
                "cached_pages": prefix["cached_pages"],
            })
            r = rows[-1]
            print(f"[prefill_microbench] B={n} {r['kv_pages']}: dense "
                  f"{r['dense_ms']}ms, paged {r['paged_ms']}ms "
                  f"(x{r['paged_speedup']}), +prefix {r['paged_prefix_ms']}ms "
                  f"({r['prefix_hit_tokens']} tokens skipped)")

    rec = {
        "arch": cfg.name,
        "backend": jax.default_backend(),
        "page_size": args.page_size,
        "prefill_chunk": args.prefill_chunk,
        "prefix_frac": args.prefix_frac,
        "sweep": rows,
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({k: v for k, v in rec.items() if k != "sweep"}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
