"""Speculative-decode microbenchmark: draft-and-verify vs one-token decode.

    PYTHONPATH=src python benchmarks/speculative_microbench.py --smoke

Measures steady-state decode throughput of the paged engine on a
REPETITIVE workload — the regime speculative decode targets — and on a
RANDOM workload (the adversarial floor: near-zero acceptance, so the
record shows what failed speculation costs).  Because which cycle a
random-init model falls into depends on the prompt, the repetitive
workload is CHOSEN in-process: a handful of candidate repeated-pattern
prompts are probed (one cheap unmeasured engine run each, which also
warms the jit caches) and the highest-acceptance candidate is measured.
Three configurations per workload:

  * ``K=0``  — the PR-2 one-token paged decode path (the baseline);
  * ``K=2`` / ``K=4`` — speculative draft-and-verify: one fused (B, K+1)
    dispatch per tick (the chunked-prefill kernel as verifier), rejected
    drafts rolled back via ``PagedKVPool.truncate``.

All configurations emit token-identical greedy streams (asserted against
the K=0 run), so the speedup column is a pure scheduling win: tokens per
second scale with tokens-per-verify-tick as long as the (B, K+1) verify
dispatch costs about the same as the (B, 1) decode dispatch — which is
the memory-bound regime QuIP's 2-bit weights put decode in.  The record
goes to ``BENCH_speculative.json`` so the gain is tracked PR-over-PR.
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
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig


def pattern_prompts(pat, n: int, prompt_len: int) -> np.ndarray:
    pat = np.asarray(pat, np.int32)
    reps = -(-prompt_len // len(pat))
    return np.tile(np.tile(pat, reps)[:prompt_len], (n, 1))


def make_engine(adapter, spec_k: int, ecfg_kw: dict) -> Engine:
    return Engine(adapter, EngineConfig(
        speculative_k=spec_k, device_sample=True, **ecfg_kw
    ))


def probe_tplt(adapter, prompts, gen: int, spec_k: int, ecfg_kw) -> float:
    """Unmeasured run returning tokens-per-lane-tick (also warms jits)."""
    engine = make_engine(adapter, spec_k, ecfg_kw)
    for p in prompts:
        engine.submit(np.asarray(p), max_new=gen)
    engine.run()
    return engine.summary()["tokens_per_lane_tick"]


def run_engine(adapter, prompts, gen: int, spec_k: int, ecfg_kw: dict):
    engine = make_engine(adapter, spec_k, ecfg_kw)
    # full warm pass over the same workload: every bucket shape this run
    # will hit compiles here, so the measured run is pure steady state
    for p in prompts:
        engine.submit(np.asarray(p), max_new=gen)
    engine.run()
    reqs = [engine.submit(np.asarray(p), max_new=gen) for p in prompts]
    engine.reset_stats()
    t0 = time.perf_counter()
    engine.run()
    wall = time.perf_counter() - t0
    s = engine.summary()
    toks = [np.asarray(r.out_tokens) for r in reqs]
    return {
        "wall_s": round(wall, 3),
        "decode_tok_s": round(s["decode_tokens"] / wall, 2),
        "decode_tokens": s["decode_tokens"],
        "spec_ticks": s["spec_ticks"],
        "acceptance_rate": round(s["acceptance_rate"], 3),
        "accepted_per_tick": round(s["accepted_per_tick"], 3),
        "tokens_per_lane_tick": round(s["tokens_per_lane_tick"], 3),
        "rolled_back_tokens": s["rolled_back_tokens"],
    }, toks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=128)
    ap.add_argument("--spec-k", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--candidates", type=int, default=10,
                    help="repeated-pattern prompts probed to find the "
                         "high-acceptance workload")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_speculative.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    if not args.smoke:
        print("[speculative_microbench] full-scale arch on CPU is "
              "impractical; using the smoke config")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    adapter = CachedDecoder.from_model(model, params)
    ecfg_kw = dict(
        max_seq_len=args.prompt_len + args.gen,
        n_slots=args.requests,
        page_size=args.page_size,
        token_budget=max(64, args.requests * 8),
        prefill_chunk=32,
        paged_decode=True,
        kv_int8=args.kv_int8,
        draft_ngram=6,
    )

    # choose the repetitive workload: probe candidate patterns, keep the
    # one the model answers most cyclically (highest acceptance)
    rng = np.random.default_rng(args.seed + 7)
    best_tplt, best_pat = -1.0, None
    for _ in range(args.candidates):
        pat = rng.integers(0, cfg.vocab, rng.choice([2, 3, 4]))
        prompts = pattern_prompts(pat, args.requests, args.prompt_len)
        tplt = probe_tplt(adapter, prompts, args.gen, min(args.spec_k),
                          ecfg_kw)
        if tplt > best_tplt:
            best_tplt, best_pat = tplt, [int(t) for t in pat]
    print(f"[speculative_microbench] chosen repetitive pattern {best_pat} "
          f"(probe tokens/lane-tick {best_tplt:.2f})")

    workloads = {
        "repetitive": pattern_prompts(
            best_pat, args.requests, args.prompt_len
        ),
        "random": np.asarray(make_calibration(
            cfg.vocab, n_segments=args.requests, seg_len=args.prompt_len,
            seed=args.seed + 3,
        ).tokens),
    }
    record = {
        "arch": cfg.name,
        "kv_pages": "int8" if args.kv_int8 else "fp",
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "repetitive_pattern": best_pat,
        "workloads": {},
    }
    for kind, prompts in workloads.items():
        base, base_toks = run_engine(adapter, prompts, args.gen, 0, ecfg_kw)
        rows = {"K0": base}
        for k in args.spec_k:
            row, toks = run_engine(adapter, prompts, args.gen, k, ecfg_kw)
            # speculative greedy decode must be token-identical to the
            # one-token path — a speedup that changes tokens is a bug
            for a, b in zip(base_toks, toks):
                np.testing.assert_array_equal(a, b)
            row["speedup_vs_K0"] = round(
                row["decode_tok_s"] / base["decode_tok_s"], 2
            )
            rows[f"K{k}"] = row
        record["workloads"][kind] = rows
        print(f"[speculative_microbench] {kind}: baseline "
              f"{base['decode_tok_s']} tok/s")
        for k in args.spec_k:
            r = rows[f"K{k}"]
            print(f"  K={k}: {r['decode_tok_s']} tok/s "
                  f"({r['speedup_vs_K0']}x), acceptance "
                  f"{r['acceptance_rate']}, {r['tokens_per_lane_tick']} "
                  f"tok/lane-tick, rolled_back {r['rolled_back_tokens']}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
