"""Tensor-parallel serving scaling benchmark (serve/distributed.py).

    PYTHONPATH=src python benchmarks/serving_tp.py --smoke

Forces a multi-device CPU host (XLA_FLAGS, set before jax imports), then
serves the same paged-decode workload through the engine at each model-
axis width in ``--mp-list``: mp=1 is the single-device baseline, wider
meshes shard the packed quantized weights (column/row-parallel), the KV
page pool (over KV heads), and the paged-attention dispatch (shard_map).
Per config it reports throughput, per-device vs total page-pool bytes
(the pool memory win: device_bytes ≈ total/mp), and token parity with
the mp=1 baseline — the record lands in ``BENCH_tp.json`` so the
distributed path's correctness AND its memory scaling are visible
PR-over-PR.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# must precede any jax import: fake a multi-device host for the mesh
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core.quantizer import QuipConfig  # noqa: E402
from repro.data import make_calibration  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import (  # noqa: E402
    CachedDecoder,
    DistributedCachedDecoder,
    Engine,
    EngineConfig,
    make_serving_mesh,
)


def run_workload(adapter, prompts, args):
    engine = Engine(adapter, EngineConfig(
        max_seq_len=args.prompt_len + args.gen,
        n_slots=args.slots,
        page_size=args.page_size,
        token_budget=args.token_budget,
        prefill_chunk=args.prefill_chunk,
        paged_decode=True,
        kv_int8=args.kv_int8,
    ))
    # warm the jit caches; compile time stays out of the measured run
    warm = engine.submit(np.asarray(prompts[0]), max_new=2)
    engine.run()
    assert warm.done
    for i in range(args.requests):
        engine.submit(np.asarray(prompts[i]), max_new=args.gen)
    engine.reset_clock()
    engine.reset_stats()
    t0 = time.time()
    done = engine.run()
    wall = time.time() - t0
    toks = [
        np.asarray(r.out_tokens, np.int32)
        for r in sorted(done, key=lambda r: r.rid)
    ]
    total = sum(len(t) for t in toks)
    return {
        "wall_s": round(wall, 3),
        "tok_s": round(total / wall, 2),
        "pool_total_bytes": engine.pool.total_bytes(),
        "pool_device_bytes": engine.pool.device_bytes(),
    }, toks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mp-list", default="1,2",
                    help="model-axis widths to sweep (comma-separated); "
                         "widths the arch's KV heads cannot divide fall "
                         "back to a replicated pool")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--fp", action="store_true",
                    help="serve fp weights instead of QuIP-quantized")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_tp.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    if not args.smoke:
        print("[serving_tp] full-scale arch on CPU is impractical; "
              "using the smoke config (pass --smoke to silence this)")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.fp:
        qm, label = None, "fp"
    else:
        from repro.launch.quantize import quantize_dense_model

        calib = make_calibration(cfg.vocab, n_segments=8, seg_len=64,
                                 seed=args.seed + 7)
        qm = quantize_dense_model(
            params, cfg,
            QuipConfig(bits=args.bits, method="ldlq", use_kernel=False),
            calib.tokens, seed=args.seed, verbose=False,
        )
        label = f"quip-{args.bits}b"
    prompts = make_calibration(
        cfg.vocab, n_segments=args.requests, seg_len=args.prompt_len,
        seed=args.seed + 3,
    ).tokens

    mp_list = [int(x) for x in args.mp_list.split(",")]
    if 1 not in mp_list:
        # parity is defined against the single-device engine; always
        # measure that baseline even if the sweep didn't ask for it
        mp_list = [1] + mp_list
    configs = []
    base_toks = None
    for mp in sorted(set(mp_list)):
        if mp > jax.device_count():
            print(f"[serving_tp] skip mp={mp}: only "
                  f"{jax.device_count()} devices")
            continue
        if mp == 1:
            adapter = (CachedDecoder.from_model(model, params) if args.fp
                       else CachedDecoder.from_quantized(qm))
        else:
            mesh = make_serving_mesh(1, mp)
            adapter = (
                DistributedCachedDecoder.from_model(model, params, mesh=mesh)
                if args.fp
                else DistributedCachedDecoder.from_quantized(qm, mesh=mesh)
            )
        rec, toks = run_workload(adapter, prompts, args)
        if mp == 1:  # the single-device baseline every width compares to
            base_toks = toks
        match = all(
            np.array_equal(a, b) for a, b in zip(base_toks, toks)
        )
        rec.update(
            mp=mp,
            pool_device_frac=round(
                rec["pool_device_bytes"] / rec["pool_total_bytes"], 4
            ),
            tokens_match_mp1=bool(match),
        )
        configs.append(rec)
        print(f"[serving_tp] mp={mp}: {rec['tok_s']} tok/s, pool "
              f"{rec['pool_device_bytes']}/{rec['pool_total_bytes']} B/device "
              f"({rec['pool_device_frac']:.0%}), parity={match}")

    record = {
        "label": label,
        "arch": cfg.name,
        "kv_pages": "int8" if args.kv_int8 else "fp",
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "devices": jax.device_count(),
        "configs": configs,
    }
    print(json.dumps(record, indent=1))
    if not configs:
        print("[serving_tp] FAIL: no config ran (every --mp-list width "
              "was skipped) — nothing measured, not writing a record")
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f)
    if not all(c["tokens_match_mp1"] for c in configs):
        print("[serving_tp] FAIL: TP token stream diverged from mp=1")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
