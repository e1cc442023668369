"""Tables 1/2 analogue: quantization-method x bits quality grid.

Trains the small bench LM, then quantizes it block-by-block with every
(method x processing x bits) combination and reports held-out perplexity.
The paper's claims to reproduce:
  * 2-bit baseline processing collapses; 2-bit IncP stays viable ("step
    function change"), for EVERY rounding method incl. nearest;
  * LDLQ(+IncP) = QuIP beats Near(+IncP);
  * 4-bit is close to fp16 for everything.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.quantizer import QuipConfig
from repro.data import make_calibration
from repro.launch.quantize import perplexity, quantize_dense_model

from benchmarks.common import emit, eval_ppl, trained_lm


def run(args) -> dict:
    cfg, model, params = trained_lm(steps=args.train_steps)
    calib = make_calibration(cfg.vocab, n_segments=args.calib_segments,
                             seg_len=args.calib_len, seed=7)
    eval_toks = make_calibration(cfg.vocab, n_segments=8, seg_len=128,
                                 seed=99).tokens

    ppl_fp = perplexity(
        lambda t: model.logits(params, model.forward(params, {"tokens": t})[0]),
        eval_toks,
    )
    results = {"fp16": ppl_fp}
    methods = ["near", "ldlq"] if args.quick else ["near", "ldlq", "ldlq_rg", "greedy"]
    bits_list = [2] if args.quick else [4, 3, 2]
    for method in methods:
        for incp in (False, True):
            for bits in bits_list:
                t0 = time.time()
                qcfg = QuipConfig(
                    bits=bits, method=method, incoherence=incp,
                    greedy_passes=3, use_kernel=False,
                )
                qm = quantize_dense_model(
                    params, cfg, qcfg, calib.tokens, verbose=False
                )
                ppl = perplexity(qm.logits, eval_toks)
                key = f"{method}{'+incp' if incp else ''}@{bits}b"
                results[key] = ppl
                emit(f"quality_grid/{key}", (time.time() - t0) * 1e6,
                     f"ppl={ppl:.2f}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--calib-segments", type=int, default=16)
    ap.add_argument("--calib-len", type=int, default=128)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="experiments/quality_grid.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    results = run(args)
    print(json.dumps(results, indent=1))
    if args.out:
        import pathlib

        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
