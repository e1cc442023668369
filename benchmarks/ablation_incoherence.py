"""Tables 3 & 5 analogue: IncP sub-step ablation + random-permutation ablation.

Table 3: rescale / incoherence / quant-range sub-steps each contribute.
Table 5: the random permutation inside the fast orthogonal multiply helps.
Metric: held-out perplexity of the quantized bench LM (2 and 3 bits).
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.quantizer import QuipConfig
from repro.data import make_calibration
from repro.launch.quantize import perplexity, quantize_dense_model

from benchmarks.common import emit, trained_lm

VARIANTS = {
    # (incoherence, rescale, spectrum_range, permute)
    "rescale_only":        dict(incoherence=False, rescale=True,  spectrum_range=False, permute=False),
    "incoherence_only":    dict(incoherence=True,  rescale=False, spectrum_range=False, permute=True),
    "rescale+incoherence": dict(incoherence=True,  rescale=True,  spectrum_range=False, permute=True),
    "full_incp":           dict(incoherence=True,  rescale=True,  spectrum_range=True,  permute=True),
    "full_no_permute":     dict(incoherence=True,  rescale=True,  spectrum_range=True,  permute=False),
}


def run(args) -> dict:
    cfg, model, params = trained_lm(steps=args.train_steps)
    calib = make_calibration(cfg.vocab, n_segments=16, seg_len=128, seed=7)
    eval_toks = make_calibration(cfg.vocab, n_segments=8, seg_len=128,
                                 seed=99).tokens
    results = {}
    bits_list = [2] if args.quick else [3, 2]
    for bits in bits_list:
        for name, kw in VARIANTS.items():
            t0 = time.time()
            qcfg = QuipConfig(bits=bits, method="ldlq", use_kernel=False, **kw)
            qm = quantize_dense_model(params, cfg, qcfg, calib.tokens,
                                      verbose=False)
            ppl = perplexity(qm.logits, eval_toks)
            results[f"{name}@{bits}b"] = ppl
            emit(f"ablation_incp/{name}@{bits}b", (time.time() - t0) * 1e6,
                 f"ppl={ppl:.2f}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="experiments/ablation_incoherence.json")
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
