"""Table 6 analogue: tr(D)/tr(H) and approximate rank of real-activation
Hessians across layers (paper: ratio <= 0.65, H approximately low-rank)."""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hessian import damp
from repro.core.proxy import trD_trH
from repro.data import make_calibration
from repro.models import layers as Lm

from benchmarks.common import emit, trained_lm


def run(args) -> dict:
    cfg, model, params = trained_lm(steps=args.train_steps)
    calib = make_calibration(cfg.vocab, n_segments=8, seg_len=128, seed=7)
    # tap the input activations of each block's attention + mlp
    x = Lm.embed(params["embed"], calib.tokens)
    positions = jnp.arange(calib.tokens.shape[1], dtype=jnp.int32)
    ratios, ranks = [], []
    layer_params = [
        jax.tree.map(lambda a: a[i], params["layers"])
        for i in range(cfg.n_layers)
    ]
    for lp in layer_params:
        h = Lm.norm_apply(lp["ln1"], x, cfg)
        X = h.reshape(-1, cfg.d_model).astype(jnp.float32)
        H = damp(X.T @ X / X.shape[0], 0.01)
        ratios.append(float(trD_trH(H)))
        ev = np.linalg.eigvalsh(np.asarray(H))
        ranks.append(float((ev > 0.01 * ev.max()).mean()))
        x = x + Lm.attention_full(lp["attn"], h, cfg, positions=positions)
        h2 = Lm.norm_apply(lp["ln2"], x, cfg)
        x = x + Lm.mlp_apply(lp["mlp"], h2, cfg)
    results = {
        "trD_trH_mean": float(np.mean(ratios)),
        "trD_trH_per_layer": ratios,
        "approx_frac_rank_mean": float(np.mean(ranks)),
        "approx_frac_rank_per_layer": ranks,
    }
    emit("trd_trh/mean", 0.0,
         f"trD/trH={results['trD_trH_mean']:.3f} (paper<=0.65) "
         f"frac_rank={results['approx_frac_rank_mean']:.3f}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--out", default="experiments/trd_trh.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    results = run(args)
    print(json.dumps({k: v for k, v in results.items() if "per_layer" not in k},
                     indent=1))
    if args.out:
        import pathlib

        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
