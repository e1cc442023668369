"""Figures 2/3 analogue: µ_W and µ_H before/after incoherence processing.

Paper: after conjugation by the two-factor random orthogonal transforms,
max|W_ij| (normalized) and max|Q_ij| (Hessian eigenvectors) drop below the
slope-1 line — i.e. both become incoherent."""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import incoherence as inc
from repro.core.hessian import damp
from repro.data import make_calibration
from repro.models import layers as Lm

from benchmarks.common import emit, trained_lm


def run(args) -> dict:
    cfg, model, params = trained_lm(steps=args.train_steps)
    calib = make_calibration(cfg.vocab, n_segments=8, seg_len=128, seed=7)
    x = Lm.embed(params["embed"], calib.tokens)
    positions = jnp.arange(calib.tokens.shape[1], dtype=jnp.int32)
    rows = []
    layer_params = [
        jax.tree.map(lambda a: a[i], params["layers"])
        for i in range(cfg.n_layers)
    ]
    for kind in (["kronecker", "hadamard"] if not args.quick else ["kronecker"]):
        xs = x
        for li, lp in enumerate(layer_params):
            h = Lm.norm_apply(lp["ln1"], xs, cfg)
            X = h.reshape(-1, cfg.d_model).astype(jnp.float32)
            H = damp(X.T @ X / X.shape[0], 0.01)
            for name in ("wq", "wo"):
                W = lp["attn"][name].T.astype(jnp.float32)
                mu_w0 = float(inc.mu_weight(W))
                mu_h0 = float(inc.mu_hessian(H))
                U = inc.make_transform(kind, W.shape[0], seed=li * 2 + 1)
                V = inc.make_transform(kind, W.shape[1], seed=li * 2 + 2)
                Wt = inc.apply_transform(V, W)
                Wt = inc.apply_transform(U, Wt.T).T
                Ht = inc.apply_transform(V, H)
                Ht = inc.apply_transform(V, Ht.T).T
                rows.append({
                    "layer": li, "proj": name, "kind": kind,
                    "mu_w_before": mu_w0,
                    "mu_w_after": float(inc.mu_weight(Wt)),
                    "mu_h_before": mu_h0,
                    "mu_h_after": float(inc.mu_hessian((Ht + Ht.T) / 2)),
                })
            xs = xs + Lm.attention_full(lp["attn"], h, cfg, positions=positions)
            h2 = Lm.norm_apply(lp["ln2"], xs, cfg)
            xs = xs + Lm.mlp_apply(lp["mlp"], h2, cfg)
    for kind in {r["kind"] for r in rows}:
        sub = [r for r in rows if r["kind"] == kind]
        emit(
            f"incoherence_stats/{kind}", 0.0,
            f"mu_w {np.mean([r['mu_w_before'] for r in sub]):.2f}->"
            f"{np.mean([r['mu_w_after'] for r in sub]):.2f}; "
            f"mu_h {np.mean([r['mu_h_before'] for r in sub]):.2f}->"
            f"{np.mean([r['mu_h_after'] for r in sub]):.2f}",
        )

    # The paper's Figs 2/3 are measured on OPT models whose weights carry
    # large outliers; the small bench LM stays near its (already
    # incoherent) gaussian init, so µ barely moves above.  Reproduce the
    # paper's setting with outlier-bearing weights (the regime IncP is
    # FOR — same generator as the unit tests):
    import sys

    sys.path.insert(0, "tests")
    from conftest import make_hessian, make_weights

    W = make_weights(256, 512, seed=0, outliers=0.01, outlier_scale=1.0)
    # full-rank decaying spectrum: µ_H over an exactly-degenerate damped
    # eigenspace is basis-arbitrary and uninformative
    G = jax.random.normal(jax.random.PRNGKey(5), (2048, 512))
    G = G * (1.0 / jnp.sqrt(1.0 + jnp.arange(512)))[None, :]
    Grot = G.at[:, 0].mul(10.0)  # outlier channel
    H = Grot.T @ Grot / 2048 + 1e-4 * jnp.eye(512)
    U = inc.make_transform("kronecker", 256, seed=1)
    V = inc.make_transform("kronecker", 512, seed=2)
    Wt = inc.apply_transform(V, W)
    Wt = inc.apply_transform(U, Wt.T).T
    Ht = inc.apply_transform(V, H)
    Ht = inc.apply_transform(V, Ht.T).T
    outlier = {
        "mu_w_before": float(inc.mu_weight(W)),
        "mu_w_after": float(inc.mu_weight(Wt)),
        "mu_h_before": float(inc.mu_hessian(H)),
        "mu_h_after": float(inc.mu_hessian((Ht + Ht.T) / 2)),
    }
    emit(
        "incoherence_stats/outlier_weights(paper_regime)", 0.0,
        f"mu_w {outlier['mu_w_before']:.1f}->{outlier['mu_w_after']:.2f}; "
        f"mu_h {outlier['mu_h_before']:.2f}->{outlier['mu_h_after']:.2f}",
    )
    return {"rows": rows, "outlier_regime": outlier}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="experiments/incoherence_stats.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    results = run(args)
    if args.out:
        import pathlib

        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
