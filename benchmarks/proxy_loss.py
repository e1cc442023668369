"""Tables 14/15 analogue: proxy loss by rounding method; biased vs unbiased.

Paper: at 2 bits LDLQ/LDLQ-RG/Greedy are roughly equivalent and all beat
Near (Table 14); unbiased (stochastic) rounding is WORSE than biased
nearest inside LDLQ, increasingly so at low bits (Table 15)."""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.methods import round_weights
from repro.core.hessian import damp
from repro.core.incoherence import to_grid, quant_range, from_grid
from repro.core.proxy import proxy_loss

from benchmarks.common import emit


def _setup(n=256, m=128, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    W = jax.random.normal(k1, (m, n)) * 0.02
    X = jax.random.normal(k2, (2048, n // 8))
    A = jax.random.normal(jax.random.PRNGKey(seed + 1), (n // 8, n))
    Xf = X @ A  # low-rank-ish activations
    H = damp(Xf.T @ Xf / 2048, 0.01)
    return W, H


def run(args) -> dict:
    W, H = _setup()
    results = {}
    for bits in (4, 3, 2):
        maxq = 2**bits - 1
        s = quant_range(W, 2.4)
        Wg = to_grid(W, s, maxq)
        for method in ("near", "stoch", "ldlq", "ldlq_stoch", "ldlq_rg", "greedy"):
            key = jax.random.PRNGKey(bits * 10)
            kw = {"greedy_passes": 3} if method in ("ldlq_rg", "greedy") else {}
            Wq = round_weights(method, Wg, H, maxq, key, **kw)
            l = float(proxy_loss(from_grid(Wq, s, maxq), W, H))
            results[f"{method}@{bits}b"] = l
            emit(f"proxy_loss/{method}@{bits}b", 0.0, f"proxy={l:.5g}")
    # Table 15 digest: unbiased - biased gap per bits
    for bits in (4, 3, 2):
        gap = results[f"ldlq_stoch@{bits}b"] - results[f"ldlq@{bits}b"]
        results[f"stoch_minus_near_gap@{bits}b"] = gap
        emit(f"proxy_loss/unbiased_gap@{bits}b", 0.0,
             f"gap={gap:.5g} (paper: positive, grows at low bits)")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/proxy_loss.json")
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
