"""Table 4 analogue: decode-path cost, fp16 vs QuIP quantized matmul.

The paper measures per-token generation latency (QuIP 81ms vs OPTQ 53ms on
an A6000).  Without a TPU we report BOTH:
  * measured CPU wall-time of the two inference paths (relative cost of
    the incoherence transforms — the paper's 1.5x observation);
  * the TPU roofline view: weight bytes/token and arithmetic intensity of
    the 2-bit packed path vs bf16 (the 16/bits x reduction that makes
    2-bit decode compute- rather than HBM-bound — DESIGN.md §3).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.quantizer import QuipConfig, quantize_layer
from repro.kernels.quant_matmul import ops as qmm
from repro.runtime.roofline import HW

from benchmarks.common import emit, timeit


def run(args) -> dict:
    m = n = args.dim
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (m, n)) * 0.02
    X = jax.random.normal(jax.random.PRNGKey(1), (2048, n))
    H = X.T @ X / 2048 + 1e-3 * jnp.eye(n)
    results = {}

    # build the quantized layer (full QuIP path: transforms + packed int2)
    for bits in (2, 3, 4):
        qcfg = QuipConfig(bits=bits, method="ldlq", use_kernel=False)
        layer, _ = quantize_layer(W, H, qcfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (args.batch, n))

        fp = jax.jit(lambda x: x @ W.T)
        qp = jax.jit(layer.__call__)
        t_fp = timeit(fp, x, iters=args.iters)
        t_q = timeit(qp, x, iters=args.iters)
        results[f"fp32_matvec@{bits}b_ref"] = t_fp
        results[f"quip_path@{bits}b"] = t_q
        emit(f"throughput/fp_matmul_{m}x{n}", t_fp, f"batch={args.batch}")
        emit(
            f"throughput/quip_{bits}b_{m}x{n}", t_q,
            f"slowdown_vs_fp={t_q / t_fp:.2f}x (paper: ~1.5x)",
        )

        # TPU roofline view (per token): bytes of weights moved
        bytes_bf16 = m * n * 2
        bytes_packed = packing.packed_rows(n, bits) * m * 4
        flops = 2 * m * n
        hw = HW()
        t_mem_bf16 = bytes_bf16 / hw.hbm_bw
        t_mem_q = bytes_packed / hw.hbm_bw
        t_compute = flops / hw.peak_flops
        results[f"tpu_intensity@{bits}b"] = flops / bytes_packed
        emit(
            f"throughput/tpu_roofline_{bits}b", 0.0,
            f"wbytes/token {bytes_bf16}->{bytes_packed} "
            f"({bytes_bf16/bytes_packed:.1f}x); decode t_mem "
            f"{t_mem_bf16*1e6:.1f}us->{t_mem_q*1e6:.1f}us vs t_compute "
            f"{t_compute*1e6:.2f}us",
        )
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="experiments/throughput.json")
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
