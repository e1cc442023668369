"""Benchmark driver: one module per paper table (DESIGN.md §7).

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus a JSON
summary per module under experiments/.  --full runs the complete grids
(the default keeps every module in quick mode so CI-on-one-core stays
under ~15 minutes)."""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        ablation_incoherence,
        incoherence_stats,
        proxy_loss,
        quality_grid,
        throughput,
        trd_trh,
    )

    quick = [] if args.full else ["--quick"]
    modules = {
        "proxy_loss": (proxy_loss, []),          # Tables 14/15
        "throughput": (throughput, []),          # Table 4
        "trd_trh": (trd_trh, []),                # Table 6
        "incoherence_stats": (incoherence_stats, quick),  # Figures 2/3
        "quality_grid": (quality_grid, quick),   # Tables 1/2
        "ablation_incoherence": (ablation_incoherence, quick),  # Tables 3/5
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    failures = []
    for name, (mod, extra) in modules.items():
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            mod.main(extra)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} took {time.time()-t0:.0f}s", flush=True)
    # grad_compression needs its own process (16 fake devices via XLA_FLAGS
    # must be set before jax init)
    if args.only is None or "grad_compression" in (args.only or ""):
        import os
        import subprocess

        print("# === grad_compression ===", flush=True)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.grad_compression"],
            env=env, capture_output=True, text=True, timeout=900,
        )
        print(r.stdout, end="")
        if r.returncode != 0:
            print(r.stderr[-2000:], file=sys.stderr)
            failures.append("grad_compression")

    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        return 1
    print("# all benchmarks complete")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
