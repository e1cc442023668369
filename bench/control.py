#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload qwen3-14b.chat --seconds 20 \\
        --seeds 11,12,13 --control-seeds 11,12,13

For each seed, in one process (the compiled programs are reused): build
the seed's weights, serve the cell's traffic for a short window, and
read the widest gap of the served check tokens below the float32
reference's best logit (the program's reading).  For each control seed,
also read the control: the reference itself in int8 (and, beside it,
float8 e4m3), picking its best token at the same positions, prompts and
served tokens.  Each reading is given as the widest gap and the mean
gap over the served positions.  The limit lies between the largest
program reading and the smallest control reading.  The last line is a
JSON summary.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax

    from harness import check, programs, spec, weights
    from harness.clock import CompileClock

    c = spec.cell(args.workload)
    run.use_cache()
    devs = run.require_chips(c["workload"]["chips"])
    clock = CompileClock()
    adapter, warmed, rows = None, set(), []
    for seed in seeds:
        t = time.perf_counter()
        arrays = weights.build_arrays(c["model"], seed)
        if adapter is None:
            adapter = run.make_adapter(c, arrays)
        else:
            fresh = run.make_adapter(c, arrays)
            adapter.embed, adapter.final_norm, adapter.blocks = (
                fresh.embed, fresh.final_norm, fresh.blocks)
            del fresh
        specs, progs = run.plan(c, seed, args.seconds)
        engine = run.new_engine(c, adapter, False)
        todo = [p for p in progs if p not in warmed]
        programs.warm(adapter, engine.pool, todo, c["cell"]["prefill_chunk"],
                      c["mix"]["sampling"]["temperature"], clock, run.log)
        warmed.update(todo)
        r = run.serve(c, engine, specs, args.seconds, clock, device=devs[0])
        del engine
        checks = run.check_tokens(c, arrays, r, seed)
        gaps = check.served_gaps(arrays, c["model"], checks["_reqs"])
        row = {"seed": seed, "program": checks["widest_gap"]["value"],
               "program_mean": float(gaps.mean()) if gaps.size else None,
               "served": checks["served_tokens_checked"]["value"],
               "output_tok_s": r["e2e"]["output_tok_s"][0],
               "itl_p95_ms": r["e2e"]["itl_p95_ms"][0],
               "compiles_in_window": r["compiles_in_window"]}
        if seed in control:
            for prec in ("int8", "fp8"):
                gaps = check.control_gaps(arrays, c["model"],
                                          checks["_reqs"], prec)
                row["control" if prec == "int8" else prec] = (
                    float(gaps.max()) if gaps.size else None)
                row[f"{prec}_mean"] = (float(gaps.mean()) if gaps.size
                                       else None)
        row["s"] = time.perf_counter() - t
        run.log(f"seed {seed}: {json.dumps(row)}")
        rows.append(row)
        adapter.embed = adapter.final_norm = adapter.blocks = None
        del arrays, r, checks
        gc.collect()
    prog = [r["program"] for r in rows if r["program"] is not None]
    ctrl = [r["control"] for r in rows if r.get("control") is not None]
    print(json.dumps({"workload": args.workload,
                      "device": devs[0].device_kind, "rows": rows,
                      "program_max": max(prog) if prog else None,
                      "control_min": min(ctrl) if ctrl else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
