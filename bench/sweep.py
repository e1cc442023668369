#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate the engine
sustains, by a sweep of fixed rates on the chip, in one process.

    python3 bench/sweep.py --workload qwen3-14b.chat --seed 5 \\
        --seconds 40 --rates 0.2,0.4,0.6

For each rate the cell runs as ``run.py`` would with its ``rate_rps``
replaced; per rate it prints the tokens served per second, the time to
first token and gap percentiles, and the backlog of arrived requests not
yet admitted at the window's start and end: a backlog that grows through
the window means the rate is above the knee.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

import run


def backlog(driver, t: float) -> int:
    return sum(1 for r in driver.reqs if r.arrival <= t and (
        r.t_admitted is None or r.t_admitted > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from harness import programs, spec, weights
    from harness.clock import CompileClock
    from harness.driver import pctl

    base = spec.cell(args.workload)
    run.use_cache()
    devs = run.require_chips(base["workload"]["chips"])
    clock = CompileClock()
    arrays = weights.build_arrays(base["model"], args.seed)
    adapter = run.make_adapter(base, arrays)
    warmed, rows = set(), []
    for rate in [float(r) for r in args.rates.split(",")]:
        c = copy.deepcopy(base)
        c["cell"]["rate_rps"] = rate
        specs, progs = run.plan(c, args.seed, args.seconds)
        engine = run.new_engine(c, adapter, False)
        todo = [p for p in progs if p not in warmed]
        programs.warm(adapter, engine.pool, todo, c["cell"]["prefill_chunk"],
                      c["mix"]["sampling"]["temperature"], clock, run.log)
        warmed.update(todo)
        r = run.serve(c, engine, specs, args.seconds, clock, device=devs[0])
        del engine
        d = r["driver"]
        t0, t1 = r["t0"], r["t1"]
        w = d.window(t0, t1)
        row = {"rate_rps": rate,
               "output_tok_s": r["e2e"]["output_tok_s"][0],
               "ttft_p50_s": pctl(w["ttft"], 50),
               "ttft_p95_s": pctl(w["ttft"], 95),
               "itl_p50_ms": 1e3 * (pctl(w["gaps"], 50) or 0),
               "itl_p95_ms": 1e3 * (pctl(w["gaps"], 95) or 0),
               "backlog_start": backlog(d, t0), "backlog_end": backlog(d, t1),
               "evictions": r["counters"]["evictions"],
               "compiles_in_window": r["compiles_in_window"]}
        run.log(f"rate {rate}: {json.dumps(row)}")
        rows.append(row)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
