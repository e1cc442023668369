#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload qwen3-14b.chat --seed 7 --seconds 45 --trace 0

Set-up builds the cell's 2-bit weights on the device from the seed,
builds the engine ``launch/serve.py --paged --paged-prefill`` serves,
warms every program the cell's traffic reaches, and opens the window
once the traffic is in steady state.  The window drives the engine open
loop for ``--seconds``; afterwards a sample of the served tokens is
checked against the plain float32 reference.  ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics instead
of the end-to-end ones.

The last line of standard output is the result, one JSON object; the
lines before it say what set-up did.  Without a TPU, or with fewer chips
than the cell asks for, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".trace")
PLAN_DIR = os.path.join(BENCH, ".plan")
# libtpu's own logs stay in the checkout too (its default is /tmp)
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".tpu_logs"))
# the engine spans whose names label the device's idle gaps
SPANS = ("step", "schedule", "prefill", "decode", "emit",
         "dispatch:prefill_paged", "dispatch:decode_paged_sample")


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)


def use_cache() -> None:
    """JAX's persistent compile cache, in the checkout and unbounded (a
    bounded cache takes a file lock per write, which concurrent compiles
    time out on, and then skips the entry)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"this cell needs {n} TPU chip(s); JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def load_metric(name: str):
    from harness import spec

    path = spec.metric_file(name)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metric
    entries: those that name the cell, or name no cells at all."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


class Dispatches(list):
    """Each dispatch's real lanes, tokens and prior contexts while
    ``on`` (the traced window): the adapter's two serving entries are
    wrapped to note them.  Padding lanes and padded chunk tails are
    addressed to the scratch page 0, which no request ever owns."""

    def __init__(self, adapter):
        import numpy as np

        from harness.work import Dispatch

        super().__init__()
        self.on = False
        prefill, decode = adapter.prefill_paged, adapter.decode_paged_sample

        def prefill_paged(tokens, positions, bt, ctx_len, pages, offs,
                          pool):
            if self.on:
                real = (np.asarray(pages) != 0).sum(axis=1)
                lanes = np.nonzero(real)[0]
                self.append(Dispatch("prefill", real[lanes].tolist(),
                                     np.asarray(ctx_len)[lanes].tolist()))
            return prefill(tokens, positions, bt, ctx_len, pages, offs, pool)

        def decode_paged_sample(tokens, positions, bt, ctx_len, pages, offs,
                                sampling, pool):
            if self.on:
                lanes = np.nonzero(np.asarray(pages) != 0)[0]
                self.append(Dispatch("decode", [1] * len(lanes),
                                     np.asarray(ctx_len)[lanes].tolist()))
            return decode(tokens, positions, bt, ctx_len, pages, offs,
                          sampling, pool)

        adapter.prefill_paged = prefill_paged
        adapter.decode_paged_sample = decode_paged_sample


def make_adapter(c: dict, arrays: dict):
    """The program's decoder over the harness's weights."""
    from harness import weights
    from harness.spec import arch
    from repro.serve import CachedDecoder

    a = arch(c["workload"]["config"], c["model"])
    prog = weights.to_program(arrays, a)
    return CachedDecoder(cfg=a, embed=prog["embed"],
                         final_norm=prog["final_norm"],
                         blocks=prog["blocks"])


def requests(c: dict, seed: int) -> list:
    """The cell's request stream for ``seed``, opened part-way through."""
    from harness import traffic

    m, geo = c["model"], c["cell"]
    specs = traffic.generate(c["mix"], geo, m["vocab"], seed,
                             geo["requests"])
    traffic.stagger(specs, geo["in_flight"], seed, m["vocab"])
    return specs


def plan(c: dict, seed: int, seconds: float):
    """The cell's requests for ``seed`` and the programs to warm: those
    the streams of the cell's ``plan_seeds`` and of ``seed`` reach.  The
    fixed seeds make the set, and so the compile cache's contents, the
    same from run to run; their share is kept in the checkout
    (``PLAN_DIR``), so only a cell's first run replays them."""
    import hashlib

    from harness import programs

    geo = c["cell"]
    # the replay runs past the window's end: a slower chip reaches
    # further into the stream than the tick model says
    horizon = geo["warm_s"] + seconds + 30
    specs = requests(c, seed)
    progs = set(programs.reachable(geo, specs, horizon))
    key = hashlib.sha256(json.dumps(
        [c["model"], c["mix"], geo, seconds], sort_keys=True).encode())
    path = os.path.join(PLAN_DIR, key.hexdigest()[:32] + ".json")
    if os.path.exists(path):
        with open(path) as f:
            fixed = [programs.Program(*p) for p in json.load(f)]
    else:
        fixed = set()
        for s in geo["plan_seeds"]:
            fixed.update(programs.reachable(geo, requests(c, s), horizon))
        os.makedirs(PLAN_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump([[p.kind, p.lanes, p.pages] for p in sorted(fixed)], f)
    return specs, sorted(progs.union(fixed))


def new_engine(c: dict, adapter, trace: bool):
    """The engine ``launch/serve.py --paged --paged-prefill`` builds, with
    the cell's geometry; traced runs annotate its spans for the
    profiler."""
    from harness import programs
    from repro.launch.serve import build_engine
    from repro.serve.telemetry import Tracer

    geo = c["cell"]
    engine = build_engine(adapter, max_seq_len=geo["max_seq_len"],
                          args=programs.engine_config(geo))
    if trace:
        engine.attach_tracer(Tracer(annotate=True))
    return engine


def serve(c: dict, engine, specs, seconds: float, clock,
          dispatches: Dispatches | None = None, device=None,
          bench: dict | None = None) -> dict:
    """Warm traffic until the stream is in steady state, then the window
    (traced when ``dispatches`` is given).  Returns the window's
    readings; ``"driver"`` holds every request.  Frees the KV pool."""
    import jax

    from harness import trace as tr
    from harness.driver import Driver, pctl
    from harness.peaks import peaks

    name, m, geo = c["workload"]["name"], c["model"], c["cell"]
    trace = dispatches is not None
    t = time.perf_counter()
    driver = Driver(engine, specs, geo["in_flight"])
    staging_s = time.perf_counter() - t
    driver.run_until(driver.start + geo["warm_s"])
    warm_traffic_s = time.perf_counter() - t

    c0 = dict(engine.stats)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no per-call Python events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    compiles0 = clock.snapshot()
    setup_s = time.perf_counter() - T_START
    if trace:
        dispatches.on = True
    t0 = engine.now()
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        driver.run_until(t0 + seconds)
    t1 = t0 + seconds
    if trace:
        dispatches.on = False
    compiles1 = clock.snapshot()
    if trace:
        jax.profiler.stop_trace()
    c1 = dict(engine.stats)
    counters = {k: c1[k] - c0[k] for k in c1}
    in_window = compiles1["compiles"] - compiles0["compiles"]
    ticks = sum(1 for a, _ in driver.ticks if t0 <= a < t1)
    log(f"window {seconds}s: {ticks} ticks, compiles in window "
        f"{in_window} (cache reads "
        f"{compiles1['cache_hits'] - compiles0['cache_hits']}); warm "
        f"traffic took {warm_traffic_s:.2f}s, of it staging "
        f"{staging_s:.2f}s")
    log(f"counters in window: {json.dumps(counters)}")
    w = driver.window(t0, t1)
    dev = device if device is not None else jax.devices()[0]
    peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    e2e = {
        "setup_s": (setup_s, "s"),
        "output_tok_s": (w["output_tokens"] / seconds, "tokens/s"),
        "itl_p95_ms": (None if not len(w["gaps"])
                       else 1e3 * pctl(w["gaps"], 95), "ms"),
    }
    log(f"window: {w['output_tokens']} tokens, {len(w['gaps'])} gaps, "
        f"{len(w['ttft'])} arrivals; itl p50 "
        f"{1e3 * (pctl(w['gaps'], 50) or 0):.2f} ms; ttft p50 "
        f"{pctl(w['ttft'], 50)} s; {w['attempted']} attempted, "
        f"{w['failed']} failed")

    per_layer, breakdown, busy = {}, None, None
    kind = dev.device_kind
    if trace:
        t = time.perf_counter()
        trc = tr.load(TRACE_DIR, SPANS)
        for plane in trc.layout:
            log(f"trace plane {plane[:300]}")
        ctx = {"trace": trc, "window": (t0, t1), "dispatches": dispatches,
               "counters": counters, "requests": driver.reqs,
               "model": m, "peaks": peaks(kind),
               "page_size": geo["page_size"],
               "outputs": w["output_tokens"], "notes": []}
        for entry in (cell_metrics(bench, name, "per_layer")
                      if bench else []):
            v = load_metric(entry["name"]).read(ctx)
            if v is not None:
                per_layer[entry["name"]] = (v, entry["unit"])
        for note in ctx["notes"]:
            log(note)
        busy = tr.busy_ns(trc.ops, trc.window) / 1e9
        breakdown = {
            "device_ops": tr.top_ops(trc.ops, trc.window),
            "idle_gaps": tr.name_gaps(tr.idle_gaps(trc.ops, trc.window),
                                      trc.spans)}
        log(f"trace: {len(trc.ops)} ops, {len(trc.modules)} program runs, "
            f"{len(trc.spans)} host spans, window "
            f"{(trc.window[1] - trc.window[0]) / 1e9:.3f}s, busy "
            f"{busy:.3f}s, read in {time.perf_counter() - t:.1f}s")
        log(f"breakdown: {json.dumps(breakdown)}")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    pool = engine.pool
    pool.k = pool.v = pool.k_scale = pool.v_scale = None  # free the KV
    return {"driver": driver, "t0": t0, "t1": t1,
            "attempted": w["attempted"],
            "failed": w["failed"], "e2e": e2e, "per_layer": per_layer,
            "breakdown": breakdown, "busy_s": busy, "window_s": seconds,
            "kind": kind, "peak_bytes": peak_bytes,
            "compiles_in_window": in_window, "counters": counters}


def check_tokens(c: dict, arrays: dict, r: dict, seed: int) -> dict:
    """The widest gap of the served check tokens below the reference's
    best logit, beside its limit; ``r`` is what ``serve`` returned."""
    from harness import check

    ck = c["cell"]["check"]
    reqs = check.sample(r["driver"], (r["t0"], r["t1"]), seed,
                        ck["max_tokens"])
    t = time.perf_counter()
    gaps = check.served_gaps(arrays, c["model"], reqs)
    log(f"reference: {len(reqs)} requests, {gaps.size} served tokens, "
        f"{sum(len(r.prompt) + len(r.out_tokens) for r in reqs)} positions "
        f"in {time.perf_counter() - t:.1f}s")
    widest = float(gaps.max()) if gaps.size else None
    return {"compiles_in_window": {"value": r["compiles_in_window"],
                                   "limit": 0},
            "served_tokens_checked": {"value": int(gaps.size),
                                      "limit": ck["min_served"]},
            "widest_gap": {"value": widest,
                           "limit": ck["widest_gap_limit"]},
            "_reqs": reqs}


def passed(checks: dict) -> bool:
    """Correct: nothing compiled inside the window, enough served tokens
    were compared, and none lies further below the reference's best
    than the limit."""
    w, n = checks["widest_gap"], checks["served_tokens_checked"]
    return (checks["compiles_in_window"]["value"] == 0
            and w["value"] is not None and w["value"] <= w["limit"]
            and n["value"] >= n["limit"])


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             device=None, bench: dict | None = None) -> dict:
    """One run of the cell ``c`` (``harness.spec.cell``).  ``device`` is
    the chip (None: JAX's default device, as in the CPU tests)."""
    import jax

    from harness import programs, weights
    from harness.clock import CompileClock

    clock = CompileClock()
    t = time.perf_counter()
    arrays = weights.build_arrays(c["model"], seed)
    dispatched_s = time.perf_counter() - t
    specs, progs = plan(c, seed, seconds)  # while the device makes them
    plan_s = time.perf_counter() - t - dispatched_s
    jax.block_until_ready(arrays)
    weights_s = time.perf_counter() - t
    adapter = make_adapter(c, arrays)
    log(f"weights {weights_s:.2f}s (of it plan {plan_s:.2f}s on the host "
        f"meanwhile); programs ({len(progs)}): "
        f"{' '.join(p.name for p in progs)}")
    dispatches = Dispatches(adapter) if trace else None
    engine = new_engine(c, adapter, trace)
    t = time.perf_counter()
    warm = programs.warm(adapter, engine.pool, progs,
                         c["cell"]["prefill_chunk"],
                         c["mix"]["sampling"]["temperature"], clock, log)
    compile_s = time.perf_counter() - t
    r = serve(c, engine, specs, seconds, clock, dispatches, device, bench)
    del engine
    log(f"set-up {r['e2e']['setup_s'][0]:.2f}s: weights and plan "
        f"{weights_s:.2f}, compile and first runs {compile_s:.2f}, "
        f"warm traffic and the rest after")
    checks = check_tokens(c, arrays, r, seed)
    del r["driver"]
    checks.pop("_reqs")
    r.update(correct=passed(checks), checks=checks, warm=warm,
             programs=[p.name for p in progs])
    return r


def result_line(r: dict, bench: dict, workload: str, trace: bool,
                count: int) -> dict:
    metrics = {}
    if trace:
        for k, (v, unit) in r["per_layer"].items():
            metrics[k] = {"value": v, "unit": unit}
    else:
        for entry in cell_metrics(bench, workload, "end_to_end"):
            v, unit = r["e2e"][entry["name"]]
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": unit}
    device = {"platform": "tpu", "kind": r["kind"], "count": count,
              "memory_peak_bytes": r["peak_bytes"]}
    out = {"correct": r["correct"], "attempted": r["attempted"],
           "failed": r["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = r["busy_s"]
        device["window_s"] = r["window_s"]
        out["breakdown"] = r["breakdown"]
    out["checks"] = r["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec

    bench = spec.benchmark()
    c = spec.cell(args.workload)
    use_cache()
    devs = require_chips(c["workload"]["chips"])
    log(f"device {devs[0].device_kind} x{len(devs)}; cell {args.workload} "
        f"seed {args.seed} seconds {args.seconds} trace {args.trace}")
    r = run_cell(c, args.seed, args.seconds, bool(args.trace),
                 device=devs[0], bench=bench)
    log(f"programs warmed: {json.dumps(r['warm'])}")
    line = result_line(r, bench, args.workload, bool(args.trace),
                       c["workload"]["chips"])
    for k, v in r["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
