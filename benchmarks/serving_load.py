"""Serving load benchmark: Poisson arrivals against the batching engine.

    PYTHONPATH=src:benchmarks python benchmarks/serving_load.py --smoke

Synthetic open-loop workload: request arrival times are drawn from a
Poisson process (``--rate`` req/s), prompt lengths jittered around
``--prompt-len``.  Reports throughput (tok/s), time-to-first-token and
inter-token latency percentiles (p50/p99), and peak KV-page occupancy —
the numbers that matter for a continuous-batching deployment.  The record
is written to ``BENCH_serving.json`` (``--out``) so perf regressions are
visible PR-over-PR.  ``--paged`` decodes in place over the page pool
(paged-attention path); ``--paged-prefill`` batches each tick's prefill
chunks into one fused cross-request dispatch; ``--kv-int8`` stores int8
KV pages.  ``--prefix-len N`` switches to a prefix-heavy workload: every
prompt opens with the same N-token header (system prompt / few-shot
block), which ``--prefix-cache`` then serves from cached pages instead of
recomputing (``prefix_hit_tokens`` in the record).  ``--speculative K``
(with ``--paged``) turns decode ticks into draft-and-verify ticks; the
record then carries acceptance_rate / accepted_per_tick /
tokens_per_lane_tick so drafting health is tracked alongside latency.
``--trace`` records per-tick spans during the measured run and adds the
span-derived per-phase time breakdown (+ coverage) to the record;
``--trace-out PATH`` also writes the Chrome/Perfetto trace JSON.

``--cancel-rate F`` cancels a seeded fraction of the measured requests at
deterministic ticks mid-run (engine fault plan, serve/faults.py) and
``--deadline-s`` arms per-request wall-clock deadlines — the record then
carries cancelled / failed / deadline_missed counts, and the ttft/itl p99
columns measure tail latency UNDER cancellation churn: surviving requests
pay for the page releases and batch-shape changes the cancels cause.

``--http`` runs the same open-loop workload OVER THE WIRE through the
streaming front door (``serve/frontdoor``): the engine lives behind an
asyncio HTTP/SSE server and every client measures latency at its own
socket, so ttft/itl include HTTP framing, the tick loop, and scheduling.
``--overload-burst N`` fires a synchronized mid-run volley and records
the 200/429/413 admission split plus degradation-ladder transitions —
the record is written to ``BENCH_serving_http.json`` by convention, and
the run fails if the graceful drain leaks a single KV page.

``--fleet N`` (with ``--http``) serves the same workload through N
data-parallel replica SUBPROCESSES behind the supervised fleet router
(``serve/fleet``): the record gains per-replica balance, the affinity
hit rate, and failover counts, and is written to ``BENCH_fleet.json``.
``--kill-mid-run`` SIGKILLs the busiest replica at the workload
midpoint — the ttft/itl percentiles then measure client-visible tail
latency UNDER crash failover (the router resubmits each orphaned
stream's prompt + journaled tokens to a survivor and splices the
continuation), and the run fails unless every admitted stream still
completed and every drained replica's leak gate was clean.

Latency percentiles (in-process mode) come from the engine's OWN
lifecycle histograms
(``Engine.summary()``), asserted equal to an external recomputation from
raw request timestamps — the benchmark cross-checks the telemetry it
reports.  Both observe FINISHED requests only: a cancelled request's
partial stream is not a latency sample.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

sys.path.insert(0, "src")

from repro.configs import get_smoke_config
from repro.core.quantizer import QuipConfig
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig


def pctl(xs, q):
    """Percentile of ``xs``, or None when empty — None stays valid JSON
    (NaN does not survive strict parsers) and sorts honestly as "no
    samples" instead of a poisoned number."""
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def rnd(x, n):
    return None if x is None else round(x, n)


def _sse_events(resp):
    """Incrementally parse SSE frames off a live ``http.client`` response,
    yielding ``(t_received, event, payload)`` per complete frame."""
    ev, data = None, None
    for raw in resp.fp:
        line = raw.decode("utf-8", "replace").rstrip("\n")
        if line.startswith("event: "):
            ev = line[len("event: "):]
        elif line.startswith("data: "):
            data = line[len("data: "):]
        elif not line and ev is not None:
            yield time.perf_counter(), ev, json.loads(data)
            ev, data = None, None


def _measured_client(port, prompt_tokens, gen, t0, arrival):
    """One open-loop client: sleep to its arrival time, POST a streaming
    generate, and timestamp every SSE frame at the socket.  Transport
    failures (a dropped stream the router could not rescue) come back as
    status 0 so they count as incomplete, never as a crash of the
    benchmark itself."""
    import http.client

    t_due = t0 + float(arrival)
    delay = t_due - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    body = json.dumps({
        "prompt": [int(t) for t in prompt_tokens],
        "max_new": gen,
        "stream": True,
    })
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        c.request("POST", "/v1/generate", body,
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        if r.status != 200:
            r.read()
            return {"status": r.status}
        token_times, done = [], None
        for t_ev, ev, payload in _sse_events(r):
            if ev == "token":
                token_times.append(t_ev)
            elif ev == "done":
                done = payload
        return {"status": 200, "t_due": t_due,
                "token_times": token_times, "done": done}
    except (ConnectionError, OSError, http.client.HTTPException):
        return {"status": 0, "done": None, "token_times": []}
    finally:
        c.close()


def run_fleet(args, cfg, prompts, lengths, arrivals):
    """N-replica fleet run: real ``launch/serve.py`` subprocesses behind
    the supervised router (serve/fleet); this process plays the clients
    AND — with ``--kill-mid-run`` — the chaos monkey.  Latency is
    measured at the client socket THROUGH the router, so a mid-run
    SIGKILL's failover splice shows up exactly where a user would feel
    it: one stretched inter-token gap, then the stream finishes."""
    import os
    import signal
    import threading

    from repro.serve.fleet import (
        FleetRouter,
        ProcessReplicaFactory,
        Supervisor,
    )

    # replica children import repro from source; make sure the tree is
    # on their path however this script itself was launched
    src = os.path.abspath("src")
    env_pp = os.environ.get("PYTHONPATH", "")
    if src not in env_pp.split(os.pathsep):
        os.environ["PYTHONPATH"] = (
            src + (os.pathsep + env_pp if env_pp else ""))

    replica_argv = [
        sys.executable, "-m", "repro.launch.serve",
        "--arch", args.arch, "--smoke",
        # identical weights on every replica (same seed): failover
        # splices must be token-identical across incarnations
        "--seed", str(args.seed),
        "--prompt-len", str(args.prompt_len),
        "--gen", str(args.gen),
        "--slots", str(args.slots),
        "--page-size", str(args.page_size),
        "--token-budget", str(args.token_budget),
        "--prefill-chunk", str(args.prefill_chunk),
        "--drain-timeout-s", str(args.drain_timeout_s),
    ]
    if args.pages is not None:
        replica_argv += ["--pages", str(args.pages)]
    for flag, on in (("--paged", args.paged),
                     ("--paged-prefill", args.paged_prefill),
                     ("--prefix-cache", args.prefix_cache),
                     ("--kv-int8", args.kv_int8),
                     ("--host-sample", args.host_sample),
                     ("--quantize", args.quantize)):
        if on:
            replica_argv.append(flag)
    if args.quantize:
        replica_argv += ["--bits", str(args.bits)]

    factory = ProcessReplicaFactory(replica_argv)
    sup = Supervisor(factory, args.fleet, probe_interval_s=0.25,
                     start_timeout_s=600.0,
                     replica_drain_timeout_s=args.drain_timeout_s + 30.0)
    router = FleetRouter(sup, port=0,
                         drain_timeout_s=args.drain_timeout_s)
    router.start_in_thread()

    # warm EVERY replica's jit caches directly at its own port (prefix
    # affinity would funnel a router-side warm-up to one replica), so
    # compile time stays out of the measured ttft
    import http.client
    for h in sup.handles:
        c = http.client.HTTPConnection("127.0.0.1", h.port, timeout=300)
        try:
            c.request("POST", "/v1/generate", json.dumps({
                "prompt": [int(t) for t in prompts[0][:8]],
                "max_new": 2, "stream": False,
            }), {"Content-Type": "application/json"})
            c.getresponse().read()
        finally:
            c.close()

    results = [None] * args.requests
    t0 = time.perf_counter()

    def client(i):
        results[i] = _measured_client(
            router.port, prompts[i][: lengths[i]], args.gen, t0,
            arrivals[i])

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.requests)]
    kill_info = {}
    if args.kill_mid_run:
        def killer():
            delay = (t0 + float(arrivals[len(arrivals) // 2])
                     - time.perf_counter())
            if delay > 0:
                time.sleep(delay)
            # the busiest healthy replica takes the SIGKILL: its live
            # streams are exactly the ones failover must rescue.  Wait
            # (bounded) for a replica to actually BE mid-stream first —
            # Poisson arrivals can cluster past the nominal midpoint,
            # and killing an idle replica exercises nothing
            deadline = time.perf_counter() + 30.0
            h = None
            while time.perf_counter() < deadline:
                busy = sorted(
                    (x for x in sup.handles
                     if x.state == "healthy" and x.inflight > 0),
                    key=lambda x: (-x.inflight, x.index))
                if busy:
                    h = busy[0]
                    break
                time.sleep(0.01)
            if h is None:  # workload already over: kill any survivor
                h = next(x for x in sup.handles if x.state == "healthy")
            kill_info.update(replica=h.index, pid=h.pid,
                             inflight_at_kill=h.inflight,
                             t_kill_s=round(time.perf_counter() - t0, 3))
            os.kill(h.pid, signal.SIGKILL)

        threads.append(threading.Thread(target=killer, daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    if kill_info:
        # let the killed slot finish its respawn before draining: the
        # record then shows the recovery, and the fresh incarnation's
        # leak gate is actually read (a replica still mid-model-build
        # has no gate yet and drains as None)
        h = sup.handles[kill_info["replica"]]
        deadline = time.perf_counter() + 180.0
        while time.perf_counter() < deadline and h.state != "healthy":
            time.sleep(0.25)
        kill_info["recovered"] = h.state == "healthy"
        kill_info["restarts"] = h.restarts

    counters = dict(router.counters)
    per_replica = [h.to_dict() for h in sup.handles]
    report = router.drain_and_join(reason="bench_complete")

    ok = [r for r in results if r and r["status"] == 200 and r["done"]]
    ttft = [r["token_times"][0] - r["t_due"]
            for r in ok if r["token_times"]]
    itl = [b - a for r in ok
           for a, b in zip(r["token_times"], r["token_times"][1:])]
    statuses = [r["status"] for r in results if r]
    total = sum(r["done"]["n_tokens"] for r in ok)
    hits = counters["affinity_hits"]
    fallbacks = counters["affinity_fallbacks"]
    rec = {
        "label": ("quip-%db" % args.bits) if args.quantize else "fp",
        "arch": cfg.name,
        "mode": "fleet",
        "transport": "http-sse",
        "decode_path": "paged" if args.paged else "gather-dense",
        "replicas": args.fleet,
        "requests": args.requests,
        "rate_req_s": args.rate,
        "kill_mid_run": bool(args.kill_mid_run),
        "kill": kill_info or None,
        "wall_s": round(wall, 3),
        "tok_s": round(total / wall, 2),
        # CLIENT-side percentiles through the router; with a mid-run
        # kill these ARE the tail-under-crash-failover figures
        "ttft_p50_s": rnd(pctl(ttft, 50), 4),
        "ttft_p99_s": rnd(pctl(ttft, 99), 4),
        "itl_p50_s": rnd(pctl(itl, 50), 4),
        "itl_p99_s": rnd(pctl(itl, 99), 4),
        "itl_max_s": rnd(max(itl), 4) if itl else None,
        "http_200": statuses.count(200),
        "http_503": statuses.count(503),
        "http_other": len([s for s in statuses if s not in (200, 503)]),
        "incomplete": args.requests - len(ok),
        "failovers": counters["failovers"],
        "failover_exhausted": counters["failover_exhausted"],
        "affinity_hit_rate": round(hits / max(1, hits + fallbacks), 3),
        "affinity_hits": hits,
        "affinity_fallbacks": fallbacks,
        "per_replica_served": [r["served"] for r in per_replica],
        "per_replica_routed": [r["routed"] for r in per_replica],
        "restarts": sum(r["restarts"] for r in per_replica),
        "completed": report.completed,
        "failed": report.failed,
        "aborted_streams": report.aborted_streams,
        "drain_clean": report.clean,
        "replica_exit_codes": [r["exit_code"] for r in report.replicas],
    }
    return rec


def run_http(args, cfg, engine, prompts, lengths, arrivals):
    """Over-the-wire run: the front door owns the engine; this process
    plays the clients.  Latency is measured where the user feels it —
    at the socket — so ttft/itl here include HTTP framing, the asyncio
    tick loop, and scheduling, on top of the engine's own numbers."""
    import http.client
    import threading

    from repro.serve.frontdoor import FrontDoor

    engine.reset_clock()
    engine.reset_stats()
    fd = FrontDoor(engine, drain_timeout_s=args.drain_timeout_s)
    fd.start_in_thread()
    results = [None] * args.requests
    t0 = time.perf_counter()

    def client(i):
        results[i] = _measured_client(
            fd.port, prompts[i][: lengths[i]], args.gen, t0, arrivals[i])

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.requests)]
    for t in threads:
        t.start()

    burst_statuses = []
    if args.overload_burst:
        # overload probe: a synchronized volley mid-run — every response
        # must be a typed verdict (200 admitted, 429/413 shed), never a
        # connection error
        mid = float(arrivals[len(arrivals) // 2])
        lock = threading.Lock()

        def burst():
            delay = t0 + mid - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            body = json.dumps({
                "prompt": [int(t) for t in prompts[0][:8]],
                "max_new": 2, "stream": False, "tenant": "burst",
            })
            c = http.client.HTTPConnection(
                "127.0.0.1", fd.port, timeout=300)
            try:
                c.request("POST", "/v1/generate", body,
                          {"Content-Type": "application/json"})
                r = c.getresponse()
                r.read()
                with lock:
                    burst_statuses.append(r.status)
            finally:
                c.close()

        bts = [threading.Thread(target=burst, daemon=True)
               for _ in range(args.overload_burst)]
        for t in bts:
            t.start()
        threads += bts

    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    report = fd.drain_and_join(reason="bench_complete")

    ok = [r for r in results if r and r["status"] == 200 and r["done"]]
    ttft = [r["token_times"][0] - r["t_due"]
            for r in ok if r["token_times"]]
    itl = [b - a for r in ok
           for a, b in zip(r["token_times"], r["token_times"][1:])]
    statuses = [r["status"] for r in results if r] + burst_statuses
    total = sum(r["done"]["n_tokens"] for r in ok)
    m = engine.metrics

    def count(name):
        return m.counter(name).value

    rec = {
        "label": ("quip-%db" % args.bits) if args.quantize else "fp",
        "arch": cfg.name,
        "mode": "http",
        "transport": "http-sse",
        "decode_path": "paged" if args.paged else "gather-dense",
        "requests": args.requests,
        "rate_req_s": args.rate,
        "max_queue": args.max_queue,
        "overload_burst": args.overload_burst,
        "wall_s": round(wall, 3),
        "tok_s": round(total / wall, 2),
        # CLIENT-side percentiles, measured at the socket
        "ttft_p50_s": rnd(pctl(ttft, 50), 4),
        "ttft_p99_s": rnd(pctl(ttft, 99), 4),
        "itl_p50_s": rnd(pctl(itl, 50), 4),
        "itl_p99_s": rnd(pctl(itl, 99), 4),
        "http_200": statuses.count(200),
        "http_429": statuses.count(429),
        "http_413": statuses.count(413),
        "http_other": len([s for s in statuses
                           if s not in (200, 429, 413)]),
        "shed_requests": count("shed_requests"),
        "ladder_escalations": count("ladder_escalations"),
        "ladder_deescalations": count("ladder_deescalations"),
        "client_disconnects": count("client_disconnects"),
        "drain_clean": report.clean,
        "leaked_pages": report.leaked_pages,
        "served_total": report.served_total,
        "peak_kv_pages": engine.pool.peak_pages_in_use,
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=20.0, help="arrivals/s")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=None)
    ap.add_argument("--token-budget", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--paged", action="store_true",
                    help="decode in place over the page pool (no per-step "
                         "dense KV gather)")
    ap.add_argument("--paged-prefill", action="store_true",
                    help="batch each tick's prefill chunks into one fused "
                         "cross-request dispatch over the page pool")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="map cached prompt-prefix pages on admission "
                         "(refcounted, copy-on-write) instead of "
                         "recomputing them")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="prefix-heavy workload: every prompt opens with "
                         "the same N-token header")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV pages with per-(token, head) scales")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decode depth (needs --paged): draft "
                         "up to K tokens per lane per tick, verify in one "
                         "fused dispatch")
    ap.add_argument("--draft", default="ngram", choices=("ngram",),
                    help="self-drafter for --speculative")
    ap.add_argument("--host-sample", action="store_true",
                    help="host-side token selection (default on the paged "
                         "path is the fused on-device draw)")
    ap.add_argument("--trace", action="store_true",
                    help="record per-tick spans during the measured run "
                         "and write the span-derived per-phase time "
                         "breakdown (schedule/prefill/decode/verify) "
                         "into the record")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="with --trace: also write the Chrome/Perfetto "
                         "trace-event JSON here")
    ap.add_argument("--cancel-rate", type=float, default=0.0, metavar="F",
                    help="cancel this fraction of the measured requests "
                         "at seeded deterministic ticks mid-run: the "
                         "latency percentiles then measure the tail "
                         "UNDER cancellation churn")
    ap.add_argument("--deadline-s", type=float, default=None, metavar="SECS",
                    help="per-request wall-clock deadline enforced at tick "
                         "boundaries; missed deadlines FAIL the request "
                         "(deadline_missed in the record)")
    ap.add_argument("--http", action="store_true",
                    help="over-the-wire mode: serve through the streaming "
                         "front door (serve/frontdoor) and measure CLIENT-"
                         "side SSE latency — ttft/itl include HTTP framing, "
                         "the asyncio tick loop, and the socket")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue (submits past it get "
                         "429 queue_full over HTTP)")
    ap.add_argument("--overload-burst", type=int, default=0, metavar="N",
                    help="with --http: fire N extra concurrent requests "
                         "mid-run and record the 200/429/413 admission "
                         "split — overload must shed, never crash")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="with --http: serve through N data-parallel "
                         "replica SUBPROCESSES behind the supervised "
                         "fleet router (serve/fleet) instead of one in-"
                         "process front door; the record gains per-"
                         "replica balance, affinity hit rate and "
                         "failover counts (BENCH_fleet.json by default)")
    ap.add_argument("--kill-mid-run", action="store_true",
                    help="with --fleet: SIGKILL the busiest replica at "
                         "the workload midpoint — ttft/itl then measure "
                         "the tail UNDER crash failover, and the record "
                         "carries failovers / restarts / recovery")
    ap.add_argument("--drain-timeout-s", type=float, default=10.0,
                    help="with --http: graceful-drain budget at shutdown")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    if args.speculative and not args.paged:
        ap.error("--speculative verifies drafts over the paged pool; "
                 "add --paged")
    if not 0.0 <= args.cancel_rate <= 1.0:
        ap.error("--cancel-rate is a fraction in [0, 1]")
    if args.http and (args.cancel_rate or args.trace):
        ap.error("--http measures the wire path; --cancel-rate/--trace "
                 "are in-process-run features")
    if args.overload_burst and not args.http:
        ap.error("--overload-burst needs --http")
    if args.fleet is not None:
        if not args.http:
            ap.error("--fleet serves over the wire; add --http")
        if args.fleet < 1:
            ap.error("--fleet needs >= 1 replica")
        if args.overload_burst:
            ap.error("--overload-burst probes the single front door's "
                     "admission ladder; drop --fleet")
        if args.kill_mid_run and args.fleet < 2:
            ap.error("--kill-mid-run needs >= 2 replicas to fail over to")
        if args.out == "BENCH_serving.json":
            args.out = "BENCH_fleet.json"
    elif args.kill_mid_run:
        ap.error("--kill-mid-run kills a fleet replica; add --fleet N")

    if not args.smoke:
        ap.error("this benchmark builds a float model with Model.init, "
                 "which only fits at the smoke widths; pass --smoke")
    cfg = get_smoke_config(args.arch)
    if args.fleet is not None:
        # fleet parent never builds a model — each replica subprocess
        # builds its own (same seed, identical weights); this process
        # only generates the workload and plays the clients
        rng = np.random.default_rng(args.seed)
        arrivals = np.cumsum(
            rng.exponential(1.0 / args.rate, args.requests))
        prompts = make_calibration(
            cfg.vocab, n_segments=args.requests, seg_len=args.prompt_len,
            seed=args.seed + 3,
        ).tokens
        lengths = rng.integers(
            max(4, args.prompt_len // 2), args.prompt_len + 1,
            args.requests)
        if args.prefix_len:
            header = prompts[0][: min(args.prefix_len,
                                      args.prompt_len - 1)]
            lengths = np.maximum(lengths, len(header) + 1)
            prompts = np.concatenate(
                [np.tile(header, (args.requests, 1)),
                 prompts[:, len(header):]], axis=1)
        rec = run_fleet(args, cfg, prompts, lengths, arrivals)
        print(json.dumps(rec, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f)
        # the run fails if any leak gate tripped or any admitted
        # stream was lost (failover exists precisely so it isn't)
        return 0 if rec["drain_clean"] and rec["incomplete"] == 0 else 1
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.quantize:
        from repro.launch.quantize import quantize_dense_model

        calib = make_calibration(cfg.vocab, n_segments=8, seg_len=64,
                                 seed=args.seed + 7)
        adapter = CachedDecoder.from_quantized(quantize_dense_model(
            params, cfg,
            QuipConfig(bits=args.bits, method="ldlq", use_kernel=False),
            calib.tokens, seed=args.seed, verbose=False,
        ))
    else:
        adapter = CachedDecoder.from_model(model, params)

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    prompts = make_calibration(
        cfg.vocab, n_segments=args.requests, seg_len=args.prompt_len,
        seed=args.seed + 3,
    ).tokens

    engine = Engine(adapter, EngineConfig(
        max_seq_len=args.prompt_len + args.gen,
        n_slots=args.slots,
        page_size=args.page_size,
        n_pages=args.pages,
        token_budget=args.token_budget,
        prefill_chunk=args.prefill_chunk,
        paged_decode=args.paged,
        paged_prefill=args.paged_prefill,
        prefix_cache=args.prefix_cache,
        kv_int8=args.kv_int8,
        speculative_k=args.speculative,
        draft=args.draft,
        device_sample=args.paged and not args.host_sample,
        deadline_s=args.deadline_s,
        max_queue=args.max_queue,
    ))
    # warm the jit caches so compile time doesn't pollute latency stats
    warm = engine.submit(np.asarray(prompts[0]), max_new=2, arrival=0.0)
    engine.run()
    assert warm.done

    # jitter prompt lengths so prefill chunking/page claims are ragged
    lengths = rng.integers(
        max(4, args.prompt_len // 2), args.prompt_len + 1, args.requests
    )
    if args.prefix_len:
        # prefix-heavy workload: one shared header, per-request tails
        header = prompts[0][: min(args.prefix_len, args.prompt_len - 1)]
        lengths = np.maximum(lengths, len(header) + 1)
        prompts = np.concatenate(
            [np.tile(header, (args.requests, 1)), prompts[:, len(header):]],
            axis=1,
        )
    if args.http:
        rec = run_http(args, cfg, engine, prompts, lengths, arrivals)
        print(json.dumps(rec, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f)
        return 0

    reqs = [
        engine.submit(np.asarray(prompts[i][: lengths[i]]), max_new=args.gen,
                      arrival=float(arrivals[i]))
        for i in range(args.requests)
    ]
    if args.cancel_rate:
        # seeded cancellation schedule: rids exist only after submission,
        # so the rules are armed on the engine's (inert) default plan
        from repro.serve.faults import FaultRule

        n_cancel = int(round(args.cancel_rate * args.requests))
        victims = rng.choice(args.requests, size=n_cancel, replace=False)
        for v in sorted(int(v) for v in victims):
            engine.faults.rules.append(FaultRule(
                kind="cancel", rid=reqs[v].rid,
                # steps counter restarts at 0 with reset_stats below, so
                # these ticks land inside the measured run
                tick=int(rng.integers(1, 2 * args.gen)),
            ))
    tracer = None
    if args.trace:  # attach AFTER warm-up: the trace covers only the
        from repro.serve import Tracer  # measured run, not compilation

        tracer = Tracer()
        engine.attach_tracer(tracer)
    engine.reset_clock()  # compile time and warm-up stats stay out of
    engine.reset_stats()  # the measured run
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0

    # external latency computation from raw request timestamps — the
    # engine's own histograms (summary()'s ttft_s_*/itl_s_*) observe the
    # SAME (arrival, t_first, token_times) data at finish, so the two
    # must agree to float tolerance (checked below)
    from repro.serve import RequestState

    fin = [r for r in done if r.state is RequestState.FINISHED]
    ttft = [r.t_first - r.arrival for r in fin]
    itl = [
        b - a
        for r in fin
        for a, b in zip(r.token_times, r.token_times[1:])
    ]
    total = sum(len(r.out_tokens) for r in done)
    s = engine.summary()
    for name, ext in (("ttft_s", ttft), ("itl_s", itl)):
        for q in (50, 99):
            eng_v, ext_v = s[f"{name}_p{q}"], pctl(ext, q)
            if (eng_v is None) != (ext_v is None) or (
                eng_v is not None
                and not np.isclose(eng_v, ext_v, rtol=1e-9, atol=1e-9)
            ):
                raise AssertionError(
                    f"engine-native {name}_p{q} {eng_v!r} diverged from "
                    f"the external computation {ext_v!r}"
                )
    rec = {
        "label": ("quip-%db" % args.bits) if args.quantize else "fp",
        "arch": cfg.name,
        "decode_path": "paged" if args.paged else "gather-dense",
        "prefill_path": "paged-batch" if args.paged_prefill else "dense-b1",
        "prefix_cache": bool(args.prefix_cache),
        "prefix_len": args.prefix_len,
        "kv_pages": "int8" if args.kv_int8 else "fp",
        "requests": args.requests,
        "rate_req_s": args.rate,
        "wall_s": round(wall, 3),
        "tok_s": round(total / wall, 2),
        # engine-native lifecycle percentiles (summary() histograms);
        # asserted equal to the external computation above
        "ttft_p50_s": rnd(s["ttft_s_p50"], 4),
        "ttft_p99_s": rnd(s["ttft_s_p99"], 4),
        "itl_p50_s": rnd(s["itl_s_p50"], 4),
        "itl_p99_s": rnd(s["itl_s_p99"], 4),
        "queue_p50_s": rnd(s["queue_s_p50"], 4),
        "queue_p99_s": rnd(s["queue_s_p99"], 4),
        "e2e_p50_s": rnd(s["e2e_s_p50"], 4),
        "peak_kv_pages": s["peak_pages_in_use"],
        "peak_kv_occupancy": round(s["peak_occupancy"], 3),
        "evictions": s["evictions"],
        "engine_steps": s["steps"],
        "prefill_batch_size": s["prefill_batch_size"],
        "prefix_hit_tokens": s["prefix_hit_tokens"],
        "cached_pages": s["cached_pages"],
        "shared_pages": s["shared_pages"],
        "max_page_ref": s["max_page_ref"],
        "cow_copies": s["cow_copies"],
        # robustness-under-churn (0 when --cancel-rate/--deadline-s off);
        # with cancel_rate > 0 the ttft/itl p99 above ARE the
        # p99-under-cancellation figures
        "cancel_rate": args.cancel_rate,
        "deadline_s": args.deadline_s,
        "cancelled": s["cancelled"],
        "failed": s["failed"],
        "deadline_missed": s["deadline_missed"],
        # speculative decode health (0 when --speculative is off)
        "speculative_k": args.speculative,
        "acceptance_rate": round(s["acceptance_rate"], 3),
        "accepted_per_tick": round(s["accepted_per_tick"], 3),
        "tokens_per_lane_tick": round(s["tokens_per_lane_tick"], 3),
        "rolled_back_tokens": s["rolled_back_tokens"],
    }
    if tracer is not None:
        from repro.serve import phase_breakdown

        pb = phase_breakdown(tracer.spans)
        rec["trace_spans"] = len(tracer)
        rec["trace_dropped"] = tracer.dropped
        rec["trace_coverage"] = round(pb["coverage"], 3)
        rec["phase_s"] = {
            name: round(p["time_s"], 4)
            for name, p in sorted(pb["phases"].items())
        }
        if args.trace_out:
            tracer.export_chrome_trace(args.trace_out)
    print(json.dumps(rec, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
