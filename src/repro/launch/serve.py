"""Serving driver: continuous-batching engine over paged KV caches.

    # quantize once, persist packed weights:
    PYTHONPATH=src python -m repro.launch.quantize --arch qwen3-14b --smoke \
        --bits 2 --out-dir /tmp/q

    # serve many concurrent requests from the artifact (no re-quantization):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
        --load-quantized /tmp/q --requests 6 --gen 16

Requests arrive staggered (``--arrival-gap``), join the decode batch while
earlier requests are mid-generation, and decode through the KV-cached
adapter — for quantized models that is the packed
``D⁻¹ → V → quant_matmul → Uᵀ`` path, NOT per-token prefix recompute.
``--paged`` decodes in place over the page pool (paged-attention kernel
path, no per-step dense KV gather); ``--paged-prefill`` additionally runs
each engine tick's prefill chunks as ONE batched cross-request dispatch
over the pool (chunked-prefill kernel path); ``--prefix-cache`` maps
previously-seen prompt-prefix pages (hash trie, refcounted copy-on-write)
into new requests instead of recomputing them; ``--kv-int8`` stores int8
KV pages.  ``--check`` verifies the engine's greedy tokens against the
recompute reference (or, for lossy int8 pages, against the gather-dense
engine oracle over the same page contents) — the oracle always runs the
dense path.

``--mesh DP,MP`` serves tensor-parallel over a (data, model) device mesh
(serve/distributed.py): packed weights shard column/row-parallel, the KV
page pool shards over KV heads, and paged decode runs under shard_map
with no cross-device KV traffic.  On CPU, force a multi-device host
first: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--speculative K`` (with ``--paged``) turns each decode tick into a
draft-and-verify tick: a host-side n-gram prompt-lookup drafter
(``--draft ngram``) proposes up to K tokens per lane from the lane's own
history, and ONE fused (B, K+1) dispatch — the chunked-prefill kernel
reused as the verifier — accepts each lane's longest matching prefix, so
a tick emits 1..K+1 tokens per lane for one weight pass.  Rejected
drafts' K/V is rolled back (``PagedKVPool.truncate``); greedy speculative
decode is token-identical to one-token decode, so it composes with
``--check``.

``--temperature``/``--top-p`` enable per-request nucleus sampling
(greedy when 0 — the default and the only ``--check`` mode);
``--stop-token`` (repeatable) finishes a request early on emission.  On
the paged path the softmax/top-p draw runs ON DEVICE, fused into the
decode/verify dispatch with per-request ``fold_in`` keys;
``--host-sample`` keeps the host-side numpy draw for debugging (the two
backends draw different — but each reproducible — non-greedy streams).

``--deadline-s`` / ``--max-queue`` / ``--screen-logits`` turn on the
robustness layer (DESIGN.md §12): per-request wall-clock deadlines
enforced at tick boundaries, bounded-queue admission backpressure, and a
per-lane NaN/Inf logit screen that quarantines a poisoned request without
touching its co-batched neighbours.  ``--fault-plan SPEC`` arms seeded
deterministic fault injection (serve/faults.py) for chaos drills — e.g.
``'alloc_fail@rid=0;nan_logits@rid=2;cancel@rid=4,tick=6'`` — and
composes with ``--check``: surviving requests must still match the
fault-free oracle token-for-token, early-terminated ones as an exact
prefix, and the run fails if any KV page leaks.

``--trace-out PATH`` records per-tick spans (step phases, fused
dispatches, request lifecycle events) into a ring buffer and writes a
Chrome/Perfetto trace-event JSON on exit; ``--trace-sync`` blocks on the
KV pool at span edges so durations measure device time rather than async
dispatch enqueue; ``--metrics-every SECS`` prints periodic one-line
metric snapshots to stderr (serve/telemetry.py — all off by default,
with a one-no-op-call hot-path cost when off).

Serving-quality canaries (DESIGN.md §13; serve/quality.py):
``--canary-every SECS`` runs a teacher-forced NLL probe over a pinned
canary prompt set through the dense reference trunk at that period (plus
once at run start) — out-of-band, the KV pool is untouched, so live
traffic stays token-identical; ``--shadow-rate F`` re-scores a
deterministic crc32-selected fraction of finished requests against the
same dense oracle and histograms max-abs-logit-diff / token-flip-rate.
``--quality-baseline PATH`` (with ``--load-quantized``) compares the
artifact's quality manifest against a stored baseline
(``launch/quality_report.py --write-baseline``) and warns on layers
whose proxy loss regressed beyond ``--quality-threshold``;
``--quality-strict`` refuses to serve instead.

``--fleet N`` (DESIGN.md §15; serve/fleet/) serves N data-parallel
replica processes behind one router on ``--router-port``: each replica
is this same CLI with the fleet flags stripped and an ephemeral
``--http-port`` appended, supervised with health probes (heartbeat +
tick-stall watchdog), exponential-backoff restarts and a give-up
circuit breaker.  The router balances by sticky prefix affinity with
least-loaded fallback, passes typed rejections through unchanged, and
journals every relayed token so a replica crash mid-stream fails over
to a survivor with a token-identical spliced continuation (greedy and
on-device-sampled paths).  ``--replica-fault IDX:SPEC`` arms a
fault plan on one replica's FIRST incarnation only — e.g.
``--replica-fault '1:replica_kill@tick=40'`` for a crash drill —
while a plain ``--fault-plan`` would re-arm on every respawn.
SIGTERM on the router runs the coordinated fleet drain (stop
admission, finish streams, drain every replica, aggregate leak
gates).
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.quantizer import QuipConfig
from repro.data import make_calibration
from repro.models import build_model

__all__ = ["greedy_generate", "quantized_generate", "build_engine", "main"]

# flags that configure the fleet parent (router + supervisor) and must
# NOT reach replica child processes; --http-port/--http-host are
# stripped too because the factory appends fresh ones per spawn
_FLEET_ONLY_FLAGS = frozenset((
    "--fleet", "--router-port", "--probe-interval-s", "--max-restarts",
    "--restart-backoff-s", "--replica-fault", "--http-port",
    "--http-host",
))


def _replica_argv(argv: list) -> list:
    """The replica child command tail: ``argv`` minus the fleet-only
    flags (handles both ``--flag value`` and ``--flag=value``).  Flags
    must be spelled out in full on a fleet command line — argparse
    prefix abbreviations would slip past this filter."""
    out, i = [], 0
    while i < len(argv):
        arg = argv[i]
        if arg.split("=", 1)[0] in _FLEET_ONLY_FLAGS:
            i += 1 if "=" in arg else 2
            continue
        out.append(arg)
        i += 1
    return out


def greedy_generate(model, params, prompt, gen: int, kv_dtype=None):
    """Reference fp path: Model.prefill + decode_step (dense batch cache)."""
    B, S = prompt.shape
    logits, cache = model.prefill(
        params, {"tokens": prompt}, kv_dtype=kv_dtype, max_len=S + gen
    )
    toks = [jnp.argmax(logits, -1)[:, None]]
    decode = jax.jit(model.decode_step)
    for i in range(gen - 1):
        logits, cache = decode(params, toks[-1], cache, jnp.int32(S + i))
        toks.append(jnp.argmax(logits, -1)[:, None])
    return jnp.concatenate(toks, axis=1)


def quantized_generate(qm, prompt, gen: int):
    """Reference recompute path: full-prefix quantized forward per token
    (O(S^2) per token — kept as the equivalence oracle for the engine's
    cached decode; see tests/test_serve.py).  Every linear runs the jnp
    dequantize-and-matmul path, never the Pallas kernel under test."""
    ref = qm.reference()
    toks = prompt
    for _ in range(gen):
        logits = ref.logits(toks)[:, -1]
        toks = jnp.concatenate([toks, jnp.argmax(logits, -1)[:, None]], axis=1)
    return toks[:, prompt.shape[1]:]


def _by_length(generate, prompts: list) -> list:
    """Run a batched reference ``generate(batch)`` once per distinct
    prompt length; returns one output row per prompt, in order."""
    out = [None] * len(prompts)
    for n in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        rows = np.asarray(generate(np.stack([prompts[i] for i in idx])))
        for i, row in zip(idx, rows):
            out[i] = row
    return out


def _prompt_lengths(spec: str) -> tuple:
    """``--prompt-len``: one length, or a comma list cycled over the
    requests (ragged prompts)."""
    try:
        lens = tuple(int(x) for x in spec.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected N or N,N,..., got {spec!r}")
    if min(lens) < 1:
        raise argparse.ArgumentTypeError(f"prompt lengths must be >= 1: {spec!r}")
    return lens


def build_engine(adapter, *, max_seq_len, args, paged=None,
                 paged_prefill=None, prefix_cache=None,
                 speculative=None, faults=None, robust=True,
                 tenants=None, record_logits=False) -> "Engine":
    from repro.serve import Engine, EngineConfig

    paged = getattr(args, "paged", False) if paged is None else paged
    ecfg = EngineConfig(
        tenants=tenants if robust else None,
        max_seq_len=max_seq_len,
        record_logits=record_logits,
        n_slots=args.slots,
        page_size=args.page_size,
        n_pages=args.pages,
        token_budget=args.token_budget,
        prefill_chunk=args.prefill_chunk,
        paged_decode=paged,
        paged_prefill=(
            getattr(args, "paged_prefill", False)
            if paged_prefill is None else paged_prefill
        ),
        prefix_cache=(
            getattr(args, "prefix_cache", False)
            if prefix_cache is None else prefix_cache
        ),
        kv_int8=getattr(args, "kv_int8", False),
        speculative_k=(
            getattr(args, "speculative", 0) if speculative is None
            else speculative
        ),
        draft=getattr(args, "draft", "ngram"),
        # the fused on-device draw is the paged-path default; --host-sample
        # keeps the host-side numpy draw for debugging
        device_sample=paged and not getattr(args, "host_sample", False),
        # robustness knobs stay off for reference oracles (robust=False):
        # an oracle must finish every request even under a chaos drill
        deadline_s=getattr(args, "deadline_s", None) if robust else None,
        max_queue=getattr(args, "max_queue", None) if robust else None,
        screen_logits=(
            getattr(args, "screen_logits", False) if robust else False
        ),
        # quality canaries follow the robustness gating: a --check oracle
        # must stay a bare reference run (no probes, no shadow re-scores)
        canary_every=getattr(args, "canary_every", None) if robust else None,
        shadow_rate=getattr(args, "shadow_rate", 0.0) if robust else 0.0,
        shadow_seed=getattr(args, "seed", 0),
    )
    return Engine(adapter, ecfg, faults=faults if robust else None)


def _serve_batch_fallback(model, params, prompts, args) -> int:
    """Non-dense families: the engine adapter is dense-only for now
    (ROADMAP open item); serve one fixed batch through the family's own
    Model.prefill/decode_step path, as the pre-engine driver did."""
    t0 = time.time()
    out = greedy_generate(model, params, prompts, args.gen)
    dt = time.time() - t0
    total = out.shape[0] * out.shape[1]
    print(f"[serve] fp {model.cfg.name} (batch fallback, family="
          f"{model.cfg.family}): {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s)")
    return 0


def main(argv=None, *, on_done=None):
    """CLI entry.  ``on_done(engine, submitted)``, when given, is called
    after the fixed (non-HTTP) workload ran, with the engine (built to
    keep every emission's logits) and the accepted ``(prompt index,
    request)`` pairs — for in-process callers that check numerics."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6,
                    help="number of concurrent requests to serve")
    ap.add_argument("--prompt-len", type=_prompt_lengths, default=(32,),
                    metavar="N[,N...]",
                    help="prompt tokens per request; a comma list is "
                         "cycled over the requests (ragged prompts)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--arrival-gap", type=float, default=0.02,
                    help="stagger between request arrivals (s)")
    # weights
    ap.add_argument("--quantize", action="store_true",
                    help="run the QuIP pipeline in-process before serving")
    ap.add_argument("--load-quantized", default=None, metavar="DIR",
                    help="serve packed weights from a quantize.py --out-dir "
                         "artifact (skips the quantization pipeline)")
    ap.add_argument("--bits", type=int, default=2)
    # engine knobs
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=None,
                    help="physical KV pages (default: no overcommit)")
    ap.add_argument("--token-budget", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--paged", action="store_true",
                    help="decode in place over the page pool (paged-"
                         "attention kernel path; no per-step dense KV "
                         "gather) instead of the gather-dense oracle")
    ap.add_argument("--paged-prefill", action="store_true",
                    help="prefill as ONE batched cross-request dispatch "
                         "per engine tick over the page pool (chunked-"
                         "prefill kernel path) instead of a B=1 "
                         "gather-dense loop")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="hash-trie prompt-prefix cache over full KV "
                         "pages: identical prompt prefixes are admitted "
                         "with their pages mapped (refcounted, copy-on-"
                         "write), not recomputed")
    ap.add_argument("--kv-int8", action="store_true",
                    help="store KV pages int8 with per-(token, head) scales")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decode (needs --paged): draft up to "
                         "K tokens per lane per tick and verify them in "
                         "ONE fused (B, K+1) dispatch — the chunked-"
                         "prefill kernel as verifier; rejected drafts' "
                         "K/V is rolled back")
    ap.add_argument("--draft", default="ngram", choices=("ngram",),
                    help="self-drafter for --speculative (ngram = prompt-"
                         "lookup over each lane's own token history)")
    ap.add_argument("--host-sample", action="store_true",
                    help="keep the host-side numpy softmax/top-p draw "
                         "(debugging); default on the paged path is the "
                         "on-device draw fused into the decode/verify "
                         "dispatch (per-request fold_in keys)")
    ap.add_argument("--mesh", default=None, metavar="DP,MP",
                    help="serve tensor-parallel over a (data, model) mesh: "
                         "packed weights + KV page pool + paged decode all "
                         "shard over the model axis (serve/distributed.py)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature > 0)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="per-request sampling seed base")
    ap.add_argument("--stop-token", type=int, action="append", default=None,
                    help="finish a request when it emits this token "
                         "(repeatable)")
    ap.add_argument("--check", action="store_true",
                    help="verify engine tokens against the recompute path")
    # failure domains (DESIGN.md §12; all off by default)
    ap.add_argument("--deadline-s", type=float, default=None, metavar="SECS",
                    help="per-request wall-clock deadline from arrival, "
                         "enforced at tick boundaries; an expired request "
                         "FAILS with finish_reason='deadline'")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="bounded admission queue: submits past N pending "
                         "requests raise a retryable AdmissionRejected "
                         "instead of queueing unboundedly")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection for chaos drills: "
                         "'kind[@key=val,...][;rule...]' with kinds "
                         "alloc_fail|pool_exhausted|nan_logits|"
                         "dispatch_error|corrupt_shard|cancel and keys "
                         "tick/rid/shard/times, e.g. "
                         "'alloc_fail@rid=0;cancel@rid=4,tick=6'")
    # streaming front door (DESIGN.md §14; serve/frontdoor/)
    ap.add_argument("--http-port", type=int, default=None, metavar="PORT",
                    help="serve over HTTP/SSE instead of the fixed batch: "
                         "start the asyncio front door on this port (0 = "
                         "ephemeral), POST /v1/generate + healthz/readyz/"
                         "metricsz; SIGTERM/SIGINT drain gracefully")
    ap.add_argument("--http-host", default="127.0.0.1",
                    help="front-door bind address (default 127.0.0.1)")
    ap.add_argument("--tenants", default=None, metavar="SPEC",
                    help="per-tenant admission policies, comma-separated "
                         "'name:rate:burst:priority' (rate in req/s, empty "
                         "or 'inf' = unlimited; priority 0 = highest), "
                         "e.g. 'paid:inf:4:0,free:2.0:4:1'")
    ap.add_argument("--drain-timeout-s", type=float, default=5.0,
                    metavar="SECS",
                    help="graceful-drain budget: in-flight lanes past this "
                         "get cancelled (pages still released exactly)")
    ap.add_argument("--tick-stall-s", type=float, default=10.0,
                    metavar="SECS",
                    help="tick-stall watchdog threshold: /healthz flips "
                         "to 503 'wedged' when the engine has not "
                         "COMPLETED a tick in this long (the supervisor "
                         "hard-restarts wedged replicas)")
    # replica fleet (DESIGN.md §15; serve/fleet/)
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="serve N data-parallel replica processes (each "
                         "this CLI + an ephemeral --http-port) behind "
                         "one supervised router; implies HTTP serving")
    ap.add_argument("--router-port", type=int, default=0, metavar="PORT",
                    help="fleet router bind port (0 = ephemeral; with "
                         "--fleet)")
    ap.add_argument("--probe-interval-s", type=float, default=0.5,
                    metavar="SECS",
                    help="supervisor health-probe period (with --fleet)")
    ap.add_argument("--max-restarts", type=int, default=3, metavar="N",
                    help="give-up circuit breaker: park a replica slot "
                         "as 'gone' after N restarts (with --fleet)")
    ap.add_argument("--restart-backoff-s", type=float, default=0.5,
                    metavar="SECS",
                    help="base restart backoff, doubling per restart "
                         "(with --fleet)")
    ap.add_argument("--replica-fault", action="append", default=None,
                    metavar="IDX:SPEC",
                    help="arm a --fault-plan SPEC on replica IDX's FIRST "
                         "incarnation only (repeatable; with --fleet) — "
                         "e.g. '1:replica_kill@tick=40' for a crash "
                         "drill whose respawn comes back clean")
    ap.add_argument("--no-ladder", action="store_true",
                    help="disable the load-shedding degradation ladder "
                         "(spec K shrink -> spec off -> shed lowest class)")
    ap.add_argument("--screen-logits", action="store_true",
                    help="NaN/Inf-screen every step's logits per lane "
                         "(one fused device reduction); a poisoned lane "
                         "is quarantined (FAILS with "
                         "finish_reason='nan_logits'), co-batched lanes "
                         "decode on unharmed")
    # telemetry (serve/telemetry.py; off by default — NULL_TRACER costs
    # one no-op call per span site)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-tick spans (step phases + fused "
                         "dispatches + request lifecycle events) and write "
                         "a Chrome/Perfetto trace-event JSON here "
                         "(load at ui.perfetto.dev)")
    ap.add_argument("--trace-sync", action="store_true",
                    help="block on the KV pool at span edges so span "
                         "durations measure device time, not async "
                         "dispatch enqueue (needs --trace-out; slows "
                         "serving — measurement mode only)")
    ap.add_argument("--metrics-every", type=float, default=None,
                    metavar="SECS",
                    help="print a one-line metrics snapshot (throughput "
                         "counters, pool occupancy, TTFT/ITL/e2e p50+p99) "
                         "to stderr every SECS seconds of engine time")
    # serving-quality canaries (DESIGN.md §13; serve/quality.py)
    ap.add_argument("--canary-every", type=float, default=None,
                    metavar="SECS",
                    help="teacher-forced NLL probe over a pinned canary "
                         "prompt set every SECS seconds (plus once at run "
                         "start) — out-of-band over the dense reference "
                         "trunk, live traffic stays token-identical")
    ap.add_argument("--canary-prompts", type=int, default=2,
                    help="canary set size (pinned sequences per probe)")
    ap.add_argument("--canary-len", type=int, default=16,
                    help="canary sequence length (tokens)")
    ap.add_argument("--shadow-rate", type=float, default=0.0, metavar="F",
                    help="re-score this deterministic fraction of finished "
                         "requests against the dense oracle trunk "
                         "(max-abs-logit-diff + token-flip-rate "
                         "histograms; crc32 selection, not hash())")
    ap.add_argument("--quality-baseline", default=None, metavar="PATH",
                    help="with --load-quantized: compare the artifact's "
                         "quality manifest against this baseline JSON "
                         "(launch/quality_report.py --write-baseline) and "
                         "warn on proxy-loss regressions")
    ap.add_argument("--quality-threshold", type=float, default=1.2,
                    help="regression ratio for --quality-baseline "
                         "(default 1.2x)")
    ap.add_argument("--quality-strict", action="store_true",
                    help="refuse to serve (exit nonzero) on any "
                         "--quality-baseline regression instead of warning")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    from repro.serve import CachedDecoder, DistributedCachedDecoder, \
        make_serving_mesh
    from repro.serve.artifacts import ArtifactCorruption, load_quantized
    from repro.serve.faults import AdmissionRejected, parse_fault_plan
    from repro.serve.scheduler import RequestState, SamplingParams

    faults = None
    if args.fault_plan:
        try:
            faults = parse_fault_plan(args.fault_plan)
        except ValueError as e:
            raise SystemExit(f"--fault-plan: {e}")

    tenants = None
    if args.tenants:
        from repro.serve.frontdoor.admission import parse_tenants

        try:
            tenants = parse_tenants(args.tenants)
        except ValueError as e:
            raise SystemExit(f"--tenants: {e}")
    if args.http_port is not None and args.check:
        raise SystemExit(
            "--check drives a fixed in-process workload; the HTTP front "
            "door serves whatever clients send — drop one of the two"
        )
    if args.speculative and not args.paged:
        raise SystemExit(
            "--speculative verifies drafts over the paged pool (the "
            "chunked-prefill kernel path); add --paged"
        )
    if args.speculative < 0:
        raise SystemExit(f"--speculative must be >= 0, got {args.speculative}")
    if args.temperature == 0 and args.top_p < 1.0:
        raise SystemExit(
            "--top-p only applies to non-greedy decoding; pass "
            "--temperature > 0 (temperature 0 is exact greedy argmax)"
        )
    if args.check and args.temperature > 0:
        raise SystemExit(
            "--check verifies greedy tokens against a greedy oracle; "
            "drop --temperature (or --check)"
        )
    if args.check and args.stop_token:
        raise SystemExit(
            "--check compares full fixed-length token streams; the "
            "references don't model early stop — drop --stop-token"
        )
    if args.trace_sync and not args.trace_out:
        raise SystemExit(
            "--trace-sync sharpens span timing for a recorded trace; "
            "add --trace-out PATH"
        )
    if not 0.0 <= args.shadow_rate <= 1.0:
        raise SystemExit(
            f"--shadow-rate must be in [0, 1], got {args.shadow_rate}"
        )
    if args.canary_every is not None and args.canary_every <= 0:
        raise SystemExit(
            f"--canary-every must be > 0 seconds, got {args.canary_every}"
        )
    if args.quality_baseline and not args.load_quantized:
        raise SystemExit(
            "--quality-baseline audits an artifact's quality manifest; "
            "add --load-quantized DIR (quantize with --out-dir first)"
        )
    if args.quality_strict and not args.quality_baseline:
        raise SystemExit(
            "--quality-strict needs a baseline to enforce; add "
            "--quality-baseline PATH"
        )
    if args.fleet is None:
        for flag, val, default in (
                ("--router-port", args.router_port, 0),
                ("--replica-fault", args.replica_fault, None)):
            if val != default:
                raise SystemExit(f"{flag} only applies to a replica "
                                 f"fleet; add --fleet N")
    else:
        if args.fleet < 1:
            raise SystemExit(f"--fleet needs >= 1 replica, "
                             f"got {args.fleet}")
        if args.check:
            raise SystemExit(
                "--check drives a fixed in-process workload; --fleet "
                "serves HTTP replicas — drop one of the two"
            )
        if args.http_port is not None:
            raise SystemExit(
                "--fleet assigns each replica its own ephemeral "
                "--http-port; use --router-port for the client-facing "
                "port"
            )
    if args.fleet is not None:
        # fleet parent: never builds a model — it spawns N replica
        # copies of this CLI (fleet flags stripped, fresh --http-port
        # appended per spawn) and serves the router in front of them
        import asyncio

        from repro.serve.fleet import (
            FleetRouter,
            ProcessReplicaFactory,
            Supervisor,
        )

        first_spawn: dict[int, list] = {}
        for spec in args.replica_fault or ():
            idx_s, sep, plan = spec.partition(":")
            if not sep or not idx_s.isdigit():
                raise SystemExit(
                    f"--replica-fault expects IDX:SPEC, got {spec!r}")
            idx = int(idx_s)
            if not 0 <= idx < args.fleet:
                raise SystemExit(
                    f"--replica-fault: replica {idx} out of range for "
                    f"--fleet {args.fleet}")
            try:  # validate here, where the error is attributable
                parse_fault_plan(plan)
            except ValueError as e:
                raise SystemExit(f"--replica-fault {spec!r}: {e}")
            first_spawn.setdefault(idx, []).extend(
                ["--fault-plan", plan])
        tail = _replica_argv(
            list(argv) if argv is not None else sys.argv[1:])
        factory = ProcessReplicaFactory(
            [sys.executable, "-m", "repro.launch.serve", *tail],
            host=args.http_host, first_spawn_args=first_spawn,
        )
        try:
            sup = Supervisor(
                factory, args.fleet, host=args.http_host,
                probe_interval_s=args.probe_interval_s,
                max_restarts=args.max_restarts,
                backoff_base_s=args.restart_backoff_s,
                replica_drain_timeout_s=args.drain_timeout_s + 30.0,
            )
        except ValueError as e:
            raise SystemExit(f"--fleet: {e}")
        router = FleetRouter(
            sup, host=args.http_host, port=args.router_port,
            drain_timeout_s=args.drain_timeout_s,
        )
        report = asyncio.run(router.serve_forever())
        return report.exit_code
    mesh = None
    if args.mesh:
        try:
            dp, mp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh expects DP,MP (e.g. 1,2), "
                             f"got {args.mesh!r}")
        try:
            mesh = make_serving_mesh(dp, mp)
        except ValueError as e:
            raise SystemExit(f"--mesh: {e}")

    qm = None
    if args.load_quantized:
        try:
            if mesh is not None:
                # leaves stream straight onto their mesh placement
                adapter, meta = DistributedCachedDecoder.load(
                    args.load_quantized, mesh=mesh, load_faults=faults
                )
                cfg = adapter.cfg
                if args.check:  # plain copy for the single-device oracle
                    qm, _ = load_quantized(args.load_quantized)
            else:
                qm, meta = load_quantized(args.load_quantized, faults=faults)
                cfg = qm.cfg
                adapter = CachedDecoder.from_quantized(qm)
        except ArtifactCorruption as e:
            # integrity failure is its own domain: the artifact EXISTS but
            # its bytes don't match the manifest — don't suggest re-pathing
            raise SystemExit(f"--load-quantized: {e}")
        except (FileNotFoundError, ValueError, KeyError) as e:
            raise SystemExit(
                f"--load-quantized: {e} (expected a directory written by "
                f"launch/quantize.py --out-dir)"
            )
        label = f"quip-{meta['quip_config']['bits']}bit[artifact]"
        print(f"[serve] loaded quantized artifact: {cfg.name} "
              f"{meta['quip_config']['bits']}-bit ({args.load_quantized}) "
              f"on {jax.local_devices()}")
        if args.quality_baseline:
            from repro.serve.quality import check_artifact_quality, \
                load_baseline

            try:
                baseline = load_baseline(args.quality_baseline)
            except (FileNotFoundError, ValueError) as e:
                raise SystemExit(f"--quality-baseline: {e}")
            regressions = check_artifact_quality(
                meta.get("quality"), baseline,
                threshold=args.quality_threshold,
            )
            for r in regressions:
                print(f"[serve] QUALITY REGRESSION {r['layer']}: "
                      f"proxy {r['baseline']:.4g} -> "
                      f"{'missing' if r['current'] is None else format(r['current'], '.4g')}"
                      f" (> {args.quality_threshold:.2f}x baseline)")
            if regressions and args.quality_strict:
                raise SystemExit(
                    f"refusing to serve: {len(regressions)} layer(s) "
                    f"regressed beyond {args.quality_threshold:.2f}x the "
                    f"quality baseline (drop --quality-strict to serve "
                    f"anyway)"
                )
            if not regressions:
                print(f"[serve] quality baseline OK "
                      f"({len(baseline['proxy_loss'])} layers within "
                      f"{args.quality_threshold:.2f}x)")
    else:
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(args.seed))
        if cfg.family != "dense" and mesh is not None:
            raise SystemExit(
                "--mesh drives the dense-family engine adapter; other "
                "families serve through the batch fallback (single device)"
            )
        if cfg.family != "dense":
            if args.quantize:
                raise SystemExit(
                    "--quantize drives the dense family; per-layer "
                    "quantization for other families goes through "
                    "repro.core.quantize_layer directly"
                )
            if args.check:
                raise SystemExit(
                    "--check verifies the engine against the reference "
                    "decode path, but non-dense families serve THROUGH "
                    "that reference path (engine adapter is dense-only; "
                    "ROADMAP open item) — nothing to check"
                )
            if len(set(args.prompt_len)) > 1:
                raise SystemExit(
                    "the batch fallback serves one fixed-shape batch; "
                    "give one --prompt-len"
                )
            prompts = make_calibration(
                cfg.vocab, n_segments=args.requests,
                seg_len=args.prompt_len[0], seed=args.seed + 3,
            ).tokens
            return _serve_batch_fallback(model, params, prompts, args)
        if args.quantize:
            from repro.launch.quantize import quantize_dense_model

            calib = make_calibration(cfg.vocab, n_segments=8, seg_len=64,
                                     seed=args.seed + 7)
            qcfg = QuipConfig(bits=args.bits, method="ldlq", use_kernel=False)
            qm = quantize_dense_model(params, cfg, qcfg, calib.tokens,
                                      seed=args.seed, verbose=False)
            adapter = (
                DistributedCachedDecoder.from_quantized(qm, mesh=mesh)
                if mesh is not None else CachedDecoder.from_quantized(qm)
            )
            label = f"quip-{args.bits}bit"
        else:
            adapter = (
                DistributedCachedDecoder.from_model(model, params, mesh=mesh)
                if mesh is not None else CachedDecoder.from_model(model, params)
            )
            label = "fp"

    max_prompt = max(args.prompt_len)
    calib = make_calibration(
        cfg.vocab, n_segments=args.requests, seg_len=max_prompt,
        seed=args.seed + 3,
    ).tokens
    prompts = [
        calib[i, : args.prompt_len[i % len(args.prompt_len)]]
        for i in range(args.requests)
    ]

    engine = build_engine(
        adapter, max_seq_len=max_prompt + args.gen, args=args,
        faults=faults, tenants=tenants, record_logits=on_done is not None,
    )
    if args.canary_every is not None:
        # pinned OFF the traffic seed stream: the canary set must stay
        # fixed across runs for the NLL gauge to be comparable
        engine.attach_canary(make_calibration(
            cfg.vocab, n_segments=args.canary_prompts,
            seg_len=args.canary_len, seed=args.seed + 1234,
        ).tokens)
    tracer = None
    if args.trace_out:
        from repro.serve import Tracer

        tracer = Tracer(sync=args.trace_sync)
        engine.attach_tracer(tracer)
    if mesh is not None:
        pool = engine.pool
        print(f"[serve] mesh data={dp} model={mp}: KV pool "
              f"{pool.total_bytes()} B total, {pool.device_bytes()} B/device")
    if args.http_port is not None:
        import asyncio

        from repro.serve.frontdoor import FrontDoor

        fd = FrontDoor(
            engine, host=args.http_host, port=args.http_port,
            drain_timeout_s=args.drain_timeout_s, ladder=not args.no_ladder,
            tick_stall_s=args.tick_stall_s,
        )
        report = asyncio.run(fd.serve_forever())
        s = engine.summary()
        for line in report.lines():
            print(f"[serve] {line}")
        fin = " ".join(
            f"{k}={v}" for k, v in sorted(s.items())
            if k.startswith("finish:")
        )
        if fin:
            print(f"[serve] finish reasons: {fin}")
        print(f"[serve] http: requests={s['http_requests']} "
              f"rejections={s['http_rejections']} "
              f"shed={s['shed_requests']} "
              f"disconnects={s['client_disconnects']} "
              f"ladder_escalations={s.get('ladder_escalations', 0)} "
              f"ladder_deescalations={s.get('ladder_deescalations', 0)}")
        if tracer is not None:
            tracer.export_chrome_trace(args.trace_out)
            print(f"[serve] trace: {len(tracer)} spans -> {args.trace_out}")
        return report.exit_code

    stop_tokens = tuple(args.stop_token or ())
    try:  # validate the sampling flags before the admission loop, so bad
        # values don't surface as a misleading pool-capacity error below
        sampling = [
            SamplingParams(temperature=args.temperature, top_p=args.top_p,
                           seed=args.sample_seed + i)
            for i in range(args.requests)
        ]
    except ValueError as e:
        raise SystemExit(f"bad sampling flags: {e}")
    submitted = []  # (prompt index, request) for accepted submissions
    for i in range(args.requests):
        try:
            req = engine.submit(
                np.asarray(prompts[i]), max_new=args.gen,
                arrival=i * args.arrival_gap,
                sampling=sampling[i],
                stop_tokens=stop_tokens,
            )
        except AdmissionRejected as e:
            if e.retryable:
                # bounded queue backpressure: a real client would retry
                # with backoff; the fixed-workload driver just reports it
                print(f"[serve] request {i} rejected (retryable): {e}")
                continue
            raise SystemExit(f"cannot admit request: {e} "
                             f"(grow --pages / --page-size or shrink --gen)")
        except ValueError as e:
            raise SystemExit(f"cannot admit request: {e}")
        submitted.append((i, req))
    t0 = time.perf_counter()
    interrupted = 0
    try:
        done = engine.run(metrics_every=args.metrics_every)
    except KeyboardInterrupt:
        # ^C is a drain request, not a crash: cancel every live lane
        # (pages released refcount-exactly) and fall through to the same
        # summary lines + leak gate a clean run prints
        interrupted = len(engine.cancel_all())
        done = engine.finished
    dt = time.perf_counter() - t0
    if interrupted:
        print(f"\n[serve] interrupted: drained {interrupted} in-flight "
              f"request(s) as cancelled")
    s = engine.summary()
    total = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {label} {cfg.name}: {len(done)} requests, {total} tokens "
          f"in {dt:.2f}s ({total/dt:.1f} tok/s)")
    n_fin = sum(1 for r in done if r.state is RequestState.FINISHED)
    n_can = sum(1 for r in done if r.state is RequestState.CANCELLED)
    n_fail = sum(1 for r in done if r.state is RequestState.FAILED)
    outcome = (f"[serve] outcomes: finished={n_fin} cancelled={n_can} "
               f"failed={n_fail}")
    if n_fail:
        reasons: dict[str, int] = {}
        for r in done:
            if r.state is RequestState.FAILED:
                reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
        outcome += f" reasons={reasons}"
    if n_can or n_fail or faults is not None:
        print(outcome)
    if faults is not None:
        print(f"[serve] faults injected: {len(faults.log)} "
              f"({'; '.join(e['kind'] for e in faults.log)})")
    # blast-radius invariant: whatever was cancelled/failed/injected, every
    # page must be back (the prefix trie legitimately retains its own refs)
    leaked = engine.pool.pages_in_use - engine.pool.cached_pages
    if leaked != 0 or engine.pool._slots:
        print(f"[serve] FAIL: {leaked} leaked pages, "
              f"{len(engine.pool._slots)} live slots after drain")
        return 1
    print(f"[serve] steps={s['steps']} prefill_tokens={s['prefill_tokens']} "
          f"decode_tokens={s['decode_tokens']} evictions={s['evictions']} "
          f"peak_kv_occupancy={s['peak_occupancy']:.0%}")
    if args.paged_prefill or args.prefix_cache:
        print(f"[serve] prefill_batch_size={s['prefill_batch_size']} "
              f"prefix_hit_tokens={s['prefix_hit_tokens']} "
              f"cached_pages={s['cached_pages']} "
              f"shared_pages={s['shared_pages']} "
              f"cow_copies={s['cow_copies']}")
    if args.speculative:
        print(f"[serve] speculative K={args.speculative}: "
              f"acceptance_rate={s['acceptance_rate']:.2f} "
              f"accepted_per_tick={s['accepted_per_tick']:.2f} "
              f"tokens_per_lane_tick={s['tokens_per_lane_tick']:.2f} "
              f"rolled_back={s['rolled_back_tokens']}")
    if s.get("ttft_s_p50") is not None:
        print(f"[serve] latency: ttft_p50={s['ttft_s_p50'] * 1e3:.1f}ms "
              f"ttft_p99={s['ttft_s_p99'] * 1e3:.1f}ms "
              f"itl_p50={(s['itl_s_p50'] or 0) * 1e3:.2f}ms "
              f"queue_p50={(s['queue_s_p50'] or 0) * 1e3:.1f}ms")
    if args.canary_every is not None:
        print(f"[serve] quality: canary_nll={s['canary_nll']:.6f} "
              f"canary_runs={s['canary_runs']} "
              f"act_absmax={s['act_absmax']:.3g} act_sat={s['act_sat']:.2e}")
    if args.shadow_rate > 0:
        print(f"[serve] shadow: samples={s['shadow_samples']} "
              f"tokens={s['shadow_tokens']} flips={s['shadow_token_flips']} "
              f"max_abs_logit_diff_p99="
              f"{s.get('shadow_max_abs_logit_diff_p99') or 0:.3g} "
              f"flip_rate_p99={s.get('shadow_flip_rate_p99') or 0:.3g}")
    if tracer is not None:
        from repro.serve import phase_breakdown

        tracer.export_chrome_trace(args.trace_out)
        pb = phase_breakdown(tracer.spans)
        phases = " ".join(
            f"{name}={p['time_s'] * 1e3:.0f}ms({p['share']:.0%})"
            for name, p in sorted(
                pb["phases"].items(), key=lambda kv: -kv[1]["time_s"]
            )
        )
        print(f"[serve] trace: {len(tracer)} spans -> {args.trace_out} "
              f"(dropped={tracer.dropped}) coverage={pb['coverage']:.0%} "
              f"{phases}")

    if on_done is not None:
        on_done(engine, submitted)
    if args.check:
        if args.kv_int8 and not (args.paged or args.paged_prefill):
            raise SystemExit(
                "--kv-int8 --check needs --paged (and/or --paged-prefill): "
                "int8 pages are lossy vs the dense references, so the only "
                "independent oracle is the gather-dense engine over the "
                "same int8 page contents — without a paged path that "
                "oracle IS the engine under test"
            )
        if args.kv_int8:
            # int8 pages are lossy vs the dense references; the oracle is
            # a gather-dense engine decoding the same int8 page contents —
            # always a SINGLE-DEVICE engine, so --mesh --kv-int8 --check
            # verifies TP against the unsharded implementation
            oracle_adapter = adapter
            if mesh is not None:
                oracle_adapter = (
                    CachedDecoder.from_quantized(qm) if qm is not None
                    else CachedDecoder.from_model(model, params)
                )
            oracle = build_engine(
                oracle_adapter, max_seq_len=max_prompt + args.gen,
                args=args, paged=False, paged_prefill=False,
                prefix_cache=False, speculative=0, robust=False,
            )
            oref = [
                oracle.submit(np.asarray(prompts[i]), max_new=args.gen)
                for i in range(args.requests)
            ]
            oracle.run()
            ref = np.stack([
                np.asarray(r.out_tokens, np.int32)
                for r in sorted(oref, key=lambda r: r.rid)
            ])
            ref_label = "gather-dense int8 engine"
        elif qm is not None:
            ref = _by_length(
                lambda b: quantized_generate(qm, jnp.asarray(b), args.gen),
                prompts,
            )
            ref_label = "quantized recompute"
        else:
            ref = _by_length(
                lambda b: greedy_generate(model, params, b, args.gen),
                prompts,
            )
            ref_label = "fp prefill/decode"
        # FINISHED rows must match the oracle token-for-token at full
        # length; CANCELLED/FAILED rows must be an exact PREFIX of it —
        # a fault may stop a request early but never corrupt its stream
        total_cmp = matched = 0
        truncated_ok = True
        for i, r in submitted:
            out = np.asarray(r.out_tokens, np.int32)
            exp = np.asarray(ref[i], np.int32)
            if r.state is RequestState.FINISHED:
                if out.size != exp.size:
                    truncated_ok = False
                    continue
            else:
                exp = exp[: out.size]
            total_cmp += exp.size
            matched += int(np.sum(out == exp))
        agree = matched / max(1, total_cmp)
        print(f"[serve] check vs {ref_label}: token agreement {agree:.2%} "
              f"over {total_cmp} tokens")
        if agree < 1.0 or not truncated_ok:
            print(f"[serve] FAIL: engine cached decode diverged from the "
                  f"{ref_label} oracle")
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
