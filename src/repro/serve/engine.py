"""Continuous-batching engine: per-step batch assembly over paged KV.

Each :meth:`Engine.step`:

  1. moves arrived requests into the FCFS queue;
  2. plans the step under the token budget (decode-prioritized, chunked
     prefill with leftover budget; admission claims pages);
  3. ensures every decode lane has a page for its next token, evicting the
     newest running sequence under page pressure (evicted requests requeue
     and later re-prefill their prompt + generated prefix);
  4. executes the step's prefill group — one batched paged dispatch over
     all planned chunks (``paged_prefill``), or a B=1 gather-dense loop
     (the oracle) — and one batched decode forward (fixed ``n_slots``
     lanes, per-lane positions), writing new K/V into the pool and
     appending tokens — greedy by default, or per-request temperature/
     top-p sampling with stop-token support
     (:class:`repro.serve.scheduler.SamplingParams`).

Decode runs one of two adapter paths:

  * gather-dense (default off, reference oracle): materialize every
    context page into a dense ``(L, B, Pmax*ps, KV, hd)`` window, forward,
    scatter new K/V back — an O(allocated pages) copy per emitted token;
  * **paged** (``EngineConfig.paged_decode`` / ``CachedDecoder.paged``):
    hand the adapter per-lane block tables + context lengths and let the
    paged-attention kernel read the pool in place; the new token's K/V is
    scattered inside the same jitted dispatch (donated buffers).  Block
    tables are bucketed to the next power of two of the *attended* page
    count, so step cost tracks live context, not allocation (a handful of
    compiles per pool geometry, reused across steps).

Prefill mirrors the decode split (``EngineConfig.paged_prefill``): the
oracle path re-gathers a dense context window per B=1 chunk, while the
paged path assembles every chunk the scheduler planned this tick into one
padded ``(B, C)`` cross-request batch — lanes bucketed to a power of two,
block tables bucketed to the longest prior context — and runs it as a
single fused dispatch with a donated in-place scatter.  With
``EngineConfig.prefix_cache`` the pool additionally maps full pages of
previously-seen prompt prefixes into newly admitted slots (refcounted,
copy-on-write), so shared system prompts/few-shot headers are admitted at
``prefill_pos > 0`` and never recomputed.

Speculative multi-token decode (``EngineConfig.speculative_k = K > 0``,
paged path only): each decode tick, a host-side self-drafter
(:mod:`repro.serve.drafter` — n-gram prompt lookup over the lane's own
history, no second model) proposes up to K tokens per lane, and ONE fused
padded ``(B, K+1)`` dispatch (``CachedDecoder.verify_paged`` — the
chunked-prefill kernel reused as the verifier) scores every lane's
``[last_emitted, d_1 .. d_K]`` chunk, selects a token per position on
device, and accepts each lane's longest matching draft prefix — so a tick
emits 1 to K+1 tokens per lane for one weight pass.  The dispatch
scatters all fed tokens' K/V; the rejected tail is un-written afterwards
via ``pool.truncate`` (refcount-aware rollback — COW already resolved any
shared page at write time).  Greedy speculative decode is token-identical
to the one-token paged path; accepted extras are charged against the NEXT
step's budget (``TokenBudgetFCFS.charge_accepted`` — rejected drafts are
never charged).

Sampling runs on device by default on the paged path
(``EngineConfig.device_sample``): the softmax/top-p draw is fused into
the decode/verify dispatch with per-request keys
``fold_in(PRNGKey(seed), emission_index)``, making sampled streams
reproducible across batching, eviction/replay, and speculative grouping.
The host-side draw (``launch/serve.py --host-sample``) is kept for
debugging; both are exact argmax at temperature 0.

All device calls are shape-static per bucket: new requests join mid-flight
without recompilation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.adapter import CachedDecoder, sample_tokens
from repro.serve.drafter import make_drafter
from repro.serve.faults import AdmissionRejected, FaultInjected, FaultPlan
from repro.serve.kv_cache import page_bucket, pages_needed
from repro.serve.quality import ShadowSampler, canary_probe
from repro.serve.scheduler import (
    Request,
    RequestState,
    SamplingParams,
    StepPlan,
    TokenBudgetFCFS,
)
from repro.serve.telemetry import (
    NULL_TRACER,
    MetricsRegistry,
    Tracer,
    compile_gauges,
    emit_metrics_line,
)

__all__ = ["Engine", "EngineConfig", "TickResult"]

# counters the engine bumps on the hot path, in reporting order; the
# legacy ``engine.stats`` mapping is a read view over exactly these
_STAT_COUNTERS = (
    "steps",
    "decode_tokens",
    "prefill_tokens",
    "evictions",
    "prefill_batches",
    "prefill_batch_size",  # widest co-batched prefill group seen
    "prefix_hit_tokens",  # prompt tokens admitted from the cache
    "spec_ticks",  # fused verify dispatches run
    "spec_lanes",  # lane-verifications (lanes summed over ticks)
    "draft_tokens",  # tokens the drafter proposed
    "accepted_tokens",  # proposed tokens the verifier accepted
    "rolled_back_tokens",  # rejected drafts un-written (truncate)
    "cancelled",  # requests reaching CANCELLED
    "failed",  # requests reaching FAILED (any reason)
    "deadline_missed",  # FAILED specifically for blowing deadline_s
    "decode_pages_live",  # pages real decode lanes hold (paged decode)
    "decode_pages_attended",  # lanes x block-table width dispatched
    "admission_rejected",  # submits refused with AdmissionRejected
    # ---- quality canaries (serve/quality.py, DESIGN.md §13) ----
    "canary_runs",  # out-of-band teacher-forced NLL probes run
    "shadow_samples",  # finished requests the drift sampler re-scored
    "shadow_tokens",  # emissions those samples covered
    "shadow_token_flips",  # emissions whose serving/oracle argmax differ
)


# device-cheap anomaly screen: ONE fused reduction over the step's logits
# produces a per-lane finite flag — the only thing shipped to the host is
# a (B,) bool, never the logits themselves
@jax.jit
def _lane_finite(logits):
    return jnp.all(jnp.isfinite(logits), axis=tuple(range(1, logits.ndim)))


@dataclasses.dataclass
class TickResult:
    """What one :meth:`Engine.tick` did — the contract between the pure
    tick function and whatever drives it (the front-door server, the
    in-process :func:`repro.serve.lifecycle.run_to_completion` loop, or
    a test).  ``emitted`` is every (request, token) emission of the tick
    in emission order; ``finished`` is every request that reached a
    terminal state since the previous tick's result was taken (including
    between-tick cancels)."""

    worked: bool
    t: float
    emitted: list  # [(Request, token), ...]
    finished: list  # [Request, ...] newly terminal


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_seq_len: int  # per-sequence token capacity (prompt + generation)
    n_slots: int = 8  # concurrent resident sequences (decode lanes)
    page_size: int = 16
    n_pages: Optional[int] = None  # default: no overcommit (+1 scratch)
    token_budget: int = 64  # tokens processed per step
    prefill_chunk: int = 32
    record_logits: bool = False  # keep per-emission logits (tests/--check)
    paged_decode: bool = False  # decode in place over the page pool
    paged_prefill: bool = False  # batched cross-request prefill over the pool
    prefix_cache: bool = False  # map cached prompt-prefix pages on admit
    kv_int8: bool = False  # int8 KV pages + per-(token, head) scales
    speculative_k: int = 0  # draft depth K (0 = one token per lane per tick)
    draft: str = "ngram"  # self-drafter kind (serve/drafter.py)
    draft_ngram: int = 3  # longest lookup pattern the ngram drafter tries
    device_sample: bool = False  # fuse the token draw into the paged dispatch
    # ---- failure domains (DESIGN.md §12) ----
    deadline_s: Optional[float] = None  # default per-request wall-clock
    #   deadline (from arrival), enforced at tick boundaries
    max_queue: Optional[int] = None  # bounded admission queue: submits past
    #   this many pending requests raise a retryable AdmissionRejected
    # ---- multi-tenant admission (serve/frontdoor, DESIGN.md §14) ----
    tenants: Optional[dict] = None  # tenant name -> scheduler.TenantPolicy
    #   (token-bucket rate limits + default priority class); None = every
    #   tenant unlimited at class 0 (exact legacy FCFS)
    aging_s: float = 2.0  # seconds of queue wait that promote a request
    #   one priority class (bounded-wait starvation freedom)
    max_evictions: Optional[int] = 8  # eviction-storm guard: a request
    #   evicted this many times FAILS ("eviction_storm") instead of
    #   replaying its prefix forever (None = legacy unbounded behavior)
    screen_logits: bool = False  # per-lane NaN/Inf screen on every step's
    #   logits; a poisoned lane is quarantined, co-batched lanes unharmed
    # ---- quality canaries (DESIGN.md §13; serve/quality.py) ----
    canary_every: Optional[float] = None  # seconds between teacher-forced
    #   NLL probes over the pinned canary set (attach_canary); one probe
    #   also fires at run start so the gauge exists from tick zero
    shadow_rate: float = 0.0  # fraction of requests re-scored against
    #   the dense oracle on finish (deterministic crc32 selection)
    shadow_seed: int = 0  # selection seed (stable across processes)

    @property
    def pages_per_seq(self) -> int:
        return pages_needed(self.max_seq_len, self.page_size)

    def total_pages(self) -> int:
        if self.n_pages is not None:
            return self.n_pages
        return self.n_slots * self.pages_per_seq + 1


class Engine:
    def __init__(self, adapter: CachedDecoder, ecfg: EngineConfig, dtype=None,
                 tracer: Optional[Tracer] = None,
                 faults: Optional[FaultPlan] = None):
        self.adapter = adapter
        self.ecfg = ecfg
        self.paged = ecfg.paged_decode or adapter.paged
        self.paged_prefill = ecfg.paged_prefill
        self.spec_k = ecfg.speculative_k
        if self.spec_k < 0:
            raise ValueError(f"speculative_k must be >= 0, got {self.spec_k}")
        if self.spec_k and not self.paged:
            raise ValueError(
                "speculative decode verifies drafts over the paged pool "
                "(the chunked-prefill kernel path); enable paged_decode"
            )
        if ecfg.device_sample and not self.paged:
            raise ValueError(
                "on-device sampling is fused into the paged dispatches; "
                "enable paged_decode (or keep host-side sampling)"
            )
        self.drafter = (
            make_drafter(ecfg.draft, self.spec_k, max_ngram=ecfg.draft_ngram)
            if self.spec_k else None
        )
        if ecfg.kv_int8:
            dtype = jnp.int8
        # the adapter owns pool construction so distributed adapters can
        # hand back a pool whose physical pages live sharded on their mesh
        self.pool = adapter.make_pool(
            n_pages=ecfg.total_pages(),
            page_size=ecfg.page_size,
            n_slots=ecfg.n_slots,
            max_pages_per_seq=ecfg.pages_per_seq,
            dtype=dtype,
            prefix_cache=ecfg.prefix_cache,
        )
        self.scheduler = TokenBudgetFCFS(
            token_budget=ecfg.token_budget, prefill_chunk=ecfg.prefill_chunk,
            max_queue=ecfg.max_queue, tenants=ecfg.tenants,
            aging_s=ecfg.aging_s,
        )
        self.running: list[Request] = []
        self.finished: list[Request] = []
        # per-tick result sinks (reset at every tick() entry): _note_emit
        # and _terminalize record into these so TickResult can hand the
        # server exactly what changed without diffing request state
        self._tick_emitted: list = []
        self._tick_finished: list = []
        # deterministic fault injection (serve/faults.py): the engine owns
        # the plan's dispatch context (tick, lane_rids) and points the
        # pool + adapter hooks at it.  Default: a fresh empty plan — every
        # hook short-circuits on the empty rule list.
        self.faults = faults if faults is not None else FaultPlan()
        self.pool.faults = self.faults
        adapter.faults = self.faults
        self._fault_log_pos = 0  # plan.log entries already reconciled
        # fast-path skip for deadline sweeps; flips on the first deadline
        self._deadlines = ecfg.deadline_s is not None
        # metrics: hot-path counters, pool gauges (live callbacks), and
        # the in-engine latency histograms (one percentile implementation
        # — benchmarks consume these instead of re-deriving latencies)
        self.metrics = MetricsRegistry()
        for name in _STAT_COUNTERS:
            self.metrics.counter(name)
        for name, fn in self.pool.metrics_gauges().items():
            self.metrics.gauge(name, fn=fn)
        self.metrics.gauge("finished", fn=lambda: len(self.finished))
        # process-wide: compiles, compile_s, compile_cache_reads
        for name, fn in compile_gauges().items():
            self.metrics.gauge(name, fn=fn)
        # tick-stall watchdog: seconds since the last COMPLETED tick.  A
        # dispatch that wedges inside tick() stops this advancing, so a
        # supervisor (or any external LB reading /healthz) can tell a
        # hung engine from a merely idle one — the tick loop keeps
        # ticking through idleness, so a healthy server's age stays
        # near the driver's sleep period.
        self._last_tick_t = 0.0
        self.metrics.gauge("last_tick_age_s", fn=self.last_tick_age_s)
        for name in ("ttft_s", "itl_s", "queue_s", "e2e_s"):
            self.metrics.histogram(name)
        # quality canaries: the shadow sampler re-scores a deterministic
        # fraction of finished requests against the adapter's dense
        # trunk; the canary probe needs a pinned prompt set, attached
        # via attach_canary (out-of-band — never touches the pool)
        self.shadow = (
            ShadowSampler(adapter, ecfg.shadow_rate, seed=ecfg.shadow_seed,
                          metrics=self.metrics, tracer=NULL_TRACER)
            if ecfg.shadow_rate > 0.0 else None
        )
        if self.shadow is not None:
            for name in ("shadow_max_abs_logit_diff", "shadow_flip_rate"):
                self.metrics.histogram(name)
        if ecfg.canary_every is not None and ecfg.canary_every <= 0:
            raise ValueError(
                f"canary_every must be > 0 seconds, got {ecfg.canary_every}"
            )
        self.canary_tokens: Optional[np.ndarray] = None
        # span tracing is OFF by default: NULL_TRACER's span() is a no-op
        # returning a shared context manager — the whole telemetry tax
        self.tracer = NULL_TRACER
        # engine-relative clock: the epoch is SET HERE (and again by
        # reset_clock) — arrival offsets submitted before the first step
        # are measured against construction time, not first use
        self._t0 = time.perf_counter()
        if tracer is not None:
            self.attach_tracer(tracer)

    # ---- submission -----------------------------------------------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new: int,
        arrival: float = 0.0,
        sampling: Optional[SamplingParams] = None,
        stop_tokens: tuple = (),
        deadline_s: Optional[float] = None,
        tenant: str = "default",
        priority: Optional[int] = None,
        resume_tokens: tuple = (),
    ) -> Request:
        """Submit a request, or raise a typed :class:`AdmissionRejected`:
        non-retryable when the request can never fit this pool (per-
        sequence or total capacity), retryable when the bounded queue is
        full.  Total-capacity accounting discounts full prompt-prefix
        pages the prefix cache already holds — a cached prompt is not
        rejected for pages it will never claim.  The forecast is
        OPTIMISTIC: ``prompt + max_new`` is a ceiling (stop tokens can
        end generation early), so the discount gives a cached prompt the
        benefit of the doubt; a request whose prefix actually outgrows
        the pool fails cleanly later ("capacity", via the queue-head
        feasibility backstop) instead of wedging the engine.
        ``deadline_s`` overrides ``EngineConfig.deadline_s`` for this
        request.  ``tenant`` bills the submit against that tenant's
        token bucket (retryable ``rate_limited`` rejection with a
        retry-after hint when overdrawn); ``priority`` pins the class
        (None inherits the tenant policy's).

        ``resume_tokens`` seeds the request with tokens a PREVIOUS
        attempt already emitted (fleet failover, DESIGN.md §15): the
        request prefills over ``prompt + resume_tokens`` — the same
        replay machinery eviction uses — and continues emitting at
        emission index ``len(resume_tokens)``.  ``max_new`` keeps its
        original meaning (total generation budget including the resumed
        tokens), so a resumed request's stream is token-identical to an
        uninterrupted run for greedy decoding and, with on-device
        sampling, for seeded sampling too (the draw key folds in the
        emission index, which resumes where it left off; the host-side
        numpy sampler's generator state cannot be fast-forwarded, so
        only those two modes carry the identity guarantee)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        total = prompt.size + max_new
        if total > self.pool.seq_capacity_tokens():
            self.metrics.inc("admission_rejected")
            raise AdmissionRejected(
                "over_capacity", retryable=False,
                needed_pages=pages_needed(total, self.ecfg.page_size),
                available_pages=self.pool.max_pages_per_seq,
            )
        need = max(1, pages_needed(total, self.ecfg.page_size))
        # -1: even a full-prefix hit claims one private copy-on-admit page
        cached = min(self.pool.cached_prefix_pages(prompt), need - 1)
        if need - cached > self.pool.n_pages - 1:
            self.metrics.inc("admission_rejected")
            raise AdmissionRejected(
                "over_capacity", retryable=False,
                needed_pages=need - cached,
                available_pages=self.pool.n_pages - 1,
            )
        resume = [int(t) for t in resume_tokens]
        if resume:
            if len(resume) >= max_new:
                raise ValueError(
                    f"resume_tokens already meets max_new "
                    f"({len(resume)} >= {max_new}); nothing to resume")
            if resume[-1] in tuple(stop_tokens):
                raise ValueError(
                    "resume_tokens ends on a stop token; the original "
                    "stream already finished")
        req = Request(
            prompt=prompt, max_new=max_new, arrival=arrival,
            sampling=sampling or SamplingParams(),
            stop_tokens=tuple(stop_tokens),
            deadline_s=(self.ecfg.deadline_s if deadline_s is None
                        else deadline_s),
            tenant=tenant, priority=priority,
        )
        if resume:
            # seed the replay state exactly as an eviction would leave
            # it: out_tokens carries the prior emissions (prefill covers
            # req.prefix = prompt + resume), token_times backfills with
            # the arrival stamp so latency accounting stays aligned, and
            # ``resumed`` lets the stream layer skip re-sending them
            req.out_tokens = resume
            req.token_times = [arrival] * len(resume)
            req.resumed = len(resume)
        if self.shadow is not None:
            # decided at submit so the decode paths know to materialize
            # this request's emission logits (crc32 of (seed, rid) —
            # deterministic across processes and batch composition)
            req.shadow = self.shadow.selects(req.rid)
        try:
            self.scheduler.submit(req)
        except AdmissionRejected:
            self.metrics.inc("admission_rejected")
            raise
        if req.deadline_s is not None:
            self._deadlines = True
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel a request by id from ANY live state — waiting, queued,
        mid-prefill, mid-decode, or mid-speculative-verify.  Pages and
        prefix-trie refcounts are released exactly as a finish would
        (the trie keeps its own refs on cached pages).  Returns whether
        a live request was found; terminal requests are left alone."""
        now = self.now()
        sch = self.scheduler
        for r in sch.waiting:
            if r.rid == rid:
                sch.waiting.remove(r)
                self._cancel(r, now)
                return True
        for r in sch.queue:
            if r.rid == rid:
                sch.queue.remove(r)
                self._cancel(r, now)
                return True
        for r in self.running:
            if r.rid == rid:
                self._cancel(r, now)  # _terminalize detaches from running
                return True
        return False

    # ---- telemetry ------------------------------------------------------

    @property
    def stats(self) -> dict:
        """Legacy read view: the hot-path counters as a plain dict (the
        registry is the source of truth; mutate via ``self.metrics``)."""
        return {n: self.metrics.counter(n).value for n in _STAT_COUNTERS}

    def attach_tracer(self, tracer: Tracer) -> None:
        """Wire a tracer through the whole stack: engine phase spans, the
        adapter's fused-dispatch spans, and the scheduler's lifecycle
        events all record into it.  The tracer's clock becomes the
        engine clock (span times share the request-arrival epoch), and
        ``sync=True`` tracers get a barrier that blocks on the pool
        buffers every fused dispatch donates and returns — so synced
        span durations are honest device time, not dispatch time."""
        tracer.clock = self.now
        if tracer.sync and tracer.sync_fn is None:
            tracer.sync_fn = self._sync_barrier
        tracer.tags.update(self.adapter.trace_tags())
        self.tracer = tracer
        self.adapter.tracer = tracer
        self.scheduler.tracer = tracer
        if self.shadow is not None:
            self.shadow.tracer = tracer

    def attach_canary(self, tokens: np.ndarray) -> None:
        """Pin the canary prompt set: (B, S) int32 token ids scored
        teacher-forced by every canary probe.  The set must stay FIXED
        for the gauge to be comparable across ticks/restarts — hence
        attached once, not sampled from traffic."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        if tokens.ndim != 2 or tokens.shape[1] < 2:
            raise ValueError(
                f"canary set must be (B, S>=2) token ids, got {tokens.shape}"
            )
        self.canary_tokens = tokens

    def _run_canary(self) -> None:
        """One out-of-band quality probe: teacher-forced NLL over the
        pinned canary set plus per-layer activation absmax / saturation,
        all published as gauges.  Runs the adapter's dense trunk against
        an EMPTY context — the KV pool is untouched, so live traffic
        stays token-identical with canaries on."""
        nll, act = canary_probe(self.adapter, self.canary_tokens)
        m = self.metrics
        m.gauge("canary_nll").set(nll)
        m.inc("canary_runs")
        absmax, sat = act["absmax"], act["sat"]
        m.gauge("act_absmax").set(float(absmax.max()))
        m.gauge("act_sat").set(float(sat.max()))
        for i in range(len(absmax)):
            m.gauge(f"act_absmax:{i}").set(float(absmax[i]))
            m.gauge(f"act_sat:{i}").set(float(sat[i]))
        self.tracer.event(
            "canary_probe", nll=nll,
            act_absmax=float(absmax.max()), act_sat=float(sat.max()),
            prompts=int(self.canary_tokens.shape[0]),
            tokens=int(self.canary_tokens.size),
        )

    def _sync_barrier(self) -> None:
        """Block until every enqueued device step has retired.  The pool
        K/V tensors are donated into and returned by every fused dispatch
        (and the oracle path's scatters), so blocking on them drains the
        per-device stream up to the last KV write."""
        jax.block_until_ready((self.pool.k, self.pool.v))

    # ---- main loop ------------------------------------------------------

    def now(self) -> float:
        """Engine-relative seconds.  Epoch: Engine construction, or the
        most recent :meth:`reset_clock` — request ``arrival`` offsets and
        all recorded span/lifecycle times share it."""
        return time.perf_counter() - self._t0

    def reset_clock(self) -> None:
        """Restart the engine-relative clock NOW (e.g. after a warm-up
        run, so arrival offsets of a measured workload start from zero).
        Takes effect immediately — not lazily on the next ``now()`` —
        so arrivals submitted before the next step share the epoch."""
        self._t0 = time.perf_counter()
        self._last_tick_t = 0.0

    def last_tick_age_s(self) -> float:
        """Seconds since the last completed :meth:`tick` (since the
        clock epoch if none has completed yet).  The tick-stall
        watchdog: a dispatch wedged INSIDE a tick stops this advancing
        past the stall threshold, which flips ``/healthz`` unhealthy so
        a fleet supervisor can hard-restart the replica."""
        return self.now() - self._last_tick_t

    def reset_stats(self) -> None:
        """Zero the cumulative counters and latency histograms (pairs
        with reset_clock after a warm-up run, so reported stats cover
        only the measured workload).  Live pool gauges are callbacks —
        they keep reporting current state — but the pool's high-water
        mark rebases to its current usage."""
        self.metrics.reset()
        self.pool.peak_pages_in_use = self.pool.pages_in_use

    # ---- lifecycle API (what a driver needs; DESIGN.md §14) -------------
    #
    # The engine does not own a loop: it exposes the pure ``tick()``
    # plus these predicates, and a driver — the in-process
    # ``lifecycle.run_to_completion`` (what ``run()`` delegates to) or
    # the front-door server's async tick task — decides when to tick,
    # when to sleep, and when to drain.

    @property
    def idle(self) -> bool:
        """No pending (waiting/queued) and no running work."""
        return not (self.scheduler.pending or self.running)

    def next_arrival(self) -> Optional[float]:
        """Arrival time of the earliest not-yet-arrived request (engine
        clock), or None — what an idle driver may sleep until."""
        return self.scheduler.waiting[0].arrival if self.scheduler.waiting \
            else None

    def live_requests(self) -> list[Request]:
        """Every non-terminal request: waiting, queued, and running."""
        sch = self.scheduler
        return [*sch.waiting, *sch.queue, *self.running]

    def cancel_all(self) -> list[Request]:
        """Cancel every live request (drain-deadline teardown).  Returns
        the requests cancelled; pages are released refcount-exactly."""
        victims = self.live_requests()
        for r in victims:
            self.cancel(r.rid)
        return victims

    def fail_running(self, reason: str) -> list[Request]:
        """Fail every request holding a lane — what a tick that raised
        leaves in an unknown device state.  Returns them; pages are
        released refcount-exactly and the next tick reports them."""
        victims = list(self.running)
        now = self.now()
        for r in victims:
            self._fail(r, reason, now)
        return victims

    def set_speculative_k(self, k: int) -> int:
        """Clamp the LIVE speculative draft depth to ``k`` (degradation
        ladder hook).  Can only shrink below — or restore up to — the
        configured ``EngineConfig.speculative_k`` (the drafter and the
        verify dispatch buckets were built for it); with 0 the engine
        falls back to plain one-token decode ticks.  Returns the depth
        actually in effect.  Reversible: calling with the configured K
        restores full speculation."""
        if k < 0:
            raise ValueError(f"speculative depth must be >= 0, got {k}")
        self.spec_k = min(k, self.ecfg.speculative_k)
        return self.spec_k

    def run(self, max_steps: Optional[int] = None,
            metrics_every: Optional[float] = None) -> list[Request]:
        """Drive until every submitted request is finished (delegates to
        :func:`repro.serve.lifecycle.run_to_completion` — the engine
        itself owns no loop)."""
        from repro.serve.lifecycle import run_to_completion

        return run_to_completion(
            self, max_steps=max_steps, metrics_every=metrics_every
        )

    _METRICS_LINE_KEYS = (
        "steps", "decode_tokens", "prefill_tokens", "evictions",
        "pages_in_use", "occupancy", "finished", "acceptance_rate",
        "ttft_s_p50", "ttft_s_p99", "itl_s_p50", "itl_s_p99",
        "e2e_s_p50", "e2e_s_p99", "canary_nll",
    )

    def _emit_metrics_snapshot(self) -> None:
        emit_metrics_line(
            self.summary(), t=self.now(), keys=list(self._METRICS_LINE_KEYS)
        )

    def step(self) -> bool:
        """One engine step; returns whether any token work was done.
        Compatibility wrapper over :meth:`tick`."""
        return self.tick().worked

    def tick(self) -> TickResult:
        """One engine tick — the pure unit of work a driver schedules.

        Returns a :class:`TickResult` carrying every ``(request, token)``
        emitted this tick (in emit order) and every request that reached
        a terminal state since the last tick ended, so a streaming front
        door can fan tokens out to clients without polling request
        objects.  Terminalizations that happen BETWEEN ticks (a server-
        side ``cancel()``) are reported by the next tick.

        Span taxonomy (telemetry, DESIGN.md §11): the whole tick is one
        ``step`` span; its direct children are ``schedule`` (arrival
        admission + planning + page claims/eviction), ``prefill``,
        and ``decode`` XOR ``verify`` (speculative) — adapter dispatch
        spans nest one level deeper inside those phases.
        """
        tr = self.tracer
        with tr.span("step"):
            now = self.now()
            with tr.span("schedule"):
                if self.faults.rules:
                    self.faults.tick = self.metrics.counter("steps").value
                    for rid in self.faults.cancel_rids():
                        self.cancel(rid)
                self.scheduler.admit_arrivals(now)
                if self._deadlines:
                    self._enforce_deadlines(now)
                plan = self.scheduler.plan(self.running, self.pool, now=now)
                self.metrics.inc("prefix_hit_tokens", plan.prefix_hit_tokens)
                decode = self._ensure_decode_pages(plan, now)
                self._check_queue_head(now)
                # drop chunks whose request the page-ensure pass evicted
                # (or a fault/cancel/deadline terminalized)
                chunks = [
                    (r, n) for r, n in plan.prefill
                    if r.state is RequestState.PREFILL
                ]
            worked = False
            if chunks:
                with tr.span(
                    "prefill", lanes=len(chunks),
                    tokens=sum(n for _, n in chunks),
                ):
                    if self.paged_prefill:
                        self._run_prefill_batch(chunks, now)
                    else:
                        for req, n in chunks:
                            self._run_prefill_chunk(req, n, now)
                worked = True
            if decode:
                if self.spec_k:
                    with tr.span("verify", lanes=len(decode)):
                        self._run_decode_spec(decode, now)
                else:
                    with tr.span("decode", lanes=len(decode)):
                        self._run_decode(decode, now)
                worked = True
            self.metrics.inc("steps")
            if self.faults.rules:
                self._reconcile_faults()
        result = TickResult(
            worked=worked, t=now,
            emitted=self._tick_emitted, finished=self._tick_finished,
        )
        # fresh sinks (not .clear()) so the returned lists stay valid
        self._tick_emitted = []
        self._tick_finished = []
        self._last_tick_t = self.now()  # watchdog: tick COMPLETED
        return result

    # ---- internals ------------------------------------------------------

    @staticmethod
    def _select_token(req: Request, logits: np.ndarray) -> int:
        """Pick the next token from last-position logits (host-side).

        Greedy (temperature 0) stays a bare argmax — the ``--check``
        oracle path.  Otherwise: temperature scale, nucleus (top-p)
        filter, then one draw from the request's own generator.
        """
        sp = req.sampling
        if sp.greedy:
            return int(np.argmax(logits))
        z = logits.astype(np.float64) / sp.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        if sp.top_p < 1.0:
            order = np.argsort(-p)
            csum = np.cumsum(p[order])
            # smallest prefix with mass >= top_p (always keeps the head)
            keep = order[: int(np.searchsorted(csum, sp.top_p)) + 1]
            nucleus = np.zeros_like(p)
            nucleus[keep] = p[keep]
            p = nucleus / nucleus.sum()
        return int(req.rng.choice(p.size, p=p))

    def _evict(self, victim: Request, now: float) -> None:
        cap = self.ecfg.max_evictions
        if cap is not None and victim.n_evictions >= cap:
            # eviction-storm guard: a sequence thrashing in and out of
            # residency FAILS cleanly — freeing its pages for the asking
            # lane — instead of replaying its prefix forever (two near-
            # capacity requests can otherwise evict each other's progress
            # until the run-loop backstop trips)
            self._fail(victim, "eviction_storm", now)
            return
        self.pool.release(victim.slot)
        self.running.remove(victim)
        self.scheduler.requeue(victim)
        self.metrics.inc("evictions")
        self.tracer.event(
            "request_evicted", rid=victim.rid,
            generated=len(victim.out_tokens), n_evictions=victim.n_evictions,
        )

    def _ensure_decode_pages(self, plan: StepPlan, now: float) -> list[Request]:
        """Claim a page for each decode lane's next token, evicting under
        pressure.  Lanes are served best-class-oldest-first and the
        victim is always the worst-class NEWEST running request —
        possibly the asking lane itself — so requests already granted
        pages this step are never clawed back (strict-FCFS preemption
        within a class; low classes yield pages to high ones).  An armed
        ``alloc_fail`` rule makes the targeted lane's claim fail
        terminally (FAILED, "alloc_fail")."""
        active = []
        faults = self.faults if self.faults.rules else None
        lane_key = lambda r: (r.priority or 0, r.arrival, r.rid)
        for r in sorted(plan.decode, key=lane_key):
            if r.state is not RequestState.DECODE:
                continue  # evicted (or terminalized) as a side effect
            if faults is not None and faults.fire("alloc_fail", rid=r.rid):
                self._fail(r, "alloc_fail", now)
                continue
            while not self.pool.extend(r.slot, self.pool.length(r.slot) + 1):
                # victim: lowest class first (largest priority number),
                # then newest — with one class this is the legacy
                # strict-FCFS choice, so token identity is preserved
                victim = max(self.running, key=lane_key)
                self._evict(victim, now)
                if r.state is not RequestState.DECODE:
                    break  # r itself was evicted or stormed out
            else:
                active.append(r)
        return active

    def _enforce_deadlines(self, now: float) -> None:
        """Fail queued/running requests past their wall-clock deadline.
        Checked at tick boundaries: a mid-tick expiry fails at the next
        boundary — the tick in flight is never torn down."""
        sch = self.scheduler
        expired = [
            r for r in (*sch.queue, *self.running)
            if r.deadline_s is not None and now - r.arrival > r.deadline_s
        ]
        for r in expired:
            if r in sch.queue:
                sch.queue.remove(r)
            self.metrics.inc("deadline_missed")
            self._fail(r, "deadline", now)

    def _check_queue_head(self, now: float) -> None:
        """Fail a head-of-queue request that can NEVER be admitted: a
        decoding sequence needs its whole prefix resident at once, so a
        prefix needing more distinct pages than the pool owns is
        infeasible — cached or not (shared trie pages still occupy
        residency).  This is the exact backstop behind submit's
        OPTIMISTIC capacity forecast (``max_new`` is a ceiling; stop
        tokens can end generation early): a request whose generated
        prefix actually outgrows the pool fails cleanly here with reason
        "capacity".  Strict FCFS means an infeasible head would starve
        everything behind it and stall the run loop otherwise."""
        q = self.scheduler.queue
        if not q:
            return
        head = q[0]
        need = max(1, pages_needed(len(head.prefix), self.ecfg.page_size))
        if need > self.pool.n_pages - 1:
            q.popleft()
            self._fail(head, "capacity", now)

    def _reconcile_faults(self) -> None:
        """Turn this step's fault firings (plan.log) into telemetry: one
        dynamic ``fault:<kind>`` counter bump and one trace event each."""
        log = self.faults.log
        for entry in log[self._fault_log_pos:]:
            self.metrics.inc("fault:" + entry["kind"])
            self.tracer.event("fault_injected", **entry)
        self._fault_log_pos = len(log)

    def _screen_lanes(self, lanes: list[Request], logits, now: float) -> None:
        """Quarantine lanes whose logits carry NaN/Inf: ONE fused per-lane
        isfinite reduction on device (only a (B,) bool crosses to the
        host), then the poisoned lane FAILS ("nan_logits") while
        co-batched lanes keep their exact, untouched logit rows — blast
        radius is one request."""
        ok = np.asarray(_lane_finite(logits))
        for b, r in enumerate(lanes):
            if ok[b] or r.state.terminal:
                continue
            self._fail(r, "nan_logits", now)

    def _fail_dispatch(self, lanes, exc: FaultInjected, now: float) -> None:
        """A dispatch_error fault fired at the adapter entry: nothing ran,
        no pool length advanced.  Fail ONLY the targeted request; the
        surviving lanes retry next tick (recomputing the identical step)
        and stay token-identical to a fault-free run."""
        for r in lanes:
            if r is not None and r.rid == exc.rid and not r.state.terminal:
                self._fail(r, "dispatch_error", now)
                return

    def _note_emit(self, req: Request, now: float) -> None:
        """Post-emit lifecycle hook: mark the request's true first token
        (a replayed request keeps its original ``t_first``) and record
        the emission for this tick's :class:`TickResult`."""
        self._tick_emitted.append((req, req.out_tokens[-1]))
        if len(req.out_tokens) == 1:
            self.tracer.event(
                "first_token", rid=req.rid, ttft_s=now - req.arrival
            )

    def _terminalize(self, req: Request, state: RequestState, reason: str,
                     now: float) -> None:
        """Shared terminal transition (FINISHED/CANCELLED/FAILED): stamp
        state + finish_reason, release pages (refcount-correct from any
        live state — the prefix trie keeps its own refs), detach from
        ``running``, and count the reason (``finish:<reason>``)."""
        req.state = state
        req.finish_reason = reason
        req.t_finish = now
        if req.slot is not None:
            self.pool.release(req.slot)
            req.slot = None
        if req in self.running:
            self.running.remove(req)
        self.finished.append(req)
        self._tick_finished.append(req)
        self.metrics.inc("finish:" + reason)

    def _finish(self, req: Request, now: float) -> None:
        reason = (
            "stop" if req.out_tokens and req.out_tokens[-1] in req.stop_tokens
            else "length"
        )
        self._terminalize(req, RequestState.FINISHED, reason, now)
        # in-engine lifecycle latencies: one histogram implementation
        # (telemetry.Histogram) observes the same values an external
        # consumer would derive from (arrival, t_first, token_times).
        # FINISHED only — a cancelled/failed request has no honest e2e.
        m = self.metrics
        m.histogram("ttft_s").observe(req.t_first - req.arrival)
        m.histogram("e2e_s").observe(now - req.arrival)
        if req.t_admitted is not None:
            m.histogram("queue_s").observe(req.t_admitted - req.arrival)
        itl = m.histogram("itl_s")
        for a, b in zip(req.token_times, req.token_times[1:]):
            itl.observe(b - a)
        self.tracer.event(
            "request_finished", rid=req.rid, tokens=len(req.out_tokens),
            e2e_s=now - req.arrival, n_evictions=req.n_evictions,
        )
        if req.shadow and self.shadow is not None:
            # FINISHED only: a cancelled/failed stream has no complete
            # emission record to score against the oracle
            self.shadow.observe(req)

    def _cancel(self, req: Request, now: float) -> None:
        self._terminalize(req, RequestState.CANCELLED, "cancelled", now)
        self.metrics.inc("cancelled")
        self.tracer.event(
            "request_cancelled", rid=req.rid, tokens=len(req.out_tokens),
        )

    def _fail(self, req: Request, reason: str, now: float) -> None:
        if req in self.scheduler.queue:  # failed while queued (deadline,
            self.scheduler.queue.remove(req)  # capacity, storm requeue)
        self._terminalize(req, RequestState.FAILED, reason, now)
        self.metrics.inc("failed")
        self.tracer.event(
            "request_failed", rid=req.rid, reason=reason,
            tokens=len(req.out_tokens), n_evictions=req.n_evictions,
        )

    def _after_prefill_chunk(self, req: Request, n: int, last_logits,
                             now: float) -> None:
        """Shared chunk epilogue: advance, register cached prompt pages,
        and emit the first generated token when the prefix completes."""
        req.prefill_pos += n
        self.metrics.inc("prefill_tokens", n)
        if self.pool.prefix_cache:
            covered = min(req.prefill_pos, len(req.prompt))
            self.pool.register_prefix(req.slot, req.prompt[:covered])
        if req.prefill_pos == len(req.prefix):
            with self.tracer.span("prefill:wait"):
                last = np.asarray(last_logits)
            if self.ecfg.screen_logits and not np.all(np.isfinite(last)):
                # poisoned boundary logits: quarantine before emitting
                self._fail(req, "nan_logits", now)
                return
            req.state = RequestState.DECODE
            with self.tracer.span("prefill:boundary"):
                tok = self._boundary_token(req, last)
            held = self.now()  # the first token is on the host now
            req.emit(
                tok, held,
                last if self.ecfg.record_logits or req.shadow else None,
            )
            self._note_emit(req, held)
            if req.done:
                self._finish(req, held)

    def _boundary_token(self, req: Request, logits: np.ndarray) -> int:
        """First-token selection at the prefill boundary.  With on-device
        sampling every draw must stay the same pure function of
        (seed, emission_index) the fused dispatches use — a host numpy
        draw here would fork a replayed (evicted) request's stream from
        its uncontended one — so non-greedy lanes run the identical
        ``sample_tokens`` math on the boundary logits."""
        sp = req.sampling
        if not self.ecfg.device_sample or sp.greedy:
            return self._select_token(req, logits)
        sel = sample_tokens(
            jnp.asarray(logits)[None, None],
            jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_p], jnp.float32),
            jnp.asarray([sp.seed], jnp.int32),
            jnp.asarray([len(req.out_tokens)], jnp.int32),
        )
        return int(sel[0, 0])

    def _run_prefill_chunk(self, req: Request, n: int, now: float) -> None:
        prefix = req.prefix
        start = req.prefill_pos
        C = self.ecfg.prefill_chunk
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = prefix[start : start + n]
        positions = (np.arange(C, dtype=np.int32) + start)[None]
        ctx_k, ctx_v = self.pool.gather([req.slot])
        if self.faults.rules:
            self.faults.lane_rids = (req.rid,)
            # only the boundary chunk's last logit is consumed; earlier
            # chunks' logits are discarded, so NaN there is unobservable
            self.faults.poison_rids = (
                (req.rid,) if start + n == len(prefix) else ())
        try:
            logits, k_new, v_new = self.adapter(
                jnp.asarray(chunk),
                jnp.asarray(positions),
                ctx_k,
                ctx_v,
                jnp.asarray([start], jnp.int32),
            )
        except FaultInjected as e:
            self._fail_dispatch([req], e, now)
            return  # prefill_pos unchanged: a surviving req replans as-is
        self.pool.write_span(req.slot, start, n, k_new[:, 0], v_new[:, 0])
        self._after_prefill_chunk(req, n, logits[0, n - 1], now)

    def _run_prefill_batch(self, chunks, now: float) -> None:
        """One fused dispatch over the step's whole co-batchable prefill
        group: lanes padded to a power of two (compile reuse across group
        sizes), chunk width fixed at ``prefill_chunk``, block tables
        bucketed to the longest prior context in the batch.  Padded lanes
        and padded chunk tails scatter to the scratch page.  Spans inside
        ``prefill``: ``prefill:prepare`` up to the dispatch, then per
        finished prompt ``prefill:wait`` and ``prefill:boundary``."""
        with self.tracer.span("prefill:prepare"):
            C = self.ecfg.prefill_chunk
            # lane bucketing shares page_bucket so the pow2 rounding has
            # one source; group size is bounded by the token budget
            B = page_bucket(len(chunks), 1 << 16)
            tokens = np.zeros((B, C), np.int32)
            positions = np.tile(np.arange(C, dtype=np.int32), (B, 1))
            ctx_len = np.zeros((B,), np.int32)
            slots: list[Optional[int]] = [None] * B
            starts = [0] * B
            ns = [0] * B
            for b, (r, n) in enumerate(chunks):
                start = r.prefill_pos
                tokens[b, :n] = r.prefix[start : start + n]
                positions[b] += start
                ctx_len[b] = start
                slots[b], starts[b], ns[b] = r.slot, start, n
            pages, offs = self.pool.span_addresses(slots, starts, ns, C)
            bt = self.pool.block_table(slots)
            bt = bt[:, : self._active_pages(int(ctx_len.max(initial=1)))]
            if self.faults.rules:
                self.faults.lane_rids = tuple(r.rid for r, _ in chunks)
                self.faults.poison_rids = tuple(
                    r.rid for r, n in chunks
                    if r.prefill_pos + n == len(r.prefix))
        try:
            logits = self.adapter.prefill_paged(
                tokens, positions, bt, ctx_len, pages, offs, self.pool
            )
        except FaultInjected as e:
            # lengths never advanced (no note_span_written): surviving
            # chunks replan next tick and recompute the identical KV
            self._fail_dispatch([r for r, _ in chunks], e, now)
            return
        self.pool.note_span_written(slots, starts, ns)
        self.metrics.inc("prefill_batches")
        self.metrics.counter("prefill_batch_size").peak(len(chunks))
        for b, (r, n) in enumerate(chunks):
            self._after_prefill_chunk(r, n, logits[b, n - 1], now)

    def _active_pages(self, max_ctx: int) -> int:
        """Pages to attend this step: covers the longest live context,
        rounded up to a power of two so the paged dispatch compiles a
        handful of bucket shapes instead of one per context length."""
        return page_bucket(
            pages_needed(max_ctx, self.ecfg.page_size),
            self.pool.max_pages_per_seq,
        )

    def _sampling_arrays(self, reqs: list[Request], B: int):
        """(temps, top_ps, seeds, draws) per lane for the fused on-device
        draw; ``draws`` is each lane's emission count so far, so the draw
        key is a pure function of (request seed, emission index)."""
        temps = np.zeros(B, np.float32)
        top_ps = np.ones(B, np.float32)
        seeds = np.zeros(B, np.int32)
        draws = np.zeros(B, np.int32)
        for b, r in enumerate(reqs):
            temps[b] = r.sampling.temperature
            top_ps[b] = r.sampling.top_p
            seeds[b] = r.sampling.seed
            draws[b] = len(r.out_tokens)
        return temps, top_ps, seeds, draws

    def _run_decode(self, decode: list[Request], now: float) -> None:
        """One decode tick.  Spans inside ``decode``: ``decode:prepare``
        (lane arrays, block table, addresses, sampling arrays), the
        adapter's ``dispatch:*``, ``decode:wait`` (the host's read of the
        selected tokens) and ``emit``."""
        tr = self.tracer
        B = self.ecfg.n_slots
        assert len(decode) <= B
        sel_np = None
        try:
            with tr.span("decode:prepare"):
                slots: list[Optional[int]] = [None] * B
                tokens = np.zeros((B, 1), np.int32)
                positions = np.zeros((B, 1), np.int32)
                ctx_len = np.zeros((B,), np.int32)
                for b, r in enumerate(decode):
                    slots[b] = r.slot
                    tokens[b, 0] = r.out_tokens[-1]
                    ctx_len[b] = self.pool.length(r.slot)
                    positions[b, 0] = ctx_len[b]
                pos_list = [int(p) for p in positions[:, 0]]
                if self.faults.rules:
                    self.faults.lane_rids = tuple(r.rid for r in decode)
                    self.faults.poison_rids = self.faults.lane_rids
                if self.paged:
                    bt = self.pool.block_table(slots)
                    bt = bt[:, : self._active_pages(
                        int(ctx_len.max(initial=1)))]
                    pages, offs = self.pool.addresses(slots, pos_list)
                    sampling = (self._sampling_arrays(decode, B)
                                if self.ecfg.device_sample else None)
            if self.paged:
                if sampling is not None:
                    sel, logits = self.adapter.decode_paged_sample(
                        tokens, positions, bt, ctx_len, pages, offs,
                        sampling, self.pool,
                    )
                    with tr.span("decode:wait"):
                        sel_np = np.asarray(sel[:, 0])
                else:
                    logits = self.adapter.decode_paged(
                        tokens, positions, bt, ctx_len, pages, offs, self.pool
                    )
                self.pool.note_written(slots, pos_list)
                # padding waste of the paged kernel's grid: live pages
                # against the (lanes x block-table width) it attends
                ps = self.ecfg.page_size
                live = ctx_len[:len(decode)]
                self.metrics.inc("decode_pages_live", sum(
                    pages_needed(int(c) + 1, ps) for c in live))
                self.metrics.inc("decode_pages_attended", B * bt.shape[1])
            else:
                ctx_k, ctx_v = self.pool.gather(slots)
                logits, k_new, v_new = self.adapter(
                    jnp.asarray(tokens),
                    jnp.asarray(positions),
                    ctx_k,
                    ctx_v,
                    jnp.asarray(ctx_len),
                )
                self.pool.write(slots, pos_list, k_new[:, :, 0], v_new[:, :, 0])
        except FaultInjected as e:
            # nothing dispatched, lengths untouched: fail the target only;
            # surviving lanes redo the identical step next tick
            self._fail_dispatch(decode, e, now)
            return
        if self.ecfg.screen_logits:
            self._screen_lanes(decode, logits, now)
        with tr.span("emit", lanes=len(decode)):
            logits_np = None
            if (sel_np is None or self.ecfg.record_logits
                    or any(r.shadow for r in decode)):
                logits_np = np.asarray(logits[:, 0])
            held = self.now()  # every lane's token is on the host now
            for b, r in enumerate(decode):
                if r.state.terminal:
                    continue  # quarantined by the screen this tick
                tok = (
                    int(sel_np[b]) if sel_np is not None
                    else self._select_token(r, logits_np[b])
                )
                r.emit(
                    tok, held,
                    logits_np[b] if self.ecfg.record_logits or r.shadow
                    else None,
                )
                self._note_emit(r, held)
                self.metrics.inc("decode_tokens")
                if r.done:
                    self._finish(r, held)

    def _run_decode_spec(self, decode: list[Request], now: float) -> None:
        """One speculative tick: draft up to K tokens per lane, verify
        every lane's ``[last_emitted, drafts...]`` chunk in ONE fused
        padded (B, K+1) dispatch, emit each lane's accepted prefix plus
        the bonus token, and roll back the rejected tail's K/V."""
        B, K = self.ecfg.n_slots, self.spec_k
        W = K + 1
        assert len(decode) <= B
        slots: list[Optional[int]] = [None] * B
        tokens = np.zeros((B, W), np.int32)
        positions = np.tile(np.arange(W, dtype=np.int32), (B, 1))
        ctx_len = np.zeros((B,), np.int32)
        drafts = np.zeros((B, K), np.int32)
        n_drafts = np.zeros((B,), np.int32)
        starts = [0] * B
        widths = [0] * B
        with self.tracer.span("draft", lanes=len(decode)):
            for b, r in enumerate(decode):
                slots[b] = r.slot
                length = self.pool.length(r.slot)
                # opportunistic draft: capped by the request's remaining
                # token budget, the slot's page capacity, and page
                # availability — drafting never evicts anyone (the
                # guaranteed +1 page was already claimed by
                # _ensure_decode_pages)
                room = min(
                    K,
                    r.max_new - len(r.out_tokens) - 1,
                    self.pool.seq_capacity_tokens() - (length + 1),
                )
                prop = (
                    self.drafter.propose(r.prefix, room)
                    if room > 0 else np.zeros(0, np.int32)
                )
                n = len(prop)
                while n > 0 and not self.pool.extend(r.slot, length + 1 + n):
                    n -= 1
                tokens[b, 0] = r.out_tokens[-1]
                tokens[b, 1 : 1 + n] = prop[:n]
                drafts[b, :n] = prop[:n]
                n_drafts[b] = n
                positions[b] += length
                ctx_len[b] = length
                starts[b], widths[b] = length, 1 + n
                self.metrics.inc("draft_tokens", n)
        pages, offs = self.pool.span_addresses(slots, starts, widths, W)
        bt = self.pool.block_table(slots)
        bt = bt[:, : self._active_pages(int(ctx_len.max(initial=1)))]
        sampling = (
            self._sampling_arrays(decode, B) if self.ecfg.device_sample
            # host-sample debugging path: zero temps make the device
            # selection pure greedy; the host re-selects from the logits
            else (np.zeros(B, np.float32), np.ones(B, np.float32),
                  np.zeros(B, np.int32), np.zeros(B, np.int32))
        )
        if self.faults.rules:
            self.faults.lane_rids = tuple(r.rid for r in decode)
            self.faults.poison_rids = self.faults.lane_rids
        try:
            sel, n_acc, logits = self.adapter.verify_paged(
                tokens, positions, bt, ctx_len, pages, offs, drafts, n_drafts,
                sampling, self.pool,
            )
        except FaultInjected as e:
            self._fail_dispatch(decode, e, now)
            # unmap the opportunistic draft page claims: lengths never
            # advanced, so surviving lanes re-draft from ctx_len next tick
            for b, r in enumerate(decode):
                if not r.state.terminal:
                    self.pool.truncate(r.slot, starts[b])
            return
        self.pool.note_span_written(slots, starts, widths)
        if self.ecfg.screen_logits:
            self._screen_lanes(decode, logits, now)
        self.metrics.inc("spec_ticks")
        self.metrics.inc("spec_lanes", len(decode))
        with self.tracer.span("emit", lanes=len(decode)):
            logits_np = None
            if (not self.ecfg.device_sample or self.ecfg.record_logits
                    or any(r.shadow for r in decode)):
                logits_np = np.asarray(logits)
            sel_np, n_acc_np = np.asarray(sel), np.asarray(n_acc)
            held = self.now()  # every lane's tokens are on the host now
            extra = 0
            for b, r in enumerate(decode):
                if r.state.terminal:
                    continue  # quarantined by the screen; slot already freed
                length = int(ctx_len[b])
                keep = self.ecfg.record_logits or r.shadow
                emitted = 0
                if self.ecfg.device_sample:
                    for i in range(int(n_acc_np[b]) + 1):
                        r.emit(
                            int(sel_np[b, i]), held,
                            logits_np[b, i] if keep else None,
                        )
                        self._note_emit(r, held)
                        emitted += 1
                        if r.done:
                            break
                else:
                    i = 0
                    while True:
                        tok = self._select_token(r, logits_np[b, i])
                        r.emit(
                            tok, held,
                            logits_np[b, i] if keep else None,
                        )
                        self._note_emit(r, held)
                        emitted += 1
                        if r.done or i >= n_drafts[b] or tok != drafts[b, i]:
                            break
                        i += 1
                self.metrics.inc("decode_tokens", emitted)
                self.metrics.inc("accepted_tokens", emitted - 1)
                self.metrics.inc("rolled_back_tokens", widths[b] - emitted)
                extra += emitted - 1
                if r.done:
                    self._finish(r, held)  # releases the slot — no rollback
                else:
                    # un-write the rejected tail: the last emitted token's
                    # KV is computed NEXT tick (it is the new
                    # last_emitted), so the valid length is ctx + emitted
                    self.pool.truncate(r.slot, length + emitted)
        # accepted extras beyond the planned one-per-lane charge the NEXT
        # step's budget; rejected drafts were never charged
        self.scheduler.charge_accepted(extra)

    # ---- reporting ------------------------------------------------------

    def summary(self) -> dict:
        """One metrics snapshot: every counter, every live pool gauge,
        the in-engine latency histograms (``ttft_s_p50`` / ``itl_s_p99``
        / ``queue_s_*`` / ``e2e_s_*`` — None until a request finished),
        and the derived speculative-health ratios."""
        s = self.metrics.snapshot()
        # speculative decode health: how often the drafter was right,
        # and how many tokens a verify tick emitted on average
        s["acceptance_rate"] = (
            s["accepted_tokens"] / max(1, s["draft_tokens"])
        )
        s["accepted_per_tick"] = (
            s["accepted_tokens"] / max(1, s["spec_ticks"])
        )
        # mean tokens ONE lane emits per verify it takes part in
        # (1.0 = no speculative benefit, K+1 = every draft accepted)
        s["tokens_per_lane_tick"] = (
            s["decode_tokens"] / max(1, s["spec_lanes"])
        ) if s["spec_ticks"] else 1.0
        return s
