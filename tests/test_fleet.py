"""Replica fleet (ISSUE 10): supervised data-parallel FrontDoor
replicas behind the failover router — affinity/journal units, engine
resume-token determinism (the mechanism that makes crash failover
token-identical), the tick-stall watchdog, and router end-to-end:
balanced routing, typed-rejection pass-through, mid-stream failover
splicing (greedy AND device-sampled), replica-unavailable 503s,
supervisor restart with give-up circuit breaker, and coordinated
fleet drain through every replica's leak gate.

Replicas here are in-process: real FrontDoors on daemon threads (the
:class:`ThreadReplicaFactory` implements the supervisor's factory
protocol), with mid-stream death simulated by the ``disconnect``
transport fault — the router sees exactly what a ``kill -9`` produces
(EOF before the done frame).  Real-process crash drills live in
scripts/ci.sh.
"""
from __future__ import annotations

import http.client
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig
from repro.serve.faults import parse_fault_plan
from repro.serve.fleet import (
    FleetRouter,
    ProcessReplicaFactory,
    ReplicaHandle,
    RequestJournal,
    Supervisor,
    prefix_key,
    rendezvous_rank,
)
from repro.serve.frontdoor import FrontDoor
from repro.serve.scheduler import SamplingParams

GEN = 8
PROMPT_LEN = 8


# ---------------------------------------------------------------------------
# fixtures + helpers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fp_stack():
    cfg = get_smoke_config("qwen3-14b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=PROMPT_LEN,
                               seed=3).tokens
    return cfg, model, params, prompts


def _engine(model, params, *, sampled=False, faults=None, **kw):
    ecfg = dict(max_seq_len=PROMPT_LEN + GEN, n_slots=4, page_size=4,
                token_budget=32, prefill_chunk=8)
    if sampled:
        # the identity guarantee for non-greedy needs the on-device
        # draw (fold_in(seed, emission_index) keys) — the paged path
        ecfg.update(paged_decode=True, device_sample=True)
    ecfg.update(kw)
    return Engine(CachedDecoder.from_model(model, params),
                  EngineConfig(**ecfg), faults=faults)


_SAMPLED = dict(temperature=0.8, top_p=0.9, seed=7)


def _reference(model, params, prompt, *, sampled=False):
    """Uninterrupted single-replica run: the token stream every fleet
    path must reproduce exactly."""
    eng = _engine(model, params, sampled=sampled)
    sp = SamplingParams(**_SAMPLED) if sampled else None
    req = eng.submit(np.asarray(prompt), max_new=GEN, sampling=sp)
    eng.run()
    return [int(t) for t in req.out_tokens]


class ThreadReplicaFactory:
    """The supervisor's factory protocol over in-process replicas: each
    'process' is a fresh engine (same weights) behind a FrontDoor on a
    daemon thread.  ``fault_for(index, generation)`` arms per-
    incarnation chaos, mirroring --replica-fault."""

    def __init__(self, model, params, *, sampled=False, fault_for=None):
        self.model = model
        self.params = params
        self.sampled = sampled
        self.fault_for = fault_for or (lambda i, g: None)
        self.spawns = []

    def spawn(self, handle):
        eng = _engine(self.model, self.params, sampled=self.sampled,
                      faults=self.fault_for(handle.index,
                                            handle.generation))
        fd = FrontDoor(eng, port=0, drain_timeout_s=2.0,
                       tick_stall_s=5.0).start_in_thread()
        handle.proc = fd
        handle.port = fd.port
        handle.generation += 1
        self.spawns.append((handle.index, fd))

    def alive(self, handle):
        return handle.proc is not None and handle.proc._thread.is_alive()

    def kill(self, handle):
        fd = handle.proc
        if fd is not None and fd._thread.is_alive():
            fd.drain_and_join("kill", timeout=30)

    def drain(self, handle, timeout_s):
        fd = handle.proc
        if fd is None:
            return None
        if not fd._thread.is_alive():
            return fd.report.exit_code if fd.report is not None else None
        return fd.drain_and_join("fleet", timeout=timeout_s).exit_code


def _fleet(model, params, n=2, *, sampled=False, fault_for=None,
           max_restarts=3, **router_kw):
    """Boot an n-replica thread fleet behind a router; returns the
    started router (callers drain it)."""
    factory = ThreadReplicaFactory(model, params, sampled=sampled,
                                   fault_for=fault_for)
    sup = Supervisor(factory, n, probe_interval_s=0.1,
                     fail_threshold=2, start_timeout_s=60,
                     max_restarts=max_restarts, backoff_base_s=0.05,
                     backoff_max_s=0.2, replica_drain_timeout_s=30)
    router = FleetRouter(sup, port=0, drain_timeout_s=10,
                         **router_kw)
    return router.start_in_thread()


def _post(port, payload: dict, timeout=60):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/generate", json.dumps(payload),
              {"Content-Type": "application/json"})
    return c, c.getresponse()


def _get_json(port, path, timeout=10):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("GET", path)
    r = c.getresponse()
    body = json.loads(r.read())
    c.close()
    return r.status, body


def _parse_sse(raw: bytes):
    events = []
    for block in raw.decode().strip().split("\n\n"):
        lines = dict(ln.split(": ", 1) for ln in block.split("\n"))
        events.append((lines["event"], json.loads(lines["data"])))
    return events


def _gen_tokens(port, prompt, *, stream=True, **extra):
    payload = {"prompt": [int(t) for t in prompt], "max_new": GEN,
               "stream": stream, **extra}
    c, r = _post(port, payload)
    try:
        assert r.status == 200, (r.status, r.read())
        raw = r.read()
    finally:
        c.close()
    if not stream:
        return json.loads(raw)["tokens"]
    events = _parse_sse(raw)
    toks = [d["token"] for ev, d in events if ev == "token"]
    done = [d for ev, d in events if ev == "done"]
    assert len(done) == 1 and done[0]["tokens"] == toks
    # token frames must be contiguous global emission indices — a bad
    # failover splice would show up as a gap or repeat here
    assert [d["i"] for ev, d in events if ev == "token"] == \
        list(range(len(toks)))
    return toks


def _wait(pred, timeout=30, every=0.02):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(every)
    return False


# ---------------------------------------------------------------------------
# units: affinity, journal, fault grammar
# ---------------------------------------------------------------------------


def test_rendezvous_rank_is_stable_permutation():
    for key in (0, 1, 0xDEADBEEF):
        r = rendezvous_rank(key, 5)
        assert sorted(r) == list(range(5))
        assert r == rendezvous_rank(key, 5)  # stable
    with pytest.raises(ValueError):
        rendezvous_rank(1, 0)


def test_rendezvous_minimal_disruption_and_spread():
    # removing the winner never reorders the rest (HRW's defining
    # property — each slot scores independently)
    for key in range(50):
        r = rendezvous_rank(key, 4)
        assert r[1:] == [i for i in rendezvous_rank(key, 4) if i != r[0]]
    # and keys spread over replicas (no slot starves)
    wins = [rendezvous_rank(k, 3)[0] for k in range(300)]
    assert all(wins.count(i) > 30 for i in range(3))


def test_prefix_key_header_granularity():
    head = list(range(100, 116))
    assert prefix_key(head + [1, 2]) == prefix_key(head + [3, 4, 5])
    assert prefix_key(head) != prefix_key([0] + head[1:])


def test_journal_records_and_resumes():
    j = RequestJournal()
    body = {"prompt": [1, 2], "max_new": 8, "seed": 7}
    e = j.open(body, stream=True)
    e.assign(0)
    e.record(0, 11)
    e.record(1, 12)
    with pytest.raises(ValueError):  # gap: splice out of sync
        e.record(3, 14)
    with pytest.raises(ValueError):  # repeat
        e.record(1, 12)
    e.assign(2)
    assert e.n_failovers == 1 and e.replica == 2
    rb = e.resume_body()
    assert rb["resume_tokens"] == [11, 12]
    assert body == {"prompt": [1, 2], "max_new": 8, "seed": 7}  # untouched
    j.note_failover(e)
    j.close(e, finish_reason="length")
    assert (len(j), j.opened, j.completed, j.failed, j.failovers) == \
        (0, 1, 1, 0, 1)
    e2 = j.open(body, stream=False)
    j.close(e2, finish_reason=None)
    assert j.failed == 1


def test_replica_fault_grammar_and_hook():
    plan = parse_fault_plan(
        "replica_kill@tick=5;replica_slow@ms=20,times=3;replica_hang")
    kinds = [r.kind for r in plan.rules]
    assert kinds == ["replica_kill", "replica_slow", "replica_hang"]
    with pytest.raises(ValueError):  # replica_slow needs ms=
        parse_fault_plan("replica_slow")
    # the hook honours tick pinning and consumes times
    plan = parse_fault_plan("replica_kill@tick=5")
    plan.tick = 4
    assert plan.replica_disruption() is None
    plan.tick = 5
    assert plan.replica_disruption().kind == "replica_kill"
    assert plan.replica_disruption() is None  # consumed


# ---------------------------------------------------------------------------
# the mechanism: resume-token replay is token-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("k", [0, 1, 5, GEN - 1])
def test_engine_resume_token_identity(fp_stack, sampled, k):
    """The failover contract at the engine level: submitting with
    ``resume_tokens=ref[:k]`` (on a FRESH engine — the survivor) must
    produce exactly ``ref`` — greedy because argmax is stateless,
    sampled because the device draw keys on fold_in(seed,
    emission_index) and the resumed request continues at emission
    index k."""
    cfg, model, params, prompts = fp_stack
    ref = _reference(model, params, prompts[0], sampled=sampled)
    assert len(ref) == GEN
    eng = _engine(model, params, sampled=sampled)
    sp = SamplingParams(**_SAMPLED) if sampled else None
    req = eng.submit(np.asarray(prompts[0]), max_new=GEN, sampling=sp,
                     resume_tokens=tuple(ref[:k]))
    assert req.resumed == k
    eng.run()
    assert [int(t) for t in req.out_tokens] == ref


def test_resume_token_validation(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params)
    with pytest.raises(ValueError):  # resume must leave budget
        eng.submit(np.asarray(prompts[0]), max_new=4,
                   resume_tokens=(1, 2, 3, 4))
    with pytest.raises(ValueError):  # resume ending in a stop token
        eng.submit(np.asarray(prompts[0]), max_new=8, stop_tokens=(3,),
                   resume_tokens=(1, 2, 3))


# ---------------------------------------------------------------------------
# tick-stall watchdog
# ---------------------------------------------------------------------------


def test_healthz_watchdog_flips_on_wedged_executor(fp_stack):
    """Block the engine executor (the real wedge mode: a dispatch that
    never returns) — /healthz must flip to 503 'wedged' while the
    socket stays responsive, then recover when ticks resume."""
    cfg, model, params, prompts = fp_stack
    fd = FrontDoor(_engine(model, params), drain_timeout_s=2.0,
                   tick_stall_s=0.15).start_in_thread()
    try:
        status, h = _get_json(fd.port, "/healthz")
        assert status == 200 and h["status"] == "ok"
        assert "last_tick_age_s" in h and "inflight" in h
        fd._exec.submit(time.sleep, 1.0)  # wedge the engine thread
        assert _wait(lambda: _get_json(fd.port, "/healthz")[0] == 503,
                     timeout=5)
        status, h = _get_json(fd.port, "/healthz")
        if status == 503:  # may already have recovered
            assert h["status"] == "wedged"
            assert h["last_tick_age_s"] > 0.15
        assert _wait(lambda: _get_json(fd.port, "/healthz")[0] == 200,
                     timeout=5)
    finally:
        assert fd.drain_and_join().exit_code == 0
    # the gauge rides the metrics registry for scrapes too
    assert "last_tick_age_s" in fd.engine.summary()


# ---------------------------------------------------------------------------
# router end-to-end
# ---------------------------------------------------------------------------


def test_router_balances_and_stays_token_identical(fp_stack):
    cfg, model, params, prompts = fp_stack
    refs = [_reference(model, params, p) for p in prompts]
    router = _fleet(model, params, n=2)
    try:
        status, rz = _get_json(router.port, "/readyz")
        assert status == 200 and rz["available_replicas"] == 2
        got_sse = [_gen_tokens(router.port, p) for p in prompts]
        got_buf = [_gen_tokens(router.port, p, stream=False)
                   for p in prompts]
        assert got_sse == refs and got_buf == refs
        # sticky affinity: the same prompt lands on the same replica
        _, fz = _get_json(router.port, "/fleetz")
        assert fz["router"]["affinity_hits"] == 6
        assert fz["router"]["failovers"] == 0
        served = [r["served"] for r in fz["replicas"]]
        assert sum(served) == 6
    finally:
        report = router.drain_and_join()
    assert report.exit_code == 0 and report.completed == 6
    assert all(r["exit_code"] == 0 for r in report.replicas)


def test_router_passes_typed_rejections_through(fp_stack):
    cfg, model, params, prompts = fp_stack
    router = _fleet(model, params, n=2)
    try:
        # over-capacity: non-retryable 413, body verbatim from the
        # replica's typed AdmissionRejected mapping
        c, r = _post(router.port, {"prompt": [1, 2, 3],
                                   "max_new": 10_000})
        body = json.loads(r.read())
        c.close()
        assert r.status == 413
        assert body["error"] == "over_capacity"
        assert body["retryable"] is False
        # malformed body: the router 400s with the replica parser's
        # own message (never reaches a replica)
        c, r = _post(router.port, {"max_new": 4})
        body = json.loads(r.read())
        c.close()
        assert r.status == 400 and body["error"] == "bad_request"
        _, fz = _get_json(router.port, "/fleetz")
        assert fz["router"]["rejections_passed"] == 1
    finally:
        assert router.drain_and_join().exit_code == 0


def test_router_failover_splices_token_identically(fp_stack):
    """Headline greedy path: kill (disconnect) the serving replica
    mid-stream; the client's single SSE stream must carry the exact
    uninterrupted reference tokens, contiguous indices, one done."""
    cfg, model, params, prompts = fp_stack
    prompt = prompts[0]
    ref = _reference(model, params, prompt)
    victim = rendezvous_rank(prefix_key(prompt), 2)[0]

    def fault_for(index, generation):
        if index == victim and generation == 0:
            return parse_fault_plan("disconnect@tokens=3")
        return None

    router = _fleet(model, params, n=2, fault_for=fault_for)
    try:
        got = _gen_tokens(router.port, prompt)
        assert got == ref
        _, fz = _get_json(router.port, "/fleetz")
        assert fz["router"]["failovers"] == 1
        assert fz["journal"]["completed"] == 1
    finally:
        report = router.drain_and_join()
    assert report.exit_code == 0 and report.failovers == 1


def test_router_failover_token_identical_sampled(fp_stack):
    """The sampled half of the acceptance bar: device-sampled streams
    (per-request seed, emission-index key folding) survive failover
    token-identically too."""
    cfg, model, params, prompts = fp_stack
    prompt = prompts[1]
    ref = _reference(model, params, prompt, sampled=True)
    assert len(set(ref)) > 1 or len(ref) == GEN  # sanity: a real stream
    victim = rendezvous_rank(prefix_key(prompt), 2)[0]

    def fault_for(index, generation):
        if index == victim and generation == 0:
            return parse_fault_plan("disconnect@tokens=2")
        return None

    router = _fleet(model, params, n=2, sampled=True,
                    fault_for=fault_for)
    try:
        got = _gen_tokens(router.port, prompt, **_SAMPLED)
        assert got == ref
        _, fz = _get_json(router.port, "/fleetz")
        assert fz["router"]["failovers"] == 1
    finally:
        assert router.drain_and_join().exit_code == 0


def test_router_503_when_no_replica_available(fp_stack):
    cfg, model, params, prompts = fp_stack
    # max_restarts=0: first failure parks the slot as 'gone' (circuit
    # breaker), so killing both replicas leaves nothing to route to
    router = _fleet(model, params, n=2, max_restarts=0)
    sup = router.sup
    try:
        for h in sup.handles:
            h.proc.drain_and_join("chaos-kill")
        assert _wait(lambda: all(h.state == "gone"
                                 for h in sup.handles), timeout=15)
        status, rz = _get_json(router.port, "/readyz")
        assert status == 503 and rz["available_replicas"] == 0
        c, r = _post(router.port, {"prompt": [1, 2, 3], "max_new": 4})
        body = json.loads(r.read())
        assert r.status == 503
        assert body == {"error": "replica_unavailable",
                        "retryable": True}
        assert r.getheader("Retry-After") == "1"
        c.close()
    finally:
        report = router.drain_and_join()
    # gone slots have no live process (exit None) — nothing to leak
    assert report.exit_code == 0


def test_supervisor_restarts_crashed_replica(fp_stack):
    """Crash replica 0 (drain its thread = the process dies), wait for
    the probe loop to respawn it, and require the restarted replica to
    serve token-identical output — fresh engine, same weights."""
    cfg, model, params, prompts = fp_stack
    ref = _reference(model, params, prompts[2])
    router = _fleet(model, params, n=1, max_restarts=2)
    sup = router.sup
    h = sup.handles[0]
    try:
        first_port = h.port
        h.proc.drain_and_join("chaos-kill")
        assert _wait(lambda: h.state == "healthy" and h.restarts == 1,
                     timeout=30)
        assert h.generation == 2 and h.port != first_port
        assert _gen_tokens(router.port, prompts[2]) == ref
        # second crash: restart budget (2) still has room
        h.proc.drain_and_join("chaos-kill-2")
        assert _wait(lambda: h.state == "healthy" and h.restarts == 2,
                     timeout=30)
        # third crash trips the give-up circuit breaker
        h.proc.drain_and_join("chaos-kill-3")
        assert _wait(lambda: h.state == "gone", timeout=30)
        assert _get_json(router.port, "/readyz")[0] == 503
    finally:
        report = router.drain_and_join()
    assert report.exit_code == 0
    assert report.replicas[0]["restarts"] == 2


def test_replicas_get_one_chip_each_on_a_tpu_host(monkeypatch):
    """On a TPU host every replica process is pinned to its own chip
    through libtpu's environment, and a fleet larger than the host's
    chips is refused before anything starts."""
    from repro.launch import serve
    from repro.serve.fleet import supervisor

    monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: 4)
    f = ProcessReplicaFactory(["true"])
    envs = [f.env(ReplicaHandle(i, "127.0.0.1")) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    Supervisor(f, 4)
    with pytest.raises(ValueError, match="this host has 4"):
        Supervisor(f, 5)
    monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: 0)
    assert ProcessReplicaFactory(["true"]).env(ReplicaHandle(0, "h")) is None
    monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: 2)
    with pytest.raises(SystemExit, match="this host has 2"):
        serve.main(["--fleet", "3"])
