"""Streaming front door (ISSUE 9): HTTP/SSE token identity vs the
in-process engine, typed-admission HTTP mapping (429/413 +
Retry-After), disconnect-triggered cancel, graceful drain through the
leak gate, the degradation ladder, and the network-layer fault hooks.

The HTTP tests run a real :class:`FrontDoor` on an ephemeral localhost
port with the server loop on a daemon thread (stdlib ``http.client``
as the client — the container has no aiohttp/requests)."""
from __future__ import annotations

import http.client
import json
import socket
import struct
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig, TenantPolicy
from repro.serve.faults import AdmissionRejected, parse_fault_plan
from repro.serve.frontdoor import (
    DegradationLadder,
    FrontDoor,
    LadderConfig,
    leak_gate,
    parse_tenants,
)
from repro.serve.frontdoor.admission import (
    parse_generate_body,
    rejection_response,
)


# ---------------------------------------------------------------------------
# fixtures + helpers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fp_stack():
    cfg = get_smoke_config("qwen3-14b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8,
                               seed=3).tokens
    return cfg, model, params, prompts


def _engine(model, params, *, gen=8, prompt_len=8, **kw):
    ecfg = dict(max_seq_len=prompt_len + gen, n_slots=4, page_size=4,
                token_budget=32, prefill_chunk=8)
    ecfg.update(kw)
    return Engine(CachedDecoder.from_model(model, params),
                  EngineConfig(**ecfg))


def _post(port, payload: dict, timeout=30):
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


def _gen_tokens(port, prompt, max_new, *, stream=True, **extra):
    """Run one generate call to completion; returns the token list."""
    payload = {"prompt": [int(t) for t in prompt], "max_new": max_new,
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
    assert done[0]["finish_reason"] in ("length", "stop")
    return toks


# ---------------------------------------------------------------------------
# token identity: HTTP/SSE == in-process, fp and quantized
# ---------------------------------------------------------------------------


def test_http_token_identity_fp(fp_stack):
    cfg, model, params, prompts = fp_stack
    gen = 8
    ref_eng = _engine(model, params, gen=gen)
    refs = [ref_eng.submit(np.asarray(p), max_new=gen) for p in prompts]
    ref_eng.run()
    ref = [[int(t) for t in r.out_tokens] for r in refs]

    fd = FrontDoor(_engine(model, params, gen=gen),
                   drain_timeout_s=5.0).start_in_thread()
    try:
        got_sse = [_gen_tokens(fd.port, p, gen) for p in prompts]
        got_buf = [_gen_tokens(fd.port, p, gen, stream=False)
                   for p in prompts]
    finally:
        report = fd.drain_and_join()
    assert got_sse == ref  # byte-identical streams over SSE
    assert got_buf == ref  # and over the buffered JSON path
    assert report.clean


def test_http_token_identity_quantized(fp_stack):
    from repro.core.quantizer import QuipConfig
    from repro.launch.quantize import quantize_dense_model

    cfg, model, params, prompts = fp_stack
    calib = make_calibration(cfg.vocab, n_segments=4, seg_len=32, seed=7)
    qm = quantize_dense_model(
        params, cfg, QuipConfig(bits=2, method="ldlq", use_kernel=False),
        calib.tokens, seed=0, verbose=False,
    )
    gen = 6
    ecfg = EngineConfig(max_seq_len=prompts.shape[1] + gen, n_slots=4,
                        page_size=4, token_budget=32, prefill_chunk=8)
    ref_eng = Engine(CachedDecoder.from_quantized(qm), ecfg)
    refs = [ref_eng.submit(np.asarray(p), max_new=gen) for p in prompts]
    ref_eng.run()
    ref = [[int(t) for t in r.out_tokens] for r in refs]

    fd = FrontDoor(Engine(CachedDecoder.from_quantized(qm), ecfg),
                   drain_timeout_s=5.0).start_in_thread()
    try:
        got = [_gen_tokens(fd.port, p, gen) for p in prompts]
    finally:
        report = fd.drain_and_join()
    assert got == ref
    assert report.clean


# ---------------------------------------------------------------------------
# typed admission -> HTTP semantics
# ---------------------------------------------------------------------------


def test_http_over_capacity_413(fp_stack):
    cfg, model, params, prompts = fp_stack
    fd = FrontDoor(_engine(model, params), drain_timeout_s=2.0
                   ).start_in_thread()
    try:
        c, r = _post(fd.port, {"prompt": [1, 2, 3], "max_new": 10_000})
        body = json.loads(r.read())
        c.close()
        assert r.status == 413
        assert body["error"] == "over_capacity"
        assert body["retryable"] is False
        assert body["needed_pages"] > body["available_pages"]
        assert "retry-after" not in {
            k.lower() for k in dict(r.getheaders())
        }
    finally:
        assert fd.drain_and_join().clean


def test_http_rate_limited_429_with_retry_after(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(
        model, params,
        tenants={"free": TenantPolicy(rate=0.001, burst=1, priority=0)},
    )
    fd = FrontDoor(eng, drain_timeout_s=5.0).start_in_thread()
    try:
        p = [int(t) for t in prompts[0]]
        assert _gen_tokens(fd.port, p, 4, tenant="free")  # burst admit
        c, r = _post(fd.port, {"prompt": p, "max_new": 4, "tenant": "free"})
        body = json.loads(r.read())
        headers = {k.lower(): v for k, v in r.getheaders()}
        c.close()
        assert r.status == 429
        assert body["error"] == "rate_limited"
        assert body["retryable"] is True
        assert body["tenant"] == "free"
        assert int(headers["retry-after"]) >= 1
    finally:
        assert fd.drain_and_join().clean


def test_http_queue_full_429_and_drain_under_traffic(fp_stack):
    """Overload behaves, not breaks: with one lane and a one-deep queue
    a third concurrent stream gets 429 queue_full + Retry-After, and a
    short-deadline drain under that live traffic cancels the in-flight
    lanes with zero leaked pages."""
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, gen=256, n_slots=1, max_queue=1,
                  token_budget=8)
    fd = FrontDoor(eng, drain_timeout_s=0.3).start_in_thread()
    p = [int(t) for t in prompts[0]]
    # stream A: read its SSE head so we know it was ADMITTED (running)
    ca, ra = _post(fd.port, {"prompt": p, "max_new": 256})
    assert ra.status == 200
    assert ra.read(1)  # first byte of the event stream
    # B parks in the queue (no free lane); its response arrives at drain
    results = {}

    def _b():
        try:
            cb, rb = _post(fd.port, {"prompt": p, "max_new": 256},
                           timeout=60)
            results["b_status"] = rb.status
            rb.read()
            cb.close()
        except (ConnectionError, OSError) as e:  # killed by drain: fine
            results["b_error"] = str(e)

    tb = threading.Thread(target=_b, daemon=True)
    tb.start()
    deadline = time.time() + 10
    while eng.scheduler.pending < 1 and time.time() < deadline:
        time.sleep(0.01)
    assert eng.scheduler.pending >= 1, "B never reached the queue"
    # C: queue full -> 429 queue_full, engine untouched and alive
    cc, rc = _post(fd.port, {"prompt": p, "max_new": 256})
    body = json.loads(rc.read())
    headers = {k.lower(): v for k, v in rc.getheaders()}
    cc.close()
    assert rc.status == 429
    assert body["error"] == "queue_full"
    assert body["retryable"] is True
    assert "retry-after" in headers
    status, health = _get_json(fd.port, "/healthz")
    assert status == 200 and health["status"] == "ok"

    report = fd.drain_and_join(reason="requested")
    ca.close()
    tb.join(10)
    assert report.clean and report.exit_code == 0
    assert report.deadline_hit  # 256-token lanes can't finish in 0.3s
    assert report.cancelled >= 1
    assert eng.metrics.counter("finish:cancelled").value >= 1


def test_http_bad_request_400(fp_stack):
    cfg, model, params, prompts = fp_stack
    fd = FrontDoor(_engine(model, params), drain_timeout_s=2.0
                   ).start_in_thread()
    try:
        for payload in (b"not json",
                        json.dumps({"max_new": 4}).encode(),
                        json.dumps({"prompt": [], "max_new": 4}).encode(),
                        json.dumps({"prompt": [1], "max_new": 4,
                                    "bogus": 1}).encode()):
            c = http.client.HTTPConnection("127.0.0.1", fd.port, timeout=10)
            c.request("POST", "/v1/generate", payload)
            r = c.getresponse()
            body = json.loads(r.read())
            c.close()
            assert r.status == 400
            assert body["error"] == "bad_request"
        status, _ = _get_json(fd.port, "/404-nope")
        assert status == 404
    finally:
        assert fd.drain_and_join().clean


# ---------------------------------------------------------------------------
# disconnect -> cancel, endpoints, shed gate
# ---------------------------------------------------------------------------


def test_mid_stream_disconnect_cancels_request(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, gen=128, token_budget=8)
    fd = FrontDoor(eng, drain_timeout_s=2.0).start_in_thread()
    p = [int(t) for t in prompts[0]]
    c, r = _post(fd.port, {"prompt": p, "max_new": 128})
    assert r.status == 200
    assert r.read(16)  # at least one token frame is in flight
    # client vanishes mid-stream: http.client already detached c.sock
    # (Connection: close), so pull the live socket from the response
    # and abort it with an RST (SO_LINGER 0) instead of a polite FIN
    sock = r.fp.raw._sock
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))
    r.close()  # drop the makefile io-ref so close() really closes
    sock.close()
    deadline = time.time() + 15
    while (eng.metrics.counter("finish:cancelled").value < 1
           and time.time() < deadline):
        time.sleep(0.02)
    assert eng.metrics.counter("finish:cancelled").value >= 1
    assert eng.metrics.counter("client_disconnects").value >= 1
    report = fd.drain_and_join()
    assert report.clean  # the dropped lane's pages all came back


def test_healthz_readyz_metricsz_and_drain_503(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, gen=256, token_budget=8)
    fd = FrontDoor(eng, drain_timeout_s=3.0).start_in_thread()
    status, h = _get_json(fd.port, "/healthz")
    assert status == 200 and h["status"] == "ok"
    status, rz = _get_json(fd.port, "/readyz")
    assert status == 200 and rz["ready"] is True and "ladder_level" in rz
    status, m = _get_json(fd.port, "/metricsz")
    assert status == 200
    assert "steps" in m and m["server"]["ladder_level"] == 0
    assert 0.0 <= m["server"]["pressure"] <= 1.0
    # park a long stream so the server stays draining long enough to probe
    p = [int(t) for t in prompts[0]]
    c, r = _post(fd.port, {"prompt": p, "max_new": 256})
    assert r.status == 200 and r.read(1)
    fd._loop.call_soon_threadsafe(fd.request_drain, "requested")
    deadline = time.time() + 5
    got_503 = False
    while time.time() < deadline:
        try:
            status, rz = _get_json(fd.port, "/readyz", timeout=2)
        except (ConnectionError, OSError):
            break  # server already shut down
        if status == 503 and rz["draining"]:
            got_503 = True
            break
        time.sleep(0.01)
    assert got_503, "readyz never reported draining"
    report = fd.drain_and_join()
    c.close()
    assert report.clean


def test_shed_gate_rejects_only_lowest_class(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(
        model, params,
        tenants={"paid": TenantPolicy(priority=0),
                 "free": TenantPolicy(priority=1)},
    )
    fd = FrontDoor(eng, drain_timeout_s=2.0).start_in_thread()
    try:
        fd.ladder.shedding = True  # force the shed rung
        p = [int(t) for t in prompts[0]]
        c, r = _post(fd.port, {"prompt": p, "max_new": 4, "tenant": "free"})
        body = json.loads(r.read())
        c.close()
        assert r.status == 429 and body["error"] == "shed"
        assert body["retryable"] is True
        # high class sails through while the shed rung is active
        assert len(_gen_tokens(fd.port, p, 4, tenant="paid")) == 4
        assert eng.metrics.counter("shed_requests").value == 1
    finally:
        assert fd.drain_and_join().clean


# ---------------------------------------------------------------------------
# degradation ladder (engine-thread unit tests, no HTTP)
# ---------------------------------------------------------------------------


def _pressurize(eng, n):
    """Park n requests in waiting (far-future arrival): pending rises,
    nothing ever runs."""
    return [eng.submit(np.arange(1, 5, dtype=np.int32), max_new=2,
                       arrival=1e9) for _ in range(n)]


def test_ladder_escalates_and_restores_spec_k(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, paged_decode=True, speculative_k=4,
                  max_queue=4)
    ladder = DegradationLadder(
        eng, LadderConfig(sustain_s=0.1, cooloff_s=0.1))
    assert ladder.actions == ["spec_half", "spec_off", "shed_low"]
    reqs = _pressurize(eng, 4)  # queue_frac = 4/4 = 1.0
    t = 0.0
    seen = []
    for _ in range(12):
        act = ladder.observe(t)
        if act:
            seen.append(act)
        t += 0.11
    assert seen == ["spec_half", "spec_off", "shed_low"]
    assert ladder.level == 3 and ladder.shedding and eng.spec_k == 0
    assert eng.metrics.counter("ladder_escalations").value == 3
    assert eng.metrics.gauge("ladder_level").value == 3
    for r in reqs:  # pressure clears -> every rung unwinds
        eng.cancel(r.rid)
    seen = []
    for _ in range(12):
        act = ladder.observe(t)
        if act:
            seen.append(act)
        t += 0.11
    assert seen == ["+shed_low", "+spec_off", "+spec_half"]
    assert ladder.level == 0 and not ladder.shedding
    assert eng.spec_k == 4  # fully restored
    assert eng.metrics.counter("ladder_deescalations").value == 3


def test_ladder_hysteresis_band_holds_level(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, max_queue=10)
    ladder = DegradationLadder(
        eng, LadderConfig(high_water=0.8, low_water=0.3, sustain_s=0.1,
                          cooloff_s=0.1))
    assert ladder.actions == ["shed_low"]  # non-speculative engine
    reqs = _pressurize(eng, 10)
    assert ladder.observe(0.0) is None  # first sight arms the timer
    assert ladder.observe(0.2) == "shed_low"
    for r in reqs[4:]:  # drop pressure into the band (6/10 = 0.6)
        eng.cancel(r.rid)
    for t in (0.4, 0.6, 0.8):
        assert ladder.observe(t) is None  # held, neither direction
    assert ladder.level == 1 and ladder.shedding


def test_set_speculative_k_clamps(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, paged_decode=True, speculative_k=4)
    assert eng.set_speculative_k(2) == 2
    assert eng.set_speculative_k(99) == 4  # clamped to the built depth
    assert eng.set_speculative_k(0) == 0
    with pytest.raises(ValueError):
        eng.set_speculative_k(-1)


# ---------------------------------------------------------------------------
# tick()/TickResult contract + lifecycle API
# ---------------------------------------------------------------------------


def test_tick_result_reports_emissions_and_finishes(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, gen=5)
    reqs = [eng.submit(np.asarray(p), max_new=5) for p in prompts]
    per_rid: dict[int, list] = {r.rid: [] for r in reqs}
    finished = []
    while not eng.idle:
        res = eng.tick()
        for req, tok in res.emitted:
            per_rid[req.rid].append(tok)
        finished.extend(res.finished)
    assert sorted(r.rid for r in finished) == sorted(r.rid for r in reqs)
    for r in reqs:  # TickResult emissions reconstruct each stream exactly
        assert per_rid[r.rid] == [int(t) for t in r.out_tokens]
    assert leak_gate(eng.pool) == (0, 0)


def test_between_tick_cancel_reported_by_next_tick(fp_stack):
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, gen=64, token_budget=8)
    req = eng.submit(np.asarray(prompts[0]), max_new=64)
    while not req.out_tokens:
        eng.tick()
    assert eng.cancel(req.rid)  # between ticks, as the server does
    res = eng.tick()
    assert req in res.finished
    assert req.finish_reason == "cancelled"
    assert eng.idle and eng.next_arrival() is None
    assert eng.cancel_all() == []  # nothing live left


# ---------------------------------------------------------------------------
# satellites: AdmissionRejected detail, fault hooks, tenant spec
# ---------------------------------------------------------------------------


def test_admission_rejected_str_carries_detail():
    e = AdmissionRejected("over_capacity", retryable=False,
                          needed_pages=9, available_pages=4)
    s = str(e)
    assert "needs 9 pages, 4 available" in s and "not retryable" in s
    assert e.http_status == 413
    d = e.to_dict()
    assert d["error"] == "over_capacity" and d["retryable"] is False
    assert d["needed_pages"] == 9 and d["available_pages"] == 4

    e = AdmissionRejected("rate_limited", retryable=True, tenant="free",
                          retry_after_s=2.5)
    s = str(e)
    assert "tenant 'free'" in s and "retry after 2.5s" in s
    assert s.endswith("retryable")
    assert e.http_status == 429
    status, headers, body = rejection_response(e)
    assert status == 429 and ("Retry-After", "3") in headers
    assert json.loads(body)["retry_after_s"] == 2.5


def test_network_fault_rules_parse_and_fire():
    plan = parse_fault_plan(
        "slow_client@ms=50;disconnect@tokens=2;admission_burst@n=3")
    assert plan.stall_ms(rid=7) == 50
    assert plan.stall_ms(rid=7) is None  # consumed
    assert not plan.disconnect_after(5, 1)  # below the token threshold
    assert plan.disconnect_after(5, 2)
    assert not plan.disconnect_after(5, 3)  # consumed
    assert plan.admission_burst() == 3
    assert plan.admission_burst() == 0
    assert [e["kind"] for e in plan.log] == [
        "slow_client", "disconnect", "admission_burst"]
    with pytest.raises(ValueError):
        parse_fault_plan("slow_client")  # ms= is required
    with pytest.raises(ValueError):
        parse_fault_plan("admission_burst@n=0")


def test_disconnect_fault_injected_over_http(fp_stack):
    """The chaos path end-to-end: an armed disconnect rule drops the SSE
    stream server-side after 2 tokens and the request is cancelled."""
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params, gen=128, token_budget=8)
    eng.faults.rules.extend(parse_fault_plan("disconnect@tokens=2").rules)
    fd = FrontDoor(eng, drain_timeout_s=2.0).start_in_thread()
    p = [int(t) for t in prompts[0]]
    c, r = _post(fd.port, {"prompt": p, "max_new": 128})
    assert r.status == 200
    raw = b""
    try:
        while True:
            chunk = r.read(64)
            if not chunk:
                break
            raw += chunk
    except (ConnectionError, OSError, http.client.IncompleteRead):
        pass  # the fault aborts the transport mid-stream
    c.close()
    deadline = time.time() + 15
    while (eng.metrics.counter("finish:cancelled").value < 1
           and time.time() < deadline):
        time.sleep(0.02)
    assert eng.metrics.counter("finish:cancelled").value == 1
    report = fd.drain_and_join()
    assert report.clean


def test_parse_tenants_spec():
    t = parse_tenants("paid:inf:4:0,free:2.0:8:1,batch:0.5")
    assert t["paid"] == TenantPolicy(rate=None, burst=4, priority=0)
    assert t["free"] == TenantPolicy(rate=2.0, burst=8, priority=1)
    assert t["batch"] == TenantPolicy(rate=0.5, burst=4, priority=0)
    for bad in ("", ":1.0", "a:1:2:3:4", "dup:1,dup:2"):
        with pytest.raises(ValueError):
            parse_tenants(bad)


def test_parse_generate_body_validation():
    ok = parse_generate_body(json.dumps(
        {"prompt": [1, 2], "max_new": 4, "tenant": "t", "priority": 1,
         "stream": False, "temperature": 0.5, "top_p": 0.9, "seed": 3,
         "stop_tokens": [7], "deadline_s": 2.0}).encode())
    assert ok.max_new == 4 and ok.tenant == "t" and not ok.stream
    assert ok.sampling.temperature == 0.5 and ok.stop_tokens == (7,)
    for bad in (
        {"prompt": [1.5], "max_new": 4},
        {"prompt": [1], "max_new": 0},
        {"prompt": [1], "max_new": 4, "priority": -1},
        {"prompt": [1], "max_new": 4, "stream": "yes"},
        {"prompt": [1], "max_new": 4, "top_p": 0.0},
        {"prompt": [1], "max_new": 4, "deadline_s": -1},
    ):
        with pytest.raises(ValueError):
            parse_generate_body(json.dumps(bad).encode())


def test_tick_error_fails_its_requests_and_exits_nonzero(fp_stack, capfd):
    """A tick that raises (a kernel the compiler refused, a device fault)
    is not swallowed: the traceback is printed, the requests holding
    lanes fail with ``tick_error``, the server keeps answering, and the
    drain report exits nonzero."""
    cfg, model, params, prompts = fp_stack
    eng = _engine(model, params)
    real_tick, calls = eng.tick, []

    def tick():
        if eng.running and not calls:
            calls.append(1)
            raise RuntimeError("refused by the compiler")
        return real_tick()

    eng.tick = tick
    fd = FrontDoor(eng, drain_timeout_s=5.0).start_in_thread()
    try:
        p = [int(t) for t in prompts[0]]
        c, r = _post(fd.port, {"prompt": p, "max_new": 4, "stream": False})
        body = json.loads(r.read())
        c.close()
        assert body["finish_reason"] == "tick_error"
        assert _gen_tokens(fd.port, p, 4)  # the next request is served
    finally:
        report = fd.drain_and_join()
    assert calls and report.tick_errors == 1
    assert report.clean and report.exit_code == 1
    assert "refused by the compiler" in capfd.readouterr().err
