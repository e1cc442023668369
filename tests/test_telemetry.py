"""Telemetry tests (serve/telemetry.py + engine wiring).

Two claims matter and both are tested here:

  * observing the engine never changes it — token streams with a tracer
    attached are identical to tracer-off runs (fp, quantized and
    speculative paths; the TP variant lives in test_distributed.py) and
    the disabled default (NULL_TRACER) costs at most a method call;
  * what it reports is honest — ring wraparound keeps the newest spans,
    exported traces pass the Chrome/Perfetto schema gate, step phases
    cover (nearly) all of step time, and the engine's own latency
    percentiles equal an external recomputation from raw timestamps.
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.quantizer import QuipConfig
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig
from repro.serve.telemetry import (
    NULL_TRACER,
    Histogram,
    MetricsRegistry,
    Span,
    Tracer,
    format_metrics_line,
    phase_breakdown,
    validate_chrome_trace,
)


# ---------------------------------------------------------------------------
# tracer unit tests (no model)
# ---------------------------------------------------------------------------


class _FakeClock:
    """Deterministic monotonic clock: one tick per call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_ring_buffer_wraparound():
    tr = Tracer(capacity=4, clock=_FakeClock())
    for i in range(7):
        tr.event(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 3
    got = [s.name for s in tr.spans]
    assert got == ["e3", "e4", "e5", "e6"]  # newest survive, oldest first
    t0s = [s.t0 for s in tr.spans]
    assert t0s == sorted(t0s)
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0 and tr.spans == []


def test_span_nesting_depth_and_attrs():
    tr = Tracer(clock=_FakeClock())
    with tr.span("step"):
        with tr.span("prefill", lanes=3):
            with tr.span("dispatch:prefill_paged"):
                pass
    by_name = {s.name: s for s in tr.spans}
    assert by_name["step"].depth == 0
    assert by_name["prefill"].depth == 1
    assert by_name["dispatch:prefill_paged"].depth == 2
    assert by_name["prefill"].attrs == {"lanes": 3}
    # spans record on exit: children land in the ring before parents
    assert [s.name for s in tr.spans] == [
        "dispatch:prefill_paged", "prefill", "step",
    ]
    for s in tr.spans:
        assert s.t1 > s.t0 and not s.instant


def test_sync_tracer_calls_barrier_at_both_edges():
    calls = []
    tr = Tracer(sync=True, sync_fn=lambda: calls.append(1),
                clock=_FakeClock())
    with tr.span("step"):
        pass
    assert len(calls) == 2  # entry + exit barrier
    # sync=True with no barrier wired is a silent no-op, not an error
    tr2 = Tracer(sync=True, clock=_FakeClock())
    with tr2.span("step"):
        pass
    assert len(tr2) == 1


def test_chrome_export_schema_and_tags(tmp_path):
    tr = Tracer(clock=_FakeClock(), tags={"mesh_model": 2})
    with tr.span("step"):
        with tr.span("decode", lanes=2):
            tr.event("first_token", rid=0)
    path = tmp_path / "trace.json"
    tr.export_chrome_trace(path)
    obj = json.load(open(path))  # round-trip through real JSON
    assert validate_chrome_trace(obj) == 3
    events = {e["name"]: e for e in obj["traceEvents"]}
    assert events["thread_name"]["ph"] == "M"
    assert events["step"]["ph"] == "X" and events["step"]["dur"] > 0
    inst = events["first_token"]
    assert inst["ph"] == "i" and inst["s"] == "t" and "dur" not in inst
    # tracer tags land on every event, merged with span attrs
    assert events["decode"]["args"] == {"mesh_model": 2, "lanes": 2}
    assert inst["args"] == {"mesh_model": 2, "rid": 0}
    assert obj["otherData"]["dropped_spans"] == 0


def test_validate_chrome_trace_rejects_malformed():
    ok = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
                           "pid": 0, "tid": 0}]}
    assert validate_chrome_trace(ok) == 1
    bad = [
        [],  # not an object
        {},  # no traceEvents
        {"traceEvents": [{"name": "a", "ph": "Z", "ts": 0, "pid": 0,
                          "tid": 0}]},  # unknown phase
        {"traceEvents": [{"name": "", "ph": "X", "ts": 0, "dur": 1,
                          "pid": 0, "tid": 0}]},  # empty name
        {"traceEvents": [{"name": "a", "ph": "X", "ts": -1, "dur": 1,
                          "pid": 0, "tid": 0}]},  # negative ts
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0, "pid": 0,
                          "tid": 0}]},  # complete event without dur
        {"traceEvents": [{"name": "a", "ph": "i", "ts": 0, "dur": 1,
                          "pid": 0, "tid": 0}]},  # instant carrying dur
        {"traceEvents": [{"name": "m", "ph": "M", "pid": 0, "tid": 0}]},
        # metadata only -> no events
    ]
    for obj in bad:
        with pytest.raises(ValueError):
            validate_chrome_trace(obj)


def test_phase_breakdown_math():
    spans = [
        Span("step", 0.0, 10.0, 0),
        Span("prefill", 0.0, 4.0, 1),
        Span("decode", 4.0, 9.0, 1),
        Span("dispatch:decode_paged", 4.0, 8.0, 2),  # nested: not a phase
        Span("first_token", 5.0, 5.0, 1, instant=True),  # mark: excluded
    ]
    pb = phase_breakdown(spans)
    assert pb["root_s"] == 10.0 and pb["root_count"] == 1
    assert set(pb["phases"]) == {"prefill", "decode"}
    assert pb["phases"]["prefill"]["share"] == pytest.approx(0.4)
    assert pb["coverage"] == pytest.approx(0.9)
    assert phase_breakdown([])["coverage"] == 0.0


def test_null_tracer_records_nothing_and_is_cheap():
    h = NULL_TRACER.span("step", lanes=4)
    assert h is NULL_TRACER.span("decode")  # one shared no-op handle
    NULL_TRACER.event("first_token", rid=1)
    assert len(NULL_TRACER) == 0 and NULL_TRACER.spans == []
    assert not NULL_TRACER.enabled
    # overhead guardrail: a disabled span site must stay within a few µs
    # per hit (one method call + a no-op context manager)
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with NULL_TRACER.span("step"):
            pass
    per_hit = (time.perf_counter() - t0) / n
    assert per_hit < 5e-6, f"disabled span site costs {per_hit * 1e6:.2f}µs"


# ---------------------------------------------------------------------------
# metrics unit tests
# ---------------------------------------------------------------------------


def test_histogram_percentiles_match_numpy_and_empty_is_none():
    h = Histogram("ttft_s")
    assert h.percentile(50) is None and h.summary()["mean"] is None
    xs = [0.5, 0.1, 0.9, 0.3, 0.7]
    for x in xs:
        h.observe(x)
    assert h.count == 5 and h.sum == pytest.approx(2.5)
    for q in (50, 99):
        assert h.percentile(q) == float(np.percentile(np.asarray(xs), q))
    s = h.summary()
    assert s["count"] == 5 and s["p50"] == 0.5
    # None (not NaN) keeps the serialized record strict-JSON-parseable
    assert "null" in json.dumps(Histogram("itl_s").summary())


def test_metrics_registry_snapshot_and_reset():
    reg = MetricsRegistry()
    reg.inc("steps")
    reg.inc("decode_tokens", 5)
    reg.counter("prefill_batch_size").peak(3)
    reg.counter("prefill_batch_size").peak(2)  # high-water mark keeps 3
    reg.gauge("occupancy").set(0.5)
    live = {"v": 7}
    reg.gauge("pages_in_use", fn=lambda: live["v"])
    reg.histogram("ttft_s").observe(0.25)
    s = reg.snapshot()
    assert s["steps"] == 1 and s["decode_tokens"] == 5
    assert s["prefill_batch_size"] == 3
    assert s["occupancy"] == 0.5 and s["pages_in_use"] == 7
    assert s["ttft_s_count"] == 1 and s["ttft_s_p50"] == 0.25
    assert reg.counter("steps") is reg.counter("steps")  # idempotent
    reg.reset()
    live["v"] = 9
    s = reg.snapshot()
    assert s["steps"] == 0 and s["occupancy"] == 0
    assert s["pages_in_use"] == 9  # callback gauges track live state
    assert s["ttft_s_count"] == 0 and s["ttft_s_p50"] is None


def test_format_metrics_line_skips_empty_histograms():
    line = format_metrics_line(
        {"steps": 3, "occupancy": 0.25, "itl_s_p50": None},
        t=1.5, keys=["steps", "occupancy", "itl_s_p50", "missing"],
    )
    assert line == "[metrics t=1.5s] steps=3 occupancy=0.25"


# ---------------------------------------------------------------------------
# engine integration: tracing never changes tokens, and reports honestly
# ---------------------------------------------------------------------------


def _smoke_cfg():
    return get_smoke_config("qwen3-14b")


def _run(adapter, prompts, gen, *, tracer=None, **ecfg_kw):
    kw = dict(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8,
    )
    kw.update(ecfg_kw)
    engine = Engine(adapter, EngineConfig(**kw), tracer=tracer)
    reqs = [
        engine.submit(np.asarray(p), max_new=gen, arrival=0.01 * i)
        for i, p in enumerate(prompts)
    ]
    engine.run()
    return engine, reqs


@pytest.fixture(scope="module")
def fp_model():
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _parity(adapter_fn, prompts, gen, **ecfg_kw):
    """Token streams must be identical with and without a sync tracer."""
    _, base = _run(adapter_fn(), prompts, gen, **ecfg_kw)
    tr = Tracer(sync=True)
    engine, traced = _run(adapter_fn(), prompts, gen, tracer=tr, **ecfg_kw)
    for a, b in zip(base, traced):
        np.testing.assert_array_equal(
            np.asarray(a.out_tokens), np.asarray(b.out_tokens)
        )
    return engine, tr


def test_tracer_parity_fp_paged(fp_model):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=3).tokens
    engine, tr = _parity(
        lambda: CachedDecoder.from_model(model, params), prompts, 5,
        paged_decode=True, paged_prefill=True,
    )
    names = {s.name for s in tr.spans}
    assert {"step", "schedule", "prefill", "decode",
            "dispatch:prefill_paged", "dispatch:decode_paged"} <= names


def test_tracer_parity_speculative(fp_model):
    cfg, model, params = fp_model
    # repetitive prompts so the ngram drafter actually proposes
    rng = np.random.default_rng(5)
    base = rng.integers(1, cfg.vocab, size=(3, 6)).astype(np.int32)
    prompts = np.concatenate([base, base], axis=1)
    engine, tr = _parity(
        lambda: CachedDecoder.from_model(model, params), prompts, 6,
        paged_decode=True, speculative_k=2, device_sample=True,
    )
    names = {s.name for s in tr.spans}
    assert {"verify", "draft", "dispatch:verify_paged"} <= names


@pytest.fixture(scope="module")
def quantized_smoke():
    from repro.launch.quantize import quantize_dense_model

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    calib = make_calibration(cfg.vocab, n_segments=4, seg_len=32, seed=7)
    qcfg = QuipConfig(bits=2, method="ldlq", use_kernel=False)
    qm = quantize_dense_model(params, cfg, qcfg, calib.tokens, seed=0,
                              verbose=False)
    return cfg, qm


def test_tracer_parity_quantized(quantized_smoke):
    cfg, qm = quantized_smoke
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=5).tokens
    _parity(
        lambda: CachedDecoder.from_quantized(qm), prompts, 4,
        paged_decode=True,
    )


def test_engine_trace_coverage_lifecycle_and_schema(fp_model, tmp_path):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=4).tokens
    tr = Tracer(sync=True)
    engine, reqs = _run(
        CachedDecoder.from_model(model, params), prompts, 5, tracer=tr,
        paged_decode=True, paged_prefill=True,
    )
    # acceptance gate: phase spans account for >= 95% of step time
    pb = phase_breakdown(tr.spans)
    assert pb["root_count"] == engine.stats["steps"]
    assert pb["coverage"] >= 0.95
    # every request leaves a full lifecycle trail
    events = [s for s in tr.spans if s.instant]
    for kind in ("request_admitted", "first_token", "request_finished"):
        rids = {s.attrs["rid"] for s in events if s.name == kind}
        assert rids == {r.rid for r in reqs}, kind
    # exported JSON passes the same schema gate CI runs
    path = tmp_path / "engine_trace.json"
    tr.export_chrome_trace(path)
    assert validate_chrome_trace(json.load(open(path))) == len(tr)
    # span timestamps share the request-arrival epoch (Engine.now)
    admits = [s for s in events if s.name == "request_admitted"]
    assert all(s.t0 >= 0 for s in admits)
    assert all(s.attrs["queue_s"] >= 0 for s in admits)


def test_engine_native_percentiles_match_external(fp_model):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=10,
                               seed=6).tokens
    engine, reqs = _run(
        CachedDecoder.from_model(model, params), prompts, 5,
        paged_decode=True,
    )
    s = engine.summary()
    done = [r for r in reqs if r.t_first is not None]
    ttft = [r.t_first - r.arrival for r in done]
    itl = [b - a for r in done
           for a, b in zip(r.token_times, r.token_times[1:])]
    e2e = [r.t_finish - r.arrival for r in done]
    for name, ext in (("ttft_s", ttft), ("itl_s", itl), ("e2e_s", e2e)):
        assert s[f"{name}_count"] == len(ext)
        for q in (50, 99):
            want = float(np.percentile(np.asarray(ext), q))
            assert s[f"{name}_p{q}"] == pytest.approx(want, abs=1e-12), name
    # summary() must serialize: empty histograms are null, never NaN
    json.dumps(s)


def test_engine_stats_property_and_clock(fp_model):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=8,
                               seed=7).tokens
    engine, reqs = _run(
        CachedDecoder.from_model(model, params), prompts, 3,
        paged_decode=True,
    )
    # legacy dict view over the registry counters
    stats = engine.stats
    assert stats["steps"] > 0
    assert stats["decode_tokens"] + stats["prefill_tokens"] > 0
    # the clock starts at construction (no first-call skew): every
    # recorded timestamp is strictly positive engine-relative seconds
    assert all(t > 0 for r in reqs for t in r.token_times)
    before = engine.now()
    engine.reset_clock()
    assert engine.now() < before
    engine.reset_stats()
    assert engine.stats["steps"] == 0
    assert engine.summary()["ttft_s_count"] == 0


def test_engine_metrics_every_emits_snapshots(fp_model, capfd):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=8,
                               seed=8).tokens
    adapter = CachedDecoder.from_model(model, params)
    engine = Engine(adapter, EngineConfig(
        max_seq_len=8 + 3, n_slots=4, page_size=4, token_budget=32,
        prefill_chunk=8, paged_decode=True,
    ))
    for i, p in enumerate(prompts):
        engine.submit(np.asarray(p), max_new=3, arrival=0.01 * i)
    engine.run(metrics_every=1e-6)
    err = capfd.readouterr().err
    assert "[metrics t=" in err and "steps=" in err


# ---------------------------------------------------------------------------
# spans and counters inside the tick, compile gauges, token stamps
# ---------------------------------------------------------------------------


def _children(spans, parent):
    """Names of the spans one level below ``parent`` inside its interval,
    in start order (instant marks excluded)."""
    return [s.name for s in sorted(spans, key=lambda s: s.t0)
            if not s.instant and s.depth == parent.depth + 1
            and parent.t0 <= s.t0 and s.t1 <= parent.t1]


def test_tick_spans_split_prepare_dispatch_wait(fp_model):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=9).tokens
    tr = Tracer()
    engine, reqs = _run(
        CachedDecoder.from_model(model, params), prompts, 5, tracer=tr,
        paged_decode=True, paged_prefill=True, device_sample=True,
    )
    spans = tr.spans
    steps = [s for s in spans if s.name == "step"]
    assert len(steps) == engine.stats["steps"]
    n_decode = n_prefill = 0
    for step in steps:
        phases = _children(spans, step)
        assert phases[0] == "schedule"
        assert set(phases) <= {"schedule", "prefill", "decode"}
        for ph in (s for s in spans if s.name in ("prefill", "decode")
                   and step.t0 <= s.t0 and s.t1 <= step.t1):
            inner = _children(spans, ph)
            if ph.name == "decode":
                n_decode += 1
                assert inner == ["decode:prepare",
                                 "dispatch:decode_paged_sample",
                                 "decode:wait", "emit"]
            else:
                n_prefill += 1
                assert inner[:2] == ["prefill:prepare",
                                     "dispatch:prefill_paged"]
                # each prompt finished this tick: its logits, its token
                assert inner[2:] == ["prefill:wait",
                                     "prefill:boundary"] * (
                    (len(inner) - 2) // 2)
    assert n_decode and n_prefill
    # one first token, so one boundary, per request
    assert sum(s.name == "prefill:boundary" for s in spans) == len(reqs)
    # the new spans sit below the phases: the breakdown is unchanged
    pb = phase_breakdown(spans)
    assert set(pb["phases"]) == {"schedule", "prefill", "decode"}
    assert pb["coverage"] >= 0.95


def test_decode_page_counters_by_hand(fp_model):
    """Two requests of 5 and 9 prompt tokens on 4-token pages: the first
    tick prefills both, then each decode tick dispatches 4 lanes against
    a block table 4 pages wide (3 pages of the 9-token context, bucketed
    up to a power of two), while the lanes hold 2 + 3 live pages with
    their new tokens."""
    cfg, model, params = fp_model
    engine = Engine(CachedDecoder.from_model(model, params), EngineConfig(
        max_seq_len=16, n_slots=4, page_size=4, token_budget=32,
        prefill_chunk=16, paged_decode=True, paged_prefill=True))
    rng = np.random.default_rng(0)
    for n in (5, 9):
        engine.submit(rng.integers(1, cfg.vocab, n), max_new=4)
    engine.tick()  # both prompts prefill
    assert engine.stats["decode_pages_attended"] == 0
    engine.tick()  # contexts 5, 9: pages of 6 and 10 tokens
    assert engine.stats["decode_pages_live"] == 2 + 3
    assert engine.stats["decode_pages_attended"] == 4 * 4
    engine.tick()  # contexts 6, 10: pages of 7 and 11 tokens
    assert engine.stats["decode_pages_live"] == (2 + 3) * 2
    assert engine.stats["decode_pages_attended"] == 4 * 4 * 2


def test_compile_gauges_count_new_shapes_only(fp_model):
    cfg, model, params = fp_model
    engine = Engine(CachedDecoder.from_model(model, params), EngineConfig(
        max_seq_len=16, n_slots=2, page_size=4, paged_decode=True))
    keys = ("compiles", "compile_s", "compile_cache_reads")
    assert all(k in engine.summary() for k in keys)
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x7, x9 = np.ones(7, np.float32), np.ones(9, np.float32)
    c0 = engine.summary()["compiles"]
    f(x7).block_until_ready()
    c1 = engine.summary()["compiles"]
    f(x7).block_until_ready()  # the same shape: no compile
    c2 = engine.summary()["compiles"]
    f(x9).block_until_ready()  # a new shape: one
    c3 = engine.summary()["compiles"]
    assert (c1 - c0, c2 - c1, c3 - c2) == (1, 0, 1)
    assert engine.summary()["compile_s"] > 0
    # process-wide: a second engine reads the same count
    other = Engine(CachedDecoder.from_model(model, params), EngineConfig(
        max_seq_len=16, n_slots=2, page_size=4, paged_decode=True))
    assert other.summary()["compiles"] == engine.summary()["compiles"]


def test_null_tracer_records_nothing_through_a_run(fp_model):
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=8,
                               seed=10).tokens
    engine, _ = _run(CachedDecoder.from_model(model, params), prompts, 3,
                     paged_decode=True, paged_prefill=True,
                     device_sample=True)
    assert engine.tracer is NULL_TRACER
    assert len(NULL_TRACER) == 0 and NULL_TRACER.spans == []


def test_programs_carry_the_incoherence_and_sampler_scopes(
        quantized_smoke):
    cfg, qm = quantized_smoke
    ad = CachedDecoder.from_quantized(qm)
    pool = ad.make_pool(n_pages=8, page_size=4, n_slots=2,
                        max_pages_per_seq=4, dtype=None, prefix_cache=False)
    z = lambda *s: np.zeros(s, np.int32)
    decode = ad._fwd_paged_s.lower(
        ad.weights, z(2, 1), z(2, 1), z(2, 4), z(2), z(2), z(2),
        np.full(2, 0.7, np.float32), np.ones(2, np.float32), z(2), z(2),
        pool.arrays, False).as_text(debug_info=True)
    prefill = ad._fwd_prefill.lower(
        ad.weights, z(2, 8), z(2, 8), z(2, 4), z(2), z(2, 8), z(2, 8),
        pool.arrays).as_text(debug_info=True)
    for text in (decode, prefill):
        assert "incoherence/v/" in text and "incoherence/u_t/" in text
    assert "/sampler/" in decode and "/sampler/" not in prefill


def test_token_stamps_are_when_the_host_holds_the_token(fp_model):
    """Each token is stamped once the host has read it: a decode token
    within 1 ms of the moment the tick returns it (what the benchmark
    harness stamps), a first token (read at its prefill boundary, before
    the tick's decode) no later than that and after the tick began."""
    cfg, model, params = fp_model
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=11).tokens
    engine = Engine(CachedDecoder.from_model(model, params), EngineConfig(
        max_seq_len=prompts.shape[1] + 6, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8, paged_decode=True,
        paged_prefill=True, device_sample=True))
    reqs = [engine.submit(np.asarray(p), max_new=6) for p in prompts]
    returned = {r.rid: [] for r in reqs}
    starts = {r.rid: [] for r in reqs}
    while not engine.idle:
        t0 = engine.now()
        res = engine.tick()
        t = engine.now()
        for req, _ in res.emitted:
            returned[req.rid].append(t)
            starts[req.rid].append(t0)
    for r in reqs:
        eng, drv = np.asarray(r.token_times), np.asarray(returned[r.rid])
        assert len(eng) == len(drv) == 6
        assert np.all(eng <= drv) and np.all(eng >= starts[r.rid])
        np.testing.assert_array_less(drv[1:] - eng[1:], 1e-3)
        assert r.t_first == eng[0]


def test_annotated_span_lines_up_with_the_profiler_trace(tmp_path):
    """One clock: a span recorded with ``annotate=True`` and its event in
    the profiler's trace agree, after the exported offset, on start and
    on duration within 1 ms; instant marks enter the trace too."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.01)
        with tr.span("decode:wait"):
            time.sleep(0.02)
        tr.event("first_token", rid=0)
    finally:
        jax.profiler.stop_trace()
    offset = tr.export_chrome_trace(tmp_path / "t.json")[
        "otherData"]["profiler_clock_offset_ns"]
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    pd = ProfileData.from_file(path)
    start = events = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name in ("decode:wait", "first_token"):
                    events = (events or {}) | {e.name: e}
    span = next(s for s in tr.spans if s.name == "decode:wait")
    ev = events["decode:wait"]
    assert abs(start + ev.start_ns - (span.t0 * 1e9 + offset)) < 1e6
    assert abs(ev.duration_ns - span.dur * 1e9) < 1e6
    assert "first_token" in events
