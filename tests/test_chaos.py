"""Chaos / robustness tests (serve/faults.py + engine failure domains).

The core invariant, asserted after every injected fault: pool pages and
prefix-trie refcounts return to baseline, and untouched requests emit
token streams bit-identical to a fault-free run — blast radius is
exactly one request.  Also covered: cancel from every lifecycle state,
deadlines, typed admission backpressure, the eviction-storm guard that
replaces the evict/replay livelock, artifact shard integrity, the
fault-plan grammar, and a hypothesis sweep over pool op interleavings.
"""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import (
    CachedDecoder,
    Engine,
    EngineConfig,
    PagedKVPool,
    RequestState,
)
from repro.serve.faults import (
    FAULT_KINDS,
    AdmissionRejected,
    FaultInjected,
    FaultPlan,
    FaultRule,
    parse_fault_plan,
)


def _smoke_cfg():
    return get_smoke_config("qwen3-14b")


@pytest.fixture(scope="module")
def fp_ctx():
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=10,
                               seed=3).tokens
    return cfg, model, params, prompts


GEN = 8

# engine paths the fault matrix sweeps; greedy host selection keeps every
# path token-identical to the dense baseline
PATHS = {
    "dense": dict(),
    "paged": dict(paged_decode=True),
    "spec": dict(paged_decode=True, speculative_k=3),
}


def _engine(model, params, *, faults=None, **kw):
    ecfg = dict(max_seq_len=24, n_slots=4, page_size=4, token_budget=32,
                prefill_chunk=8)
    ecfg.update(kw)
    return Engine(CachedDecoder.from_model(model, params),
                  EngineConfig(**ecfg), faults=faults)


def _run(engine, prompts, gen=GEN, **submit_kw):
    reqs = [engine.submit(np.asarray(p), max_new=gen, **submit_kw)
            for p in prompts]
    engine.run()
    return reqs


def _assert_pool_clean(engine):
    pool = engine.pool
    assert not pool._slots, "live slots after drain"
    assert pool.pages_in_use == pool.cached_pages, "leaked pages"
    # free list exact: every non-scratch page is either free or trie-held
    free = set(pool._free_pages)
    for p in range(1, pool.n_pages):
        assert (p in free) == (pool._page_ref[p] == 0)


@pytest.fixture(scope="module")
def baseline(fp_ctx):
    """Fault-free greedy tokens per prompt index (identical on every
    engine path — asserted by test_serve; recomputed once here)."""
    cfg, model, params, prompts = fp_ctx
    reqs = _run(_engine(model, params), prompts)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert all(r.finish_reason == "length" for r in reqs)
    return [list(r.out_tokens) for r in reqs]


# ---------------------------------------------------------------------------
# Fault matrix: every injectable kind x every engine path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize(
    "kind", ["alloc_fail", "nan_logits", "dispatch_error", "cancel"]
)
def test_fault_blast_radius_is_one_request(fp_ctx, baseline, path, kind):
    """Inject one fault at a known (kind, rid): the target terminates
    with that reason, every other request is token-identical to the
    fault-free run, and the pool returns to baseline."""
    cfg, model, params, prompts = fp_ctx
    target = 2
    plan = FaultPlan()
    eng = _engine(model, params, faults=plan,
                  screen_logits=(kind == "nan_logits"), **PATHS[path])
    reqs = [eng.submit(np.asarray(p), max_new=GEN) for p in prompts]
    plan.rules.append(FaultRule(
        kind=kind, rid=reqs[target].rid,
        tick=6 if kind == "cancel" else None,
    ))
    eng.run()

    victim = reqs[target]
    if kind == "cancel":
        assert victim.state is RequestState.CANCELLED
        assert victim.finish_reason == "cancelled"
        assert eng.stats["cancelled"] == 1
    else:
        assert victim.state is RequestState.FAILED
        assert victim.finish_reason == kind
        assert eng.stats["failed"] == 1
    # an early-terminated stream is a PREFIX of the fault-free one,
    # never a corruption of it
    out = list(victim.out_tokens)
    assert out == baseline[target][: len(out)]
    for i, r in enumerate(reqs):
        if i == target:
            continue
        assert r.state is RequestState.FINISHED
        assert list(r.out_tokens) == baseline[i], f"survivor {i} diverged"
    assert len(plan.log) == 1 and plan.log[0]["kind"] == kind
    assert eng.metrics.snapshot()[f"fault:{kind}"] == 1
    assert eng.metrics.snapshot()[f"finish:{victim.finish_reason}"] == 1
    _assert_pool_clean(eng)


def test_pool_exhausted_fault_is_transient(fp_ctx, baseline):
    """A pool-level admit/extend denial is NOT fatal: the engine routes
    it through its normal eviction/requeue machinery and every request
    still finishes with exact tokens."""
    cfg, model, params, prompts = fp_ctx
    plan = FaultPlan(rules=[FaultRule(kind="pool_exhausted", times=2)])
    eng = _engine(model, params, faults=plan, paged_decode=True)
    reqs = _run(eng, prompts)
    assert len(plan.log) == 2
    for i, r in enumerate(reqs):
        assert r.state is RequestState.FINISHED
        assert list(r.out_tokens) == baseline[i]
    _assert_pool_clean(eng)


def test_quantized_path_fault_quarantine():
    """The fault matrix on packed 2-bit weights: quantized co-batched
    lanes survive a poisoned lane token-identically."""
    from repro.launch.quantize import quantize_dense_model
    from repro.core.quantizer import QuipConfig

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    calib = make_calibration(cfg.vocab, n_segments=4, seg_len=32, seed=7)
    qm = quantize_dense_model(
        params, cfg, QuipConfig(bits=2, method="ldlq", use_kernel=False),
        calib.tokens, seed=0, verbose=False,
    )
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=5).tokens
    base = _run(Engine(CachedDecoder.from_quantized(qm), EngineConfig(
        max_seq_len=18, n_slots=3, page_size=4, token_budget=32,
        prefill_chunk=8, paged_decode=True)), prompts, gen=6)
    plan = FaultPlan()
    eng = Engine(CachedDecoder.from_quantized(qm), EngineConfig(
        max_seq_len=18, n_slots=3, page_size=4, token_budget=32,
        prefill_chunk=8, paged_decode=True, screen_logits=True),
        faults=plan)
    reqs = [eng.submit(np.asarray(p), max_new=6) for p in prompts]
    plan.rules.append(FaultRule(kind="nan_logits", rid=reqs[1].rid))
    eng.run()
    assert reqs[1].state is RequestState.FAILED
    assert reqs[1].finish_reason == "nan_logits"
    for i in (0, 2):
        assert list(reqs[i].out_tokens) == list(base[i].out_tokens)
    _assert_pool_clean(eng)


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a multi-device host "
           "(jax_num_cpu_devices, conftest.py)",
)
def test_tp_engine_fault_quarantine(fp_ctx, baseline):
    """TP parity under faults: cancel + NaN quarantine on a 2-way model
    mesh leave survivors token-identical to the single-device baseline
    (the fault hooks are host-side, so the shard_map dispatches never
    see the plan)."""
    from repro.serve import DistributedCachedDecoder, make_serving_mesh

    cfg, model, params, prompts = fp_ctx
    mesh = make_serving_mesh(1, 2)
    plan = FaultPlan()
    eng = Engine(
        DistributedCachedDecoder.from_model(model, params, mesh=mesh),
        EngineConfig(max_seq_len=24, n_slots=4, page_size=4,
                     token_budget=32, prefill_chunk=8, paged_decode=True,
                     screen_logits=True),
        faults=plan,
    )
    reqs = [eng.submit(np.asarray(p), max_new=GEN) for p in prompts]
    plan.rules.append(FaultRule(kind="nan_logits", rid=reqs[1].rid))
    plan.rules.append(FaultRule(kind="cancel", rid=reqs[3].rid, tick=7))
    eng.run()
    assert reqs[1].state is RequestState.FAILED
    assert reqs[1].finish_reason == "nan_logits"
    assert reqs[3].state is RequestState.CANCELLED
    assert list(reqs[3].out_tokens) == baseline[3][: len(reqs[3].out_tokens)]
    for i in (0, 2):
        assert list(reqs[i].out_tokens) == baseline[i]
    _assert_pool_clean(eng)


# ---------------------------------------------------------------------------
# cancel() from every lifecycle state
# ---------------------------------------------------------------------------


def test_cancel_from_queued_and_unknown_and_terminal(fp_ctx, baseline):
    cfg, model, params, prompts = fp_ctx
    eng = _engine(model, params)
    reqs = [eng.submit(np.asarray(p), max_new=GEN) for p in prompts]
    assert eng.cancel(reqs[1].rid)  # still QUEUED (no step yet)
    assert reqs[1].state is RequestState.CANCELLED
    assert reqs[1].out_tokens == []
    assert not eng.cancel(reqs[1].rid)  # already terminal
    assert not eng.cancel(10**9)  # unknown rid
    eng.run()
    for i in (0, 2, 3):
        assert list(reqs[i].out_tokens) == baseline[i]
    _assert_pool_clean(eng)


def test_cancel_mid_prefill_releases_pages(fp_ctx):
    """Cancel while the prompt is mid-chunked-prefill: pages claimed so
    far release, the co-scheduled request is unaffected."""
    cfg, model, params, _ = fp_ctx
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=16,
                               seed=9).tokens
    base = _run(_engine(model, params, max_seq_len=24, prefill_chunk=4),
                prompts, gen=4)
    plan = FaultPlan()
    eng = _engine(model, params, max_seq_len=24, prefill_chunk=4,
                  faults=plan)
    reqs = [eng.submit(np.asarray(p), max_new=4) for p in prompts]
    # 16-token prompt / 4-token chunks: tick 2 is mid-prefill
    plan.rules.append(FaultRule(kind="cancel", rid=reqs[0].rid, tick=2))
    eng.run()
    assert reqs[0].state is RequestState.CANCELLED
    assert reqs[0].out_tokens == []  # never reached its first token
    assert list(reqs[1].out_tokens) == list(base[1].out_tokens)
    _assert_pool_clean(eng)


def test_cancel_mid_speculative_verify(fp_ctx, baseline):
    """Cancel landing between speculative ticks: accepted tokens stay (a
    prefix of the baseline), draft pages and the slot release."""
    cfg, model, params, prompts = fp_ctx
    plan = FaultPlan()
    eng = _engine(model, params, faults=plan, paged_decode=True,
                  speculative_k=3)
    reqs = [eng.submit(np.asarray(p), max_new=GEN) for p in prompts]
    plan.rules.append(FaultRule(kind="cancel", rid=reqs[2].rid, tick=5))
    eng.run()
    assert reqs[2].state is RequestState.CANCELLED
    out = list(reqs[2].out_tokens)
    assert out == baseline[2][: len(out)]
    for i in (0, 1, 3):
        assert list(reqs[i].out_tokens) == baseline[i]
    _assert_pool_clean(eng)


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


def test_deadline_fails_expired_request_only(fp_ctx, baseline):
    cfg, model, params, prompts = fp_ctx
    eng = _engine(model, params)
    doomed = eng.submit(np.asarray(prompts[0]), max_new=GEN,
                        deadline_s=1e-9)
    ok = eng.submit(np.asarray(prompts[1]), max_new=GEN)
    eng.run()
    assert doomed.state is RequestState.FAILED
    assert doomed.finish_reason == "deadline"
    assert eng.stats["deadline_missed"] == 1
    assert ok.state is RequestState.FINISHED
    assert list(ok.out_tokens) == baseline[1]
    _assert_pool_clean(eng)


def test_engine_default_deadline_applies(fp_ctx):
    cfg, model, params, prompts = fp_ctx
    eng = _engine(model, params, deadline_s=1e-9)
    reqs = _run(eng, prompts[:2])
    assert all(r.state is RequestState.FAILED for r in reqs)
    assert all(r.finish_reason == "deadline" for r in reqs)
    # per-request override wins over the engine default
    eng2 = _engine(model, params, deadline_s=1e-9)
    r = eng2.submit(np.asarray(prompts[0]), max_new=4, deadline_s=60.0)
    eng2.run()
    assert r.state is RequestState.FINISHED


# ---------------------------------------------------------------------------
# Typed admission backpressure
# ---------------------------------------------------------------------------


def test_admission_rejected_over_capacity(fp_ctx):
    cfg, model, params, prompts = fp_ctx
    eng = _engine(model, params)  # seq capacity 24 tokens
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(np.asarray(prompts[0]), max_new=100)
    e = ei.value
    assert isinstance(e, ValueError)  # old except-sites keep working
    assert e.reason == "over_capacity" and not e.retryable
    assert e.needed_pages > e.available_pages


def test_admission_rejected_queue_full_is_retryable(fp_ctx):
    cfg, model, params, prompts = fp_ctx
    eng = _engine(model, params, max_queue=2)
    eng.submit(np.asarray(prompts[0]), max_new=4)
    eng.submit(np.asarray(prompts[1]), max_new=4)
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(np.asarray(prompts[2]), max_new=4)
    assert ei.value.reason == "queue_full" and ei.value.retryable
    assert ei.value.pending == 2 and ei.value.limit == 2
    assert eng.stats["admission_rejected"] == 1
    eng.run()  # drain: the queue frees, a retry now succeeds
    r = eng.submit(np.asarray(prompts[2]), max_new=4)
    eng.run()
    assert r.state is RequestState.FINISHED


def test_admission_capacity_is_prefix_cache_aware(fp_ctx):
    """A prompt whose leading pages the trie already holds is not
    rejected for pages it will never claim: the same submit that a cold
    pool rejects is admitted once the prefix is cached."""
    cfg, model, params, _ = fp_ctx
    prompts = make_calibration(cfg.vocab, n_segments=1, seg_len=16,
                               seed=11).tokens
    geo = dict(max_seq_len=24, page_size=4, n_pages=6, n_slots=2,
               prefix_cache=True, prefill_chunk=8)
    # cold pool: 16 prompt + 8 gen = 6 pages > the 5 usable -> rejected
    cold = _engine(model, params, **geo)
    with pytest.raises(AdmissionRejected) as ei:
        cold.submit(np.asarray(prompts[0]), max_new=8)
    assert ei.value.reason == "over_capacity"
    # warm the trie with the same prompt (4 full pages) at a size that
    # fits outright, then retry the submit that was just rejected
    warm = _engine(model, params, **geo)
    _run(warm, prompts, gen=4)
    assert warm.pool.cached_prefix_pages(prompts[0]) == 4
    req = warm.submit(np.asarray(prompts[0]), max_new=8)
    assert req is not None


# ---------------------------------------------------------------------------
# Evict/replay pathologies: the queue-head capacity backstop and the
# eviction-storm guard
# ---------------------------------------------------------------------------


def test_outgrown_prefix_fails_capacity_not_stall(fp_ctx):
    """Submit's capacity forecast is optimistic (prefix-cache discount,
    and ``max_new`` is only a ceiling), so a cached 16-token prompt with
    8 requested tokens is admitted into a pool whose 5 usable pages can
    never hold the resulting 6-page prefix.  When generation actually
    outgrows the pool the request must FAIL cleanly ("capacity") at the
    queue-head feasibility backstop — the pre-backstop behavior was an
    engine-wide stall (the requeued head could never be re-admitted and
    the run loop span until its backstop RuntimeError)."""
    cfg, model, params, _ = fp_ctx
    prompts = make_calibration(cfg.vocab, n_segments=1, seg_len=16,
                               seed=11).tokens
    geo = dict(max_seq_len=24, page_size=4, n_pages=6, n_slots=2,
               prefix_cache=True, prefill_chunk=8)
    warm = _engine(model, params, **geo)
    _run(warm, prompts, gen=4)  # seed the trie so the discount admits
    doomed = warm.submit(np.asarray(prompts[0]), max_new=8)
    warm.run()  # must terminate, not stall into the run-loop backstop
    assert doomed.state is RequestState.FAILED
    assert doomed.finish_reason == "capacity"
    # it decoded up to the pool's physical edge before failing
    assert len(doomed.out_tokens) > 0
    assert warm.stats["evictions"] >= 1
    assert warm.metrics.counter("finish:capacity").value == 1
    _assert_pool_clean(warm)


STORM_GENS = (24, 16, 16)


def _storm_run(model, params, prompts, cap):
    """Three co-tenants over a pool that holds any two: the newest is
    repeatedly evicted at the elders' page boundaries and replays its
    prefix each time (readmission maps its cached prompt pages shared)."""
    geo = dict(max_seq_len=40, page_size=4, n_pages=10, n_slots=3,
               token_budget=32, prefix_cache=True, prefill_chunk=8,
               max_evictions=cap)
    eng = _engine(model, params, **geo)
    reqs = [eng.submit(np.asarray(p), max_new=g)
            for p, g in zip(prompts, STORM_GENS)]
    eng.run()
    return eng, reqs


@pytest.fixture(scope="module")
def storm_prompts(fp_ctx):
    cfg = fp_ctx[0]
    return make_calibration(cfg.vocab, n_segments=3, seg_len=8,
                            seed=5).tokens


@pytest.fixture(scope="module")
def storm_baseline(fp_ctx, storm_prompts):
    """Same workload over an ample pool: no pressure, no evictions."""
    _, model, params, _ = fp_ctx
    eng = _engine(model, params, max_seq_len=40, n_pages=24, n_slots=3,
                  page_size=4, token_budget=32, prefill_chunk=8)
    reqs = [eng.submit(np.asarray(p), max_new=g)
            for p, g in zip(storm_prompts, STORM_GENS)]
    eng.run()
    return [list(r.out_tokens) for r in reqs]


def test_evict_replay_thrash_without_guard(fp_ctx, storm_prompts,
                                           storm_baseline):
    """With the storm cap disabled the newest co-tenant is evicted and
    replays repeatedly (burning recompute each round) before everything
    converges — the wasted work the guard exists to bound.  Replay
    determinism: every stream still matches the pressure-free run."""
    _, model, params, _ = fp_ctx
    eng, reqs = _storm_run(model, params, storm_prompts, cap=None)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert eng.stats["evictions"] >= 3
    assert max(r.n_evictions for r in reqs) >= 2  # same victim, twice
    for r, want in zip(reqs, storm_baseline):
        assert list(r.out_tokens) == want
    _assert_pool_clean(eng)


def test_eviction_storm_guard_fails_cleanly(fp_ctx, storm_prompts,
                                            storm_baseline):
    """Same workload with ``max_evictions=1``: the thrashing request
    FAILS with its own reason at its second eviction instead of
    replaying again, the co-tenants finish token-identically to the
    pressure-free run, and the pool returns to baseline."""
    _, model, params, _ = fp_ctx
    eng, reqs = _storm_run(model, params, storm_prompts, cap=1)
    stormed = [r for r in reqs if r.state is RequestState.FAILED]
    assert len(stormed) == 1
    assert stormed[0].finish_reason == "eviction_storm"
    assert stormed[0].n_evictions == 1
    assert eng.metrics.counter("finish:eviction_storm").value == 1
    for r, want in zip(reqs, storm_baseline):
        if r.state is RequestState.FINISHED:
            assert list(r.out_tokens) == want
    _assert_pool_clean(eng)


# ---------------------------------------------------------------------------
# Artifact integrity (per-shard SHA-256)
# ---------------------------------------------------------------------------


def _save_tiny(tmp_path):
    from repro.checkpoint.store import save_checkpoint

    tree = {"a": np.arange(8, dtype=np.float32).reshape(2, 4),
            "b": {"c": np.ones((3,), np.int32)}}
    return save_checkpoint(tmp_path / "ckpt", 0, tree,
                           extra_meta={"kind": "test"}), tree


def test_shard_digest_roundtrip_and_corruption(tmp_path):
    from repro.checkpoint.store import ArtifactCorruption, load_arrays

    step_dir, tree = _save_tiny(tmp_path)
    manifest = json.loads((step_dir / "manifest.json").read_text())
    assert len(manifest["shard_digests"]) == manifest["n_shards"] >= 1
    arrays, _, _meta = load_arrays(tmp_path / "ckpt")
    np.testing.assert_array_equal(arrays["a"], tree["a"])
    # rot shard 0's recorded digest: verify must name the shard (same
    # failure mode as rotting the bytes, without also breaking the zip)
    manifest["shard_digests"][0] = "0" * 64
    (step_dir / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(ArtifactCorruption) as ei:
        load_arrays(tmp_path / "ckpt")
    assert ei.value.shard == 0
    assert "shard 0" in str(ei.value) and "sha256" in str(ei.value)
    assert isinstance(ei.value, ValueError)  # launch except-sites catch it
    # verify=False is the explicit escape hatch
    load_arrays(tmp_path / "ckpt", verify=False)


def test_predigest_manifest_warns_not_fails(tmp_path):
    from repro.checkpoint.store import load_arrays

    step_dir, _ = _save_tiny(tmp_path)
    mpath = step_dir / "manifest.json"
    manifest = json.loads(mpath.read_text())
    del manifest["shard_digests"]
    mpath.write_text(json.dumps(manifest))
    with pytest.warns(UserWarning, match="predates shard checksums"):
        load_arrays(tmp_path / "ckpt")


def test_corrupt_shard_fault_injection(tmp_path):
    from repro.checkpoint.store import ArtifactCorruption, load_arrays

    _save_tiny(tmp_path)
    plan = parse_fault_plan("corrupt_shard@shard=0")
    with pytest.raises(ArtifactCorruption):
        load_arrays(tmp_path / "ckpt",
                    _corrupt_shards=plan.corrupt_shards())
    assert plan.rules[0].fired == 1


# ---------------------------------------------------------------------------
# Fault-plan grammar
# ---------------------------------------------------------------------------


def test_parse_fault_plan_grammar():
    plan = parse_fault_plan(
        "alloc_fail@rid=0;nan_logits@rid=2,times=3;cancel@rid=4,tick=6"
    )
    kinds = [r.kind for r in plan.rules]
    assert kinds == ["alloc_fail", "nan_logits", "cancel"]
    assert plan.rules[1].times == 3
    assert plan.rules[2].tick == 6
    assert all(k in FAULT_KINDS for k in kinds)


@pytest.mark.parametrize("bad", [
    "", "frobnicate", "alloc_fail@bogus=1", "alloc_fail@tick=x",
    "cancel", "alloc_fail@times=0",
])
def test_parse_fault_plan_rejects(bad):
    with pytest.raises(ValueError):
        parse_fault_plan(bad)


def test_fault_rules_consume_and_log():
    plan = FaultPlan(rules=[FaultRule(kind="alloc_fail", rid=7, times=2)])
    assert plan.fire("alloc_fail", rid=7)
    assert plan.fire("alloc_fail", rid=7)
    assert not plan.fire("alloc_fail", rid=7)  # consumed
    assert not plan.fire("alloc_fail", rid=8)  # wrong rid never fires
    assert len(plan.log) == 2
    with pytest.raises(ValueError):
        FaultRule(kind="cancel")  # cancel must name a rid
    with pytest.raises(ValueError):
        FaultRule(kind="nope")


# The hypothesis pool-leak audit lives in test_chaos_properties.py (the
# repo's property sweeps skip as a module when hypothesis is missing;
# the deterministic chaos tests above must run regardless).
