"""Tensor-parallel serving tests (serve/distributed.py).

Need a multi-device host: tests/conftest.py splits the CPU into 8
virtual devices before the backend starts; with a single device the
whole module skips.
Everything asserts TOKEN-IDENTICAL behavior vs the single-device engine —
sharding is a layout choice, never a numerics choice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_hessian, make_weights

from repro.configs import get_smoke_config
from repro.core.quantizer import QuipConfig, quantize_layer
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import (
    CachedDecoder,
    DistributedCachedDecoder,
    Engine,
    EngineConfig,
    make_serving_mesh,
    save_quantized,
)
from repro.serve.distributed import (
    PACKED_AXES,
    shard_quantized_linear,
    shard_quantized_model,
)
from repro.runtime.sharding import MeshContext, serving_rules

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a multi-device host (jax_num_cpu_devices, conftest.py)",
)


@pytest.fixture(scope="module")
def mesh():
    return make_serving_mesh(1, 2)


@pytest.fixture(scope="module")
def ctx(mesh):
    return MeshContext(mesh=mesh, rules=serving_rules())


def _smoke_cfg():
    return get_smoke_config("qwen3-14b")


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
    return cfg, qm, qcfg


def _run_engine(adapter, prompts, gen, **ecfg_kw):
    kw = dict(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8, paged_decode=True,
    )
    kw.update(ecfg_kw)
    engine = Engine(adapter, EngineConfig(**kw))
    reqs = [engine.submit(np.asarray(p), max_new=gen) for p in prompts]
    engine.run()
    return engine, [np.asarray(r.out_tokens) for r in reqs]


# ---------------------------------------------------------------------------
# Sharded quantized linears
# ---------------------------------------------------------------------------


def test_sharded_linear_outputs_match_unsharded(ctx):
    """Column- and row-parallel packed placements both reproduce the
    unsharded structured inference path (up to matmul reassociation)."""
    W, H = make_weights(64, 128, seed=3), make_hessian(128, seed=3)
    layer, _ = quantize_layer(
        W, H, QuipConfig(bits=2, use_kernel=False), seed=1,
        collect_stats=False,
    )
    x = make_weights(5, 128, seed=9)
    y0 = np.asarray(layer(x))
    for name in ("attn.wq", "attn.wo"):  # one column-, one row-parallel
        sharded = shard_quantized_linear(layer, ctx, name)
        y = np.asarray(jax.jit(lambda xx: sharded(xx))(x))
        np.testing.assert_allclose(y, y0, rtol=0, atol=1e-5)


def test_shard_quantized_model_layout_and_originals(ctx, quantized_smoke):
    """Every packed tensor lands model-axis-sharded per PACKED_AXES; the
    input model's arrays are untouched (fully replicated, single device)."""
    _, qm, _ = quantized_smoke
    sq = shard_quantized_model(qm, ctx)
    for blk, blk0 in zip(sq.blocks, qm.blocks):
        for name, axes in PACKED_AXES.items():
            if name not in blk:
                continue
            spec = tuple(blk[name].packed.sharding.spec)
            want = tuple("model" if a else None for a in axes)
            assert spec == want, (name, spec)
            # original stays where it was
            assert len(blk0[name].packed.devices()) == 1


# ---------------------------------------------------------------------------
# Sharded artifact load round-trip
# ---------------------------------------------------------------------------


def test_sharded_artifact_load_roundtrip(tmp_path, mesh, quantized_smoke):
    """load(mesh=...) streams packed codes straight onto the mesh and the
    resulting per-linear outputs match the plainly-loaded artifact."""
    from repro.serve.artifacts import load_quantized

    cfg, qm, qcfg = quantized_smoke
    save_quantized(tmp_path / "art", qm, qcfg)
    adapter, meta = DistributedCachedDecoder.load(tmp_path / "art", mesh=mesh)
    assert meta["quip_config"]["bits"] == 2
    qm_plain, _ = load_quantized(tmp_path / "art")
    for blk_s, blk_p in zip(adapter.blocks, qm_plain.blocks):
        for name in PACKED_AXES:
            if name not in blk_s:
                continue
            lin_s, lin_p = blk_s[name], blk_p[name]
            assert "model" in tuple(lin_s.packed.sharding.spec)
            x = make_weights(3, lin_p.n, seed=13)
            np.testing.assert_allclose(
                np.asarray(lin_s(x)), np.asarray(lin_p(x)), rtol=0, atol=1e-5
            )


# ---------------------------------------------------------------------------
# Sharded page pool
# ---------------------------------------------------------------------------


def _adapters(mesh):
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return (
        CachedDecoder.from_model(model, params),
        DistributedCachedDecoder.from_model(model, params, mesh=mesh),
        model, params,
    )


@pytest.mark.parametrize("dtype", [None, jnp.int8])
def test_sharded_pool_accounting_and_roundtrip(mesh, dtype):
    """The sharded pool is byte-for-byte the same accounting machine as
    the single-device pool (admit/extend/release are host-side), its
    physical pages split over KV heads (device_bytes == total/mp), and
    write/gather round-trips bit-identically."""
    plain, dist, *_ = _adapters(mesh)
    kw = dict(n_pages=9, page_size=4, n_slots=3, max_pages_per_seq=4,
              dtype=dtype)
    p0, p1 = plain.make_pool(**kw), dist.make_pool(**kw)
    mp = mesh.shape["model"]
    assert p1.total_bytes() == p0.total_bytes()
    assert p1.device_bytes() == p0.total_bytes() // mp
    assert tuple(p1.k.sharding.spec) == (None, None, "model", None, None)
    # identical admit/extend/evict decisions
    for pool in (p0, p1):
        a = pool.admit(5)
        b = pool.admit(9)
        assert (a, b) == (0, 1)
        assert pool.extend(a, 8) and not pool.extend(b, 17)
        pool.release(b)
        assert pool.pages_in_use == 2
    # write/gather round-trip through the sharded buffers
    cfg = _smoke_cfg()
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    k = jax.random.normal(jax.random.PRNGKey(2), (L, 6, KV, hd), jnp.float32)
    for pool in (p0, p1):
        pool.write_span(0, 0, 6, k, -k)
    g0, g1 = p0.gather([0])[0], p1.gather([0])[0]
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


# ---------------------------------------------------------------------------
# TP engine vs single-device engine: token parity
# ---------------------------------------------------------------------------


def test_tp_engine_fp_token_parity(mesh):
    plain, dist, model, params = _adapters(mesh)
    cfg = model.cfg
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=3).tokens
    _, t0 = _run_engine(plain, prompts, 6)
    eng, t1 = _run_engine(dist, prompts, 6)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)
    assert eng.pool.device_bytes() * mesh.shape["model"] \
        == eng.pool.total_bytes()


def test_tp_engine_quantized_token_parity(mesh, quantized_smoke):
    """The ISSUE acceptance check: a 2-device model mesh serving sharded
    packed weights over the sharded pool emits the exact token stream of
    the single-device paged engine."""
    cfg, qm, _ = quantized_smoke
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=12,
                               seed=5).tokens
    _, t0 = _run_engine(CachedDecoder.from_quantized(qm), prompts, 5)
    _, t1 = _run_engine(
        DistributedCachedDecoder.from_quantized(qm, mesh=mesh), prompts, 5
    )
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_int8_pages_token_parity(mesh):
    plain, dist, model, params = _adapters(mesh)
    cfg = model.cfg
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=9,
                               seed=8).tokens
    _, t0 = _run_engine(plain, prompts, 5, kv_int8=True)
    _, t1 = _run_engine(dist, prompts, 5, kv_int8=True)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_eviction_token_parity(mesh):
    """Eviction/requeue (host-side scheduling over the sharded pool) and
    re-prefill through the sharded gather path keep exact tokens."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8,
                               seed=4).tokens
    dist = DistributedCachedDecoder.from_model(model, params, mesh=mesh)
    eng, t1 = _run_engine(dist, prompts, 8, n_slots=3, page_size=4,
                          n_pages=10)
    assert eng.stats["evictions"] > 0
    plain = CachedDecoder.from_model(model, params)
    _, t0 = _run_engine(plain, prompts, 8, n_slots=3, page_size=4,
                        n_pages=10)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_batched_prefill_token_parity(mesh):
    """Batched paged prefill under shard_map (chunk attention over the
    KV-head-sharded pool) emits the single-device engine's exact tokens."""
    plain, dist, model, params = _adapters(mesh)
    cfg = model.cfg
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=3).tokens
    _, t0 = _run_engine(plain, prompts, 6, paged_prefill=True)
    eng, t1 = _run_engine(dist, prompts, 6, paged_prefill=True)
    assert eng.stats["prefill_batches"] > 0
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_batched_prefill_int8_token_parity(mesh):
    plain, dist, model, params = _adapters(mesh)
    cfg = model.cfg
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=9,
                               seed=8).tokens
    _, t0 = _run_engine(plain, prompts, 5, paged_prefill=True, kv_int8=True)
    _, t1 = _run_engine(dist, prompts, 5, paged_prefill=True, kv_int8=True)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_tp_engine_write_kernel_token_and_pool_parity(mesh, monkeypatch,
                                                      kv_int8):
    """The pool write as the Pallas kernel (interpret mode) under the TP
    adapter's ``shard_map``, through batched prefill and decode: the
    single-device engine's tokens, and bit for bit the pool that the XLA
    scatter writes under the same ``shard_map``."""
    import repro.serve.distributed as dist_mod
    from repro.serve.kv_cache import write_kv

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=9,
                               seed=8).tokens
    kw = dict(paged_prefill=True, kv_int8=kv_int8)

    def tp_run():
        dist = DistributedCachedDecoder.from_model(
            model, params, mesh=mesh, paged_interpret=True
        )
        eng, toks = _run_engine(dist, prompts, 4, **kw)
        assert dist._pool_sharded
        return eng.pool.arrays, toks

    _, t0 = _run_engine(
        CachedDecoder.from_model(model, params, paged_interpret=True),
        prompts, 4, **kw,
    )
    pool, t1 = tp_run()
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)
    # the same engine with the XLA scatter in the shard_map (interpret off
    # for the write alone)
    monkeypatch.setattr(dist_mod, "write_kv",
                        lambda *a: write_kv(*a[:8]))
    want, t2 = tp_run()
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(pool, want):
        if a is None:
            assert b is None
            continue
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8)
        )


def test_tp_engine_prefix_cache_token_parity(mesh):
    """Prefix-cache hits over the sharded pool: the COW page copy and
    shared-page mapping are layout-preserving (host accounting is device-
    agnostic), so TP serving with prefix caching matches single-device."""
    plain, dist, model, params = _adapters(mesh)
    cfg = model.cfg
    base = make_calibration(cfg.vocab, n_segments=1, seg_len=8, seed=5).tokens
    prompts = np.tile(np.asarray(base), (3, 1))  # 8 tokens == 2 full pages

    def run(adapter):
        engine = Engine(adapter, EngineConfig(
            max_seq_len=prompts.shape[1] + 5, n_slots=4, page_size=4,
            token_budget=32, prefill_chunk=8, paged_decode=True,
            paged_prefill=True, prefix_cache=True,
        ))
        reqs = [
            engine.submit(np.asarray(p), max_new=5, arrival=0.2 * i)
            for i, p in enumerate(prompts)
        ]
        engine.run()
        return engine, [np.asarray(r.out_tokens) for r in reqs]

    e0, t0 = run(plain)
    e1, t1 = run(dist)
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)
    s0, s1 = e0.summary(), e1.summary()
    assert s1["prefix_hit_tokens"] == s0["prefix_hit_tokens"] > 0
    assert s1["cow_copies"] == s0["cow_copies"] >= 1  # copy-on-admit ran


def test_indivisible_kv_heads_fall_back_replicated(quantized_smoke):
    """A model axis the KV-head count cannot divide degrades to the
    replicated pool + single-device attention math — same tokens, no
    crash (the divisibility fallback)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices for an indivisible model axis")
    cfg, qm, _ = quantized_smoke
    assert cfg.n_kv_heads % 4 != 0  # smoke config has 2 KV heads
    mesh4 = make_serving_mesh(1, 4)
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=10,
                               seed=6).tokens
    _, t0 = _run_engine(CachedDecoder.from_quantized(qm), prompts, 4)
    dist = DistributedCachedDecoder.from_quantized(qm, mesh=mesh4)
    eng, t1 = _run_engine(dist, prompts, 4)
    assert not dist._pool_sharded
    assert eng.pool.device_bytes() == eng.pool.total_bytes()
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_speculative_token_parity(mesh):
    """Speculative draft-and-verify under shard_map: the TP engine's
    greedy stream is token-identical to the single-device speculative
    engine (which is itself pinned to the one-token path), with real
    draft acceptance on a cyclic workload."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.tile(np.asarray([7, 91, 33, 150], np.int32), (3, 8))
    gen = 10
    kw = dict(speculative_k=4, device_sample=True)
    eng_tp, tp = _run_engine(
        DistributedCachedDecoder.from_model(model, params, mesh=mesh),
        prompts, gen, **kw,
    )
    assert eng_tp.summary()["accepted_tokens"] > 0
    _, single = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen, **kw,
    )
    for a, b in zip(tp, single):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_speculative_int8_token_parity(mesh):
    """Speculative verify over int8 sharded pages (round-tripped chunk
    K/V + fp diagonal override, all under shard_map) matches the
    single-device int8 speculative engine exactly."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.tile(np.asarray([7, 91, 33, 150], np.int32), (3, 8))
    gen = 8
    kw = dict(speculative_k=4, device_sample=True, kv_int8=True)
    _, tp = _run_engine(
        DistributedCachedDecoder.from_model(model, params, mesh=mesh),
        prompts, gen, **kw,
    )
    _, single = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen, **kw,
    )
    for a, b in zip(tp, single):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_device_sampled_stream_parity(mesh):
    """On-device sampling (fold_in keys) is layout-independent: the TP
    engine draws the exact sampled stream of the single-device engine."""
    from repro.serve.scheduler import SamplingParams

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=2).tokens
    gen = 6
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=23)

    def run(adapter):
        engine = Engine(adapter, EngineConfig(
            max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
            token_budget=32, prefill_chunk=8, paged_decode=True,
            device_sample=True,
        ))
        reqs = [engine.submit(np.asarray(p), max_new=gen, sampling=sp)
                for p in prompts]
        engine.run()
        return [np.asarray(r.out_tokens) for r in reqs]

    tp = run(DistributedCachedDecoder.from_model(model, params, mesh=mesh))
    single = run(CachedDecoder.from_model(model, params))
    for a, b in zip(tp, single):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Telemetry under TP: tracing never changes tokens; spans carry mesh tags
# ---------------------------------------------------------------------------


def test_tp_engine_traced_token_parity_and_mesh_tags(mesh, tmp_path):
    """A sync tracer attached to the TP engine must not perturb the token
    stream, and every exported span must carry the mesh geometry tags
    (DistributedCachedDecoder.trace_tags) so distributed traces stay
    interpretable offline."""
    from repro.serve import Tracer, phase_breakdown, validate_chrome_trace

    plain, dist, model, params = _adapters(mesh)
    cfg = model.cfg
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10,
                               seed=9).tokens
    gen = 5
    _, t0 = _run_engine(plain, prompts, gen)
    tracer = Tracer(sync=True)
    engine = Engine(dist, EngineConfig(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8, paged_decode=True,
    ), tracer=tracer)
    reqs = [engine.submit(np.asarray(p), max_new=gen) for p in prompts]
    engine.run()
    for a, b in zip(t0, [np.asarray(r.out_tokens) for r in reqs]):
        np.testing.assert_array_equal(a, b)
    assert tracer.tags["mesh_model"] == mesh.shape["model"]
    assert tracer.tags["mesh_data"] == mesh.shape["data"]
    assert tracer.tags["pool_sharded"] is True
    obj = tracer.export_chrome_trace(tmp_path / "tp_trace.json")
    validate_chrome_trace(obj)
    spans = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    assert spans and all(
        e["args"]["mesh_model"] == mesh.shape["model"] for e in spans
    )
    assert phase_breakdown(tracer.spans)["coverage"] >= 0.95


# ---------------------------------------------------------------------------
# Quality canaries under TP: the sharded canary scores what the unsharded
# one would (DESIGN.md §13)
# ---------------------------------------------------------------------------


def test_tp_canary_nll_matches_single_device(mesh):
    """The teacher-forced canary probe runs the same dense trunk on
    single-device and TP adapters; GSPMD sharding is a layout choice,
    so the NLL must agree to float tolerance (the probe's NLL itself is
    float64 on host — any divergence is real logit drift)."""
    from repro.serve.quality import teacher_forced_nll

    plain, dist, model, _ = _adapters(mesh)
    canary = make_calibration(model.cfg.vocab, n_segments=2, seg_len=12,
                              seed=99).tokens
    single = teacher_forced_nll(plain, canary)
    sharded = teacher_forced_nll(dist, canary)
    assert abs(single - sharded) < 1e-6


def test_tp_engine_canary_gauge_matches_offline(mesh):
    """End-to-end: a TP engine's canary gauge equals the offline
    teacher-forced NLL computed through the same sharded adapter."""
    from repro.serve.quality import teacher_forced_nll

    _, dist, model, _ = _adapters(mesh)
    cfg = model.cfg
    canary = make_calibration(cfg.vocab, n_segments=2, seg_len=12,
                              seed=99).tokens
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=8,
                               seed=3).tokens
    gen = 3
    engine = Engine(dist, EngineConfig(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8, paged_decode=True,
        canary_every=1e-4,
    ))
    engine.attach_canary(canary)
    for p in prompts:
        engine.submit(np.asarray(p), max_new=gen)
    engine.run()
    s = engine.summary()
    assert s["canary_runs"] >= 1
    assert s["canary_nll"] == teacher_forced_nll(dist, canary)
