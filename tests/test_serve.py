"""Serving subsystem tests: paged KV pool invariants, continuous-batching
engine equivalence with the recompute/dense-cache reference paths (fp and
quantized), eviction-under-pressure recovery, and quantized-artifact
save/load round-trips."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_hessian, make_weights

from repro.configs import get_smoke_config
from repro.core.quantizer import (
    QuipConfig,
    linear_from_arrays,
    linear_to_arrays,
    quantize_layer,
)
from repro.data import make_calibration
from repro.models import build_model
from repro.serve import CachedDecoder, Engine, EngineConfig, PagedKVPool
from repro.serve.artifacts import load_quantized, save_quantized


def _smoke_cfg():
    return get_smoke_config("qwen3-14b")


# ---------------------------------------------------------------------------
# PagedKVPool invariants
# ---------------------------------------------------------------------------


def _pool(n_pages=9, page_size=4, n_slots=3, max_pages=4):
    return PagedKVPool(
        _smoke_cfg(), n_pages=n_pages, page_size=page_size, n_slots=n_slots,
        max_pages_per_seq=max_pages,
    )


def test_pool_admit_extend_release_accounting():
    pool = _pool()  # 8 usable pages
    assert pool.pages_in_use == 0
    a = pool.admit(5)  # 2 pages
    b = pool.admit(4)  # 1 page
    assert a is not None and b is not None and a != b
    assert pool.pages_in_use == 3
    assert pool.extend(a, 8)  # no new page needed
    assert pool.pages_in_use == 3
    assert pool.extend(a, 9)  # 3rd page
    assert pool.pages_in_use == 4
    pool.release(a)
    assert pool.pages_in_use == 1
    pool.release(b)
    assert pool.pages_in_use == 0
    assert pool.peak_pages_in_use == 4


def test_pool_admit_exhaustion_and_slot_limits():
    pool = _pool(n_pages=5, n_slots=2)  # 4 usable pages
    a = pool.admit(16)  # 4 pages: everything
    assert a is not None
    assert pool.admit(1) is None  # no pages left
    pool.release(a)
    a = pool.admit(1)
    b = pool.admit(1)
    assert a is not None and b is not None
    assert pool.admit(1) is None  # no slots left
    assert not pool.extend(a, 17)  # over max_pages_per_seq
    assert pool.fits(16) and not pool.fits(17)


def test_pool_extend_fails_without_free_pages():
    pool = _pool(n_pages=4, n_slots=2)  # 3 usable
    a = pool.admit(8)  # 2 pages
    b = pool.admit(4)  # 1 page
    assert not pool.extend(a, 9)  # would need a 3rd page
    pool.release(b)
    assert pool.extend(a, 9)


def test_pool_write_gather_roundtrip():
    cfg = _smoke_cfg()
    pool = _pool(page_size=4, max_pages=2)
    slot = pool.admit(6)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    k = jnp.arange(L * 6 * KV * hd, dtype=jnp.float32).reshape(L, 6, KV, hd)
    pool.write_span(slot, 0, 6, k, -k)
    assert pool.length(slot) == 6
    gk, gv = pool.gather([slot, None])
    assert gk.shape == (L, 2, 8, KV, hd)
    np.testing.assert_array_equal(np.asarray(gk[:, 0, :6]), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(gv[:, 0, :6]), np.asarray(-k))
    # single-token write at position 6 (second page)
    tok_k = jnp.full((L, 1, KV, hd), 7.0)
    pool.write([slot], [6], tok_k, tok_k)
    gk, _ = pool.gather([slot])
    np.testing.assert_array_equal(np.asarray(gk[:, 0, 6]), np.asarray(tok_k[:, 0]))
    assert pool.length(slot) == 7


# ---------------------------------------------------------------------------
# Engine equivalence vs reference decode paths
# ---------------------------------------------------------------------------


def _run_engine(adapter, prompts, gen, *, arrival_gap=0.0, **ecfg_kw):
    kw = dict(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8, record_logits=True,
    )
    kw.update(ecfg_kw)
    engine = Engine(adapter, EngineConfig(**kw))
    reqs = [
        engine.submit(np.asarray(p), max_new=gen, arrival=i * arrival_gap)
        for i, p in enumerate(prompts)
    ]
    engine.run()
    return engine, reqs


def test_engine_fp_matches_dense_cache_path():
    """Engine (paged cache, continuous batching, chunked prefill) must
    reproduce Model.prefill/decode_step logits and greedy tokens."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10, seed=3).tokens
    gen = 6
    _, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        arrival_gap=0.01,
    )
    ref_toks = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        assert len(r.out_tokens) == gen
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref_toks[i])
    # logits equivalence (cached engine decode vs dense-cache decode),
    # recompute-free reference: full forward over prompt+generated
    full = np.concatenate([np.asarray(prompts), ref_toks], axis=1)
    hidden, _ = model.forward(params, {"tokens": jnp.asarray(full)})
    ref_logits = np.asarray(model.logits(params, hidden))
    S = prompts.shape[1]
    for i, r in enumerate(reqs):
        got = np.stack(r.step_logits)  # (gen, V)
        want = ref_logits[i, S - 1 : S - 1 + gen]
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


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


def test_engine_quantized_matches_recompute(quantized_smoke):
    """Cached decode through the packed D^-1 -> V -> quant_matmul -> U^T
    path == the old per-token full-recompute, token-for-token."""
    from repro.launch.serve import quantized_generate

    cfg, qm, _ = quantized_smoke
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=12, seed=5).tokens
    gen = 5
    _, reqs = _run_engine(
        CachedDecoder.from_quantized(qm), prompts, gen, arrival_gap=0.01,
    )
    ref = np.asarray(quantized_generate(qm, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])
    # logits along the way must match the recompute oracle too
    for i, r in enumerate(reqs):
        seq = jnp.asarray(
            np.concatenate([np.asarray(prompts[i]), ref[i][:-1]])[None]
        )
        want = np.asarray(qm.logits(seq))[0, prompts.shape[1] - 1 :]
        np.testing.assert_allclose(
            np.stack(r.step_logits), want, rtol=2e-3, atol=2e-3
        )


# ---------------------------------------------------------------------------
# Paged fast path (in-place pool attention) vs the gather-dense oracle
# ---------------------------------------------------------------------------


def test_engine_paged_fp_matches_reference():
    """--check-style equivalence for the paged fast path: decode through
    the paged-attention dispatch (no per-step dense KV gather) must emit
    the exact greedy tokens of the dense-cache reference, logits included."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10, seed=3).tokens
    gen = 6
    _, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        arrival_gap=0.01, paged_decode=True,
    )
    ref_toks = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref_toks[i])
    full = np.concatenate([np.asarray(prompts), ref_toks], axis=1)
    hidden, _ = model.forward(params, {"tokens": jnp.asarray(full)})
    ref_logits = np.asarray(model.logits(params, hidden))
    S = prompts.shape[1]
    for i, r in enumerate(reqs):
        got = np.stack(r.step_logits)
        want = ref_logits[i, S - 1 : S - 1 + gen]
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_engine_paged_quantized_matches_recompute(quantized_smoke):
    """Paged decode with QuantizedLinear projections routed through the
    quant_matmul kernel dispatch == the per-token recompute oracle."""
    from repro.launch.serve import quantized_generate

    cfg, qm, _ = quantized_smoke
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=12, seed=5).tokens
    gen = 5
    _, reqs = _run_engine(
        CachedDecoder.from_quantized(qm), prompts, gen, arrival_gap=0.01,
        paged_decode=True,
    )
    ref = np.asarray(quantized_generate(qm, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_paged_int8_matches_gather_int8():
    """int8 pages: the paged kernel path dequantizes the same stored pages
    as the gather-dense oracle — token streams must agree exactly."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=9, seed=8).tokens
    gen = 5
    runs = []
    for paged in (False, True):
        _, reqs = _run_engine(
            CachedDecoder.from_model(model, params), prompts, gen,
            paged_decode=paged, kv_int8=True,
        )
        runs.append([np.asarray(r.out_tokens) for r in reqs])
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a, b)


def test_engine_paged_eviction_under_page_pressure():
    """Eviction/requeue still reproduces exact tokens when decode runs the
    paged fast path (re-prefill after eviction goes through the oracle
    prefill into the same pool the kernel then reads)."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=4).tokens
    gen = 8
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        n_slots=3, page_size=4, n_pages=10, paged_decode=True,
    )
    assert engine.stats["evictions"] > 0
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_paged_interpret_kernel_end_to_end():
    """The actual Pallas kernel (interpret mode) inside the fused decode
    dispatch — not just the jnp fallback — agrees with the reference."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=1, seg_len=10, seed=3).tokens
    gen = 3
    _, reqs = _run_engine(
        CachedDecoder.from_model(model, params, paged_interpret=True),
        prompts, gen, n_slots=2, paged_decode=True,
    )
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    np.testing.assert_array_equal(np.asarray(reqs[0].out_tokens), ref[0])


# ---------------------------------------------------------------------------
# Batched paged prefill (one fused cross-request dispatch per tick)
# ---------------------------------------------------------------------------


def test_engine_batched_prefill_fp_matches_reference():
    """Cross-request batched paged prefill must emit the exact greedy
    tokens AND logits of the dense-cache reference."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10, seed=3).tokens
    gen = 6
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        arrival_gap=0.01, paged_decode=True, paged_prefill=True,
    )
    assert engine.stats["prefill_batches"] > 0
    ref_toks = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref_toks[i])
    full = np.concatenate([np.asarray(prompts), ref_toks], axis=1)
    hidden, _ = model.forward(params, {"tokens": jnp.asarray(full)})
    ref_logits = np.asarray(model.logits(params, hidden))
    S = prompts.shape[1]
    for i, r in enumerate(reqs):
        got = np.stack(r.step_logits)
        want = ref_logits[i, S - 1 : S - 1 + gen]
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_engine_batched_prefill_batches_multiple_lanes():
    """Co-arriving requests actually share one prefill dispatch (the
    scheduler's co-batchable group, not a B=1 loop)."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=8, seed=3).tokens
    engine, _ = _run_engine(
        CachedDecoder.from_model(model, params), prompts, 2,
        paged_decode=True, paged_prefill=True, token_budget=64,
    )
    assert engine.stats["prefill_batch_size"] >= 4


def test_engine_batched_prefill_quantized_matches_recompute(quantized_smoke):
    from repro.launch.serve import quantized_generate

    cfg, qm, _ = quantized_smoke
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=12, seed=5).tokens
    gen = 5
    _, reqs = _run_engine(
        CachedDecoder.from_quantized(qm), prompts, gen, arrival_gap=0.01,
        paged_decode=True, paged_prefill=True,
    )
    ref = np.asarray(quantized_generate(qm, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_batched_prefill_int8_matches_gather_int8():
    """int8 pages: the batched paged-prefill engine writes the same pages
    (shared quantizer) the gather-dense int8 engine reads — exact tokens."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=9, seed=8).tokens
    gen = 5
    runs = []
    for paged in (False, True):
        _, reqs = _run_engine(
            CachedDecoder.from_model(model, params), prompts, gen,
            paged_decode=paged, paged_prefill=paged, kv_int8=True,
        )
        runs.append([np.asarray(r.out_tokens) for r in reqs])
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a, b)


def test_engine_batched_prefill_eviction_under_page_pressure():
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=4).tokens
    gen = 8
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        n_slots=3, page_size=4, n_pages=10, paged_decode=True,
        paged_prefill=True,
    )
    assert engine.stats["evictions"] > 0
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_batched_prefill_interpret_kernel_end_to_end():
    """The actual chunked-prefill Pallas kernel (interpret mode) inside
    the fused dispatch — not just the jnp fallback — end to end."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=10, seed=3).tokens
    gen = 3
    _, reqs = _run_engine(
        CachedDecoder.from_model(model, params, paged_interpret=True),
        prompts, gen, n_slots=2, paged_decode=True, paged_prefill=True,
    )
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


# ---------------------------------------------------------------------------
# Prefix cache: trie hits, refcounts, copy-on-write, eviction
# ---------------------------------------------------------------------------


def test_engine_prefix_cache_skips_recompute_same_tokens():
    """Identical prompts: later admissions map cached pages (hit tokens
    counted, prefill work reduced) and still emit reference tokens."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base = make_calibration(cfg.vocab, n_segments=1, seg_len=12, seed=3).tokens
    prompts = np.tile(np.asarray(base), (3, 1))
    gen = 5
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        arrival_gap=0.2, paged_decode=True, paged_prefill=True,
        prefix_cache=True,
    )
    s = engine.summary()
    # 12-token prompts, 4-token pages: 2 later requests x >= 8 cached
    assert s["prefix_hit_tokens"] >= 16
    assert s["cached_pages"] >= 2
    assert s["prefill_tokens"] <= 3 * 12 - s["prefix_hit_tokens"]
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_prefix_cache_page_aligned_full_hit():
    """A prompt that is entirely cached full pages: admission maps a
    private COPY of the last page (copy-on-admit), recomputes only the
    final token, and emits the reference stream."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base = make_calibration(cfg.vocab, n_segments=1, seg_len=8, seed=5).tokens
    prompts = np.tile(np.asarray(base), (2, 1))  # 8 tokens == 2 full pages
    gen = 4
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        arrival_gap=0.2, paged_decode=True, paged_prefill=True,
        prefix_cache=True,
    )
    s = engine.summary()
    assert s["prefix_hit_tokens"] == 7  # capped at len(prompt) - 1
    assert s["cow_copies"] >= 1  # the copy-on-admit of the last page
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_prefix_cache_survives_eviction_pressure():
    """Prefix cache + overcommitted pool: cache-only pages are reclaimed
    under pressure, eviction/replay still reproduces exact tokens."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=4).tokens
    gen = 8
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        n_slots=3, page_size=4, n_pages=10, paged_decode=True,
        paged_prefill=True, prefix_cache=True,
    )
    assert engine.stats["evictions"] > 0
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def _prefix_pool(**kw):
    args = dict(n_pages=13, page_size=4, n_slots=4, max_pages_per_seq=4,
                prefix_cache=True)
    args.update(kw)
    return PagedKVPool(_smoke_cfg(), **args)


def test_pool_prefix_trie_hit_and_refcounts():
    cfg = _smoke_cfg()
    pool = _prefix_pool()
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    toks = np.arange(10, dtype=np.int32)
    k = jnp.arange(L * 10 * KV * hd, dtype=jnp.float32).reshape(L, 10, KV, hd)
    a = pool.admit(10, tokens=toks)
    assert pool.length(a) == 0  # cold cache
    pool.write_span(a, 0, 10, k, -k)
    pool.register_prefix(a, toks)
    assert pool.cached_pages == 2  # two full 4-token pages of the prompt
    b = pool.admit(10, tokens=toks)
    assert pool.length(b) == 8  # both full pages mapped
    assert pool.shared_pages == 2 and pool.max_page_ref == 3
    gk, gv = pool.gather([b])
    np.testing.assert_array_equal(np.asarray(gk[:, 0, :8]), np.asarray(k[:, :8]))
    np.testing.assert_array_equal(np.asarray(gv[:, 0, :8]), np.asarray(-k[:, :8]))
    # different tokens past page 1 -> only one page matches
    toks2 = toks.copy()
    toks2[6] += 1
    c = pool.admit(10, tokens=toks2)
    assert pool.length(c) == 4
    # releasing the original keeps cached pages alive via the trie's refs
    pool.release(a)
    d = pool.admit(10, tokens=toks)
    assert pool.length(d) == 8


def test_pool_copy_on_write_divergence():
    """Writing into a shared page copies it first: the original owner's
    (and the cache's) view is untouched, the writer's view diverges."""
    cfg = _smoke_cfg()
    pool = _prefix_pool()
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    toks = np.arange(8, dtype=np.int32)
    k = jnp.arange(L * 8 * KV * hd, dtype=jnp.float32).reshape(L, 8, KV, hd)
    a = pool.admit(10, tokens=toks)
    pool.write_span(a, 0, 8, k, -k)
    pool.register_prefix(a, toks)
    b = pool.admit(10, tokens=toks)
    assert pool.length(b) == 8 and pool.shared_pages == 2
    assert pool.cow_copies == 0
    # b diverges INSIDE the shared prefix (e.g. a fork edited upstream)
    patch = jnp.full((L, 1, KV, hd), 99.0)
    pool.write_span(b, 5, 1, patch, patch)
    assert pool.cow_copies == 1
    ga, _ = pool.gather([a])
    np.testing.assert_array_equal(np.asarray(ga[:, 0, :8]), np.asarray(k))
    gb, _ = pool.gather([b])
    np.testing.assert_array_equal(np.asarray(gb[:, 0, 5]), np.asarray(patch[:, 0]))
    np.testing.assert_array_equal(np.asarray(gb[:, 0, 4]), np.asarray(k[:, 4]))
    # a fresh admit still sees the ORIGINAL cached content
    c = pool.admit(10, tokens=toks)
    gc_, _ = pool.gather([c])
    np.testing.assert_array_equal(np.asarray(gc_[:, 0, :8]), np.asarray(k))


def test_pool_prefix_cache_reclaimed_under_pressure():
    """Cache-only pages (refcount held solely by the trie) are reclaimed
    LRU-first when admit/extend would otherwise fail."""
    cfg = _smoke_cfg()
    pool = _prefix_pool()  # 12 usable pages
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    toks = np.arange(8, dtype=np.int32)
    k = jnp.zeros((L, 8, KV, hd), jnp.float32)
    a = pool.admit(8, tokens=toks)
    pool.write_span(a, 0, 8, k, k)
    pool.register_prefix(a, toks)
    pool.release(a)
    assert pool.cached_pages == 2 and pool.pages_in_use == 2
    # demand every page: the cached pages must be reclaimed, not block
    slots = [pool.admit(16) for _ in range(3)]
    assert all(s is not None for s in slots)
    assert pool.cached_pages == 0
    for s in slots:
        pool.release(s)
    assert pool.pages_in_use == 0


def test_pool_int8_write_gather_roundtrip():
    cfg = _smoke_cfg()
    pool = PagedKVPool(
        cfg, n_pages=9, page_size=4, n_slots=3, max_pages_per_seq=2,
        dtype=jnp.int8,
    )
    slot = pool.admit(6)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    k = jax.random.normal(jax.random.PRNGKey(0), (L, 6, KV, hd), jnp.float32)
    pool.write_span(slot, 0, 6, k, -k)
    gk, gv = pool.gather([slot])
    assert gk.dtype == jnp.dtype(cfg.dtype)
    # int8 quantization error is bounded by scale/2 = max|x|/254 per head
    np.testing.assert_allclose(
        np.asarray(gk[:, 0, :6]), np.asarray(k), atol=0.03, rtol=0.02
    )
    np.testing.assert_allclose(
        np.asarray(gv[:, 0, :6]), np.asarray(-k), atol=0.03, rtol=0.02
    )


def test_engine_eviction_under_page_pressure():
    """Overcommitted pool: decode runs out of pages mid-stream, the newest
    sequence is evicted, requeued, and still finishes with exact tokens."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=4).tokens
    gen = 8
    # each seq needs 4 pages of 4; give the pool only 9 usable pages for 3
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        n_slots=3, page_size=4, n_pages=10,
    )
    assert engine.stats["evictions"] > 0
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_eviction_victim_can_be_asking_lane():
    """Regression: under hard pressure the victim must be the NEWEST
    running request — possibly the very lane asking for a page — never an
    older lane already granted pages this step (that used to leave a freed
    slot inside the decode batch -> KeyError)."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompts = make_calibration(cfg.vocab, n_segments=4, seg_len=16, seed=6).tokens
    gen = 16
    # 4 seqs x up to 8 pages of 4, but only 15 usable pages
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        n_slots=4, page_size=4, n_pages=16, record_logits=False,
    )
    assert engine.stats["evictions"] > 0
    assert engine.pool.pages_in_use == 0  # everything released at drain
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_sampling_reproducible_and_greedy_default():
    """Non-greedy decode: same seed -> same stream regardless of batch
    composition; temperature 0 stays the exact greedy argmax path."""
    from repro.serve.scheduler import SamplingParams

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    adapter = CachedDecoder.from_model(model, params)
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=2).tokens
    gen = 6
    sp = SamplingParams(temperature=0.9, top_p=0.85, seed=42)

    def run(batch):
        engine = Engine(adapter, EngineConfig(
            max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
            token_budget=32, prefill_chunk=8,
        ))
        reqs = [
            engine.submit(np.asarray(prompts[i]), max_new=gen, sampling=sp)
            for i in batch
        ]
        engine.run()
        return {i: np.asarray(r.out_tokens) for i, r in zip(batch, reqs)}

    solo = run([0])
    batched = run([0, 1, 2])
    np.testing.assert_array_equal(solo[0], batched[0])
    # greedy (default SamplingParams) matches the reference generator
    from repro.launch.serve import greedy_generate

    engine = Engine(adapter, EngineConfig(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8,
    ))
    reqs = [engine.submit(np.asarray(p), max_new=gen) for p in prompts]
    engine.run()
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_sampling_param_validation():
    from repro.serve.scheduler import SamplingParams

    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


def test_stop_token_finishes_request_early():
    """A request stops at its first stop-token emission (token included);
    the greedy stream up to that point is unchanged."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=2, seg_len=8, seed=2).tokens
    gen = 8
    ref = np.asarray(greedy_generate(model, params, prompts, gen))
    stop = int(ref[0, 2])  # stop request 0 at its 3rd greedy token
    engine = Engine(
        CachedDecoder.from_model(model, params),
        EngineConfig(max_seq_len=prompts.shape[1] + gen, n_slots=4,
                     page_size=4, token_budget=32, prefill_chunk=8),
    )
    r0 = engine.submit(np.asarray(prompts[0]), max_new=gen,
                       stop_tokens=(stop,))
    r1 = engine.submit(np.asarray(prompts[1]), max_new=gen)
    engine.run()
    want = list(ref[0, : list(ref[0]).index(stop) + 1])
    np.testing.assert_array_equal(np.asarray(r0.out_tokens), want)
    assert len(r0.out_tokens) <= 3
    np.testing.assert_array_equal(np.asarray(r1.out_tokens), ref[1])
    assert engine.pool.pages_in_use == 0  # early finish released its pages


def test_engine_rejects_oversized_request():
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(
        CachedDecoder.from_model(model, params),
        EngineConfig(max_seq_len=16, n_slots=2, page_size=4),
    )
    with pytest.raises(ValueError):
        engine.submit(np.arange(10, dtype=np.int32), max_new=8)  # 18 > 16


# ---------------------------------------------------------------------------
# Quantized artifacts: save -> load round-trip
# ---------------------------------------------------------------------------


def test_linear_arrays_roundtrip(small_wh):
    W, H = small_wh
    qcfg = QuipConfig(bits=2, use_kernel=False)
    layer, _ = quantize_layer(W, H, qcfg, seed=11, collect_stats=False)
    arrays, meta = linear_to_arrays(layer)
    arrays = {k: np.asarray(v) for k, v in arrays.items()}  # simulate npz
    rebuilt = linear_from_arrays(arrays, meta)
    np.testing.assert_array_equal(np.asarray(rebuilt.packed), np.asarray(layer.packed))
    # transforms regenerate bit-identically from seeds
    np.testing.assert_array_equal(
        np.asarray(rebuilt.dequantize()), np.asarray(layer.dequantize())
    )
    x = make_weights(5, W.shape[1], seed=9)
    np.testing.assert_allclose(
        np.asarray(rebuilt(x)), np.asarray(layer(x)), rtol=0, atol=1e-6
    )


def test_artifact_save_load_identical_outputs(tmp_path, quantized_smoke):
    cfg, qm, qcfg = quantized_smoke
    save_quantized(tmp_path / "art", qm, qcfg, extra_meta={"stats": qm.stats})
    qm2, meta = load_quantized(tmp_path / "art")
    assert meta["quip_config"]["bits"] == 2
    assert qm2.cfg == cfg
    toks = make_calibration(cfg.vocab, n_segments=2, seg_len=16, seed=2).tokens
    np.testing.assert_allclose(
        np.asarray(qm2.logits(toks)), np.asarray(qm.logits(toks)),
        rtol=0, atol=1e-5,
    )
    # per-linear quant_matmul outputs are identical
    lin, lin2 = qm.blocks[0]["attn.wq"], qm2.blocks[0]["attn.wq"]
    x = make_weights(3, lin.n, seed=13)
    np.testing.assert_allclose(
        np.asarray(lin2(x)), np.asarray(lin(x)), rtol=0, atol=1e-6
    )


def test_artifact_rejects_non_artifact_dir(tmp_path):
    from repro.checkpoint import save_checkpoint

    save_checkpoint(tmp_path / "ckpt", 0, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        load_quantized(tmp_path / "ckpt")


# ---------------------------------------------------------------------------
# Speculative decode: drafter, KV rollback, budget accounting, token parity
# ---------------------------------------------------------------------------


def _spec_prompts(n=3, reps=8):
    """Cyclic prompts: the model falls into repetitive continuations the
    n-gram drafter predicts, so speculative ticks actually accept."""
    return np.tile(np.asarray([7, 91, 33, 150], np.int32), (n, reps))


def _spec_prompts_mixed():
    """Three different cycles: the model follows some and breaks others,
    so a speculative run both accepts drafts and rolls rejected ones back
    (on one repeated cycle every draft can land, and nothing rolls back)."""
    return np.stack([
        np.tile([7, 91, 33, 150], 8), np.tile([3, 3, 9, 9], 8),
        np.tile([12, 40, 12, 41], 8),
    ]).astype(np.int32)


def test_drafter_ngram_proposals():
    from repro.serve.drafter import NgramDrafter, make_drafter

    d = NgramDrafter(4, max_ngram=3)
    # periodic history drafts at full depth (iterative continuation past
    # the history's edge, not truncated at it)
    np.testing.assert_array_equal(
        d.propose(np.tile([5, 9], 6)), [5, 9, 5, 9]
    )
    np.testing.assert_array_equal(d.propose([1, 2, 3, 7, 7, 7]), [7] * 4)
    # no repeated n-gram -> nothing proposed
    assert d.propose(np.arange(10)).size == 0
    # propose(k) caps below the drafter depth
    assert len(d.propose(np.tile([5, 9], 6), 2)) == 2
    # the trailing n-gram itself is never its own match
    assert d.propose(np.asarray([1, 2])).size == 0
    with pytest.raises(ValueError):
        NgramDrafter(0)
    with pytest.raises(ValueError):
        make_drafter("oracle", 4)
    assert make_drafter("ngram", 2).k == 2


def test_pool_truncate_rollback():
    pool = _pool(n_pages=9, page_size=4, n_slots=3, max_pages=4)
    cfg = _smoke_cfg()
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    slot = pool.admit(10)  # 3 pages
    k = jnp.ones((L, 10, KV, hd), jnp.float32)
    pool.write_span(slot, 0, 10, k, k)  # 3 pages claimed via span write?
    assert pool.length(slot) == 10
    pages_before = pool.pages_in_use
    # rollback into the middle of page 2: page 3 is wholly invalid
    assert pool.truncate(slot, 6) == 1
    assert pool.length(slot) == 6
    assert pool.pages_in_use == pages_before - 1
    # rollback that only shrinks length within a kept page frees nothing
    assert pool.truncate(slot, 5) == 0
    assert pool.length(slot) == 5
    # growing via truncate is rejected
    with pytest.raises(ValueError):
        pool.truncate(slot, 7)
    # rollback to zero keeps one page mapped (admit's minimum)
    assert pool.truncate(slot, 0) == 1
    assert pool.length(slot) == 0
    pool.release(slot)
    assert pool.pages_in_use == 0


def test_pool_spec_write_rollback_cow_shared_tail():
    """A speculative write + rollback on a lane whose tail page is
    prefix-cache-shared: the write triggers copy-on-write (the cached
    page is NEVER mutated), and the rollback only unmaps the lane's
    private view — refcounts stay exact and LRU reclaim still works."""
    cfg = _smoke_cfg()
    pool = _prefix_pool()
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    toks = np.arange(8, dtype=np.int32)
    k = jnp.arange(L * 8 * KV * hd, dtype=jnp.float32).reshape(L, 8, KV, hd)
    a = pool.admit(8, tokens=toks)
    pool.write_span(a, 0, 8, k, -k)
    pool.register_prefix(a, toks)
    b = pool.admit(12, tokens=toks)  # partial hit: both pages shared
    assert pool.length(b) == 8 and pool.shared_pages == 2
    # roll b back INTO the shared tail page (a replayed/evicted lane) —
    # truncate itself must not mutate or free the shared page
    dropped = pool.truncate(b, 6)
    assert pool.length(b) == 6
    assert pool.shared_pages == 2  # page 1 still shared (trie + a + b)
    cow0 = pool.cow_copies
    # speculative verify writes [last, d1, d2] at positions 6..8 (the
    # engine claims draft pages first): the write path must COW the
    # shared page 1 before the scatter
    assert pool.extend(b, 9)
    kv_new = jnp.full((L, 3, KV, hd), 99.0)
    pool.write_span(b, 6, 3, kv_new, kv_new)
    assert pool.cow_copies >= cow0 + 1
    # rollback the rejected tail (keep only position 6)
    pool.truncate(b, 7)
    assert pool.length(b) == 7
    # the cached/shared page kept its ORIGINAL content: a fresh hit still
    # maps bit-identical K/V
    ga, _ = pool.gather([a])
    np.testing.assert_array_equal(np.asarray(ga[:, 0, :8]), np.asarray(k))
    c = pool.admit(10, tokens=toks)
    gc_, _ = pool.gather([c])
    np.testing.assert_array_equal(
        np.asarray(gc_[:, 0, :8]), np.asarray(k)
    )
    # release everything; trie-held pages reclaim under pressure as before
    for s in (a, b, c):
        pool.release(s)
    slots = [pool.admit(16) for _ in range(3)]
    assert all(s is not None for s in slots)
    assert pool.cached_pages == 0
    for s in slots:
        pool.release(s)
    assert pool.pages_in_use == 0


def test_scheduler_charges_on_accept_not_propose():
    """Accepted speculative extras debit the NEXT step's prefill budget;
    rejected drafts never touch it (no double charge on the retry tick)."""
    from repro.serve.scheduler import Request, RequestState, TokenBudgetFCFS

    class _FakePool:
        def admit(self, n, tokens=None):
            return None  # nothing admissible: isolate the budget math

        def length(self, slot):
            return 0

    sched = TokenBudgetFCFS(token_budget=8, prefill_chunk=4)
    running = []
    for _ in range(2):
        r = Request(prompt=np.arange(4, dtype=np.int32), max_new=4)
        r.state = RequestState.PREFILL
        r.prefill_pos = 0
        running.append(r)
    # no debt: 8 budget -> two 4-token chunks
    plan = sched.plan(running, _FakePool())
    assert [n for _, n in plan.prefill] == [4, 4]
    # 5 accepted extras last tick -> only 3 budget left for prefill
    sched.charge_accepted(5)
    plan = sched.plan(running, _FakePool())
    assert sum(n for _, n in plan.prefill) == 3
    # the debt was settled, not carried: next plan is back to full budget
    plan = sched.plan(running, _FakePool())
    assert sum(n for _, n in plan.prefill) == 8
    with pytest.raises(ValueError):
        sched.charge_accepted(-1)


def test_engine_speculative_fp_token_and_logits_parity():
    """Greedy speculative decode (device selection) emits exactly the
    one-token dense reference's tokens AND logits, while actually
    accepting drafts and rolling back rejected K/V."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts_mixed()
    gen = 12
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        paged_decode=True, speculative_k=4, device_sample=True,
    )
    s = engine.summary()
    assert s["spec_ticks"] > 0
    assert s["accepted_tokens"] > 0  # cyclic prompts: drafts really land
    assert s["rolled_back_tokens"] > 0  # and some really get rolled back
    assert engine.pool.pages_in_use == 0
    ref = np.asarray(greedy_generate(model, params, jnp.asarray(prompts), gen))
    full = np.concatenate([np.asarray(prompts), ref], axis=1)
    hidden, _ = model.forward(params, {"tokens": jnp.asarray(full)})
    ref_logits = np.asarray(model.logits(params, hidden))
    S = prompts.shape[1]
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])
        np.testing.assert_allclose(
            np.stack(r.step_logits), ref_logits[i, S - 1 : S - 1 + gen],
            rtol=2e-3, atol=2e-3,
        )


def test_engine_speculative_host_sample_path_matches():
    """--host-sample debugging path: the verify dispatch returns logits
    and the host re-selects/accepts — same greedy stream."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts()
    gen = 10
    _, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        paged_decode=True, speculative_k=3, device_sample=False,
    )
    ref = np.asarray(greedy_generate(model, params, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_speculative_quantized_matches_recompute(quantized_smoke):
    from repro.launch.serve import quantized_generate

    cfg, qm, _ = quantized_smoke
    prompts = _spec_prompts()
    gen = 8
    _, reqs = _run_engine(
        CachedDecoder.from_quantized(qm), prompts, gen,
        paged_decode=True, speculative_k=4, device_sample=True,
    )
    ref = np.asarray(quantized_generate(qm, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_speculative_int8_matches_sequential_int8():
    """int8 pages: the verify dispatch round-trips the chunk K/V through
    the page quantizer with the fp diagonal override, so speculative
    decode is token-identical to the sequential int8 paged engine."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts()
    gen = 10
    runs = []
    for k in (0, 4):
        eng, reqs = _run_engine(
            CachedDecoder.from_model(model, params), prompts, gen,
            paged_decode=True, speculative_k=k, device_sample=True,
            kv_int8=True, record_logits=False,
        )
        runs.append([np.asarray(r.out_tokens) for r in reqs])
        if k:
            assert eng.summary()["accepted_tokens"] > 0
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a, b)


def test_engine_speculative_eviction_under_page_pressure():
    """Speculative lanes under page pressure: drafts are opportunistic
    (never evict anyone), eviction/replay still reproduces exact tokens,
    and every page is back on the free list at drain."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts(reps=4)  # 16-token prompts
    gen = 12
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        n_slots=3, page_size=4, n_pages=14, paged_decode=True,
        speculative_k=4, device_sample=True, record_logits=False,
    )
    assert engine.stats["evictions"] > 0
    assert engine.pool.pages_in_use == 0
    ref = np.asarray(greedy_generate(model, params, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])


def test_engine_speculative_prefix_cache_cow_and_parity():
    """Speculative decode + prefix cache: shared prompt pages are mapped,
    speculative writes COW instead of mutating cached pages, and the
    stream still matches the dense reference."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts()
    gen = 10
    engine, reqs = _run_engine(
        CachedDecoder.from_model(model, params), prompts, gen,
        arrival_gap=0.2, paged_decode=True, paged_prefill=True,
        prefix_cache=True, speculative_k=4, device_sample=True,
    )
    s = engine.summary()
    assert s["prefix_hit_tokens"] > 0
    assert s["accepted_tokens"] > 0
    ref = np.asarray(greedy_generate(model, params, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])
    # cached prompt pages survived speculative COW traffic intact: a new
    # identical-prompt engine admission still decodes the same stream
    assert s["cached_pages"] > 0


def test_engine_speculative_stop_token_mid_acceptance():
    """A stop token inside an accepted draft run finishes the request at
    the stop emission; later accepted tokens are discarded and their
    pages released."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts(n=1)
    gen = 12
    ref = np.asarray(greedy_generate(model, params, jnp.asarray(prompts), gen))
    stop = int(ref[0, 5])
    engine = Engine(
        CachedDecoder.from_model(model, params),
        EngineConfig(max_seq_len=prompts.shape[1] + gen, n_slots=2,
                     page_size=4, token_budget=32, prefill_chunk=8,
                     paged_decode=True, speculative_k=4,
                     device_sample=True),
    )
    r = engine.submit(np.asarray(prompts[0]), max_new=gen,
                      stop_tokens=(stop,))
    engine.run()
    want = list(ref[0, : list(ref[0]).index(stop) + 1])
    np.testing.assert_array_equal(np.asarray(r.out_tokens), want)
    assert engine.pool.pages_in_use == 0


def test_engine_speculative_interpret_kernel_end_to_end():
    """The verify dispatch through the actual chunked-prefill Pallas
    kernel (interpret mode) — including the diagonal-override int8 path —
    not just the jnp oracle."""
    from repro.launch.serve import greedy_generate

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _spec_prompts(n=2)
    gen = 4
    _, reqs = _run_engine(
        CachedDecoder.from_model(model, params, paged_interpret=True),
        prompts, gen, n_slots=2, paged_decode=True, speculative_k=2,
        device_sample=True,
    )
    ref = np.asarray(greedy_generate(model, params, jnp.asarray(prompts), gen))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.out_tokens), ref[i])
    # int8: kernel (interpret) must agree with the jnp-oracle engine
    runs = []
    for interpret in (False, True):
        _, reqs = _run_engine(
            CachedDecoder.from_model(model, params,
                                     paged_interpret=interpret),
            prompts, gen, n_slots=2, paged_decode=True, speculative_k=2,
            device_sample=True, kv_int8=True, record_logits=False,
        )
        runs.append([np.asarray(r.out_tokens) for r in reqs])
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a, b)


def test_engine_speculative_requires_paged():
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    adapter = CachedDecoder.from_model(model, params)
    with pytest.raises(ValueError):
        Engine(adapter, EngineConfig(max_seq_len=16, speculative_k=2))
    with pytest.raises(ValueError):
        Engine(adapter, EngineConfig(max_seq_len=16, device_sample=True))
    with pytest.raises(ValueError):
        Engine(adapter, EngineConfig(max_seq_len=16, speculative_k=-1,
                                     paged_decode=True))


# ---------------------------------------------------------------------------
# On-device sampling (fused softmax/top-p draw, fold_in keys)
# ---------------------------------------------------------------------------


def test_device_sampling_greedy_matches_host_greedy():
    """device_sample with temperature 0 is the exact argmax: identical
    tokens to the host-selection paged engine."""
    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=10, seed=3).tokens
    gen = 6
    runs = []
    for dev in (False, True):
        _, reqs = _run_engine(
            CachedDecoder.from_model(model, params), prompts, gen,
            paged_decode=True, device_sample=dev,
        )
        runs.append([np.asarray(r.out_tokens) for r in reqs])
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a, b)


def test_device_sampling_reproducible_across_batching():
    """fold_in(seed, emission_index) keys: the sampled stream of a request
    does not depend on batch composition — and the speculative engine
    draws the exact stream sequential decode draws."""
    from repro.serve.scheduler import SamplingParams

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    adapter = CachedDecoder.from_model(model, params)
    prompts = _spec_prompts()
    gen = 8
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=17)

    def run(batch, spec_k=0):
        engine = Engine(adapter, EngineConfig(
            max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
            token_budget=32, prefill_chunk=8, paged_decode=True,
            device_sample=True, speculative_k=spec_k,
        ))
        reqs = [
            engine.submit(np.asarray(prompts[i]), max_new=gen, sampling=sp)
            for i in batch
        ]
        engine.run()
        return {i: np.asarray(r.out_tokens) for i, r in zip(batch, reqs)}

    solo = run([0])
    batched = run([0, 1, 2])
    np.testing.assert_array_equal(solo[0], batched[0])
    # speculative grouping draws the same stream as sequential decode
    spec = run([0, 1, 2], spec_k=4)
    for i in range(3):
        np.testing.assert_array_equal(batched[i], spec[i])
    # a different seed gives a different stream (the draw is real)
    sp2 = SamplingParams(temperature=0.8, top_p=0.9, seed=18)
    engine = Engine(adapter, EngineConfig(
        max_seq_len=prompts.shape[1] + gen, n_slots=4, page_size=4,
        token_budget=32, prefill_chunk=8, paged_decode=True,
        device_sample=True,
    ))
    r = engine.submit(np.asarray(prompts[0]), max_new=gen, sampling=sp2)
    engine.run()
    assert not np.array_equal(np.asarray(r.out_tokens), solo[0])


def test_device_sample_tokens_top_p_and_temperature():
    """Unit checks on the fused sampler: greedy lanes take the argmax, a
    near-zero top-p collapses to the argmax, and draws land only inside
    the nucleus."""
    from repro.serve.adapter import sample_tokens

    V = 16
    logits = jnp.asarray(
        np.linspace(0, 3, V, dtype=np.float32)[None, None, :]
    )  # monotone: argmax is V-1
    args = lambda t, p: (
        jnp.asarray([t], jnp.float32), jnp.asarray([p], jnp.float32),
        jnp.asarray([3], jnp.int32), jnp.asarray([0], jnp.int32),
    )
    assert int(sample_tokens(logits, *args(0.0, 1.0))[0, 0]) == V - 1
    assert int(
        sample_tokens(logits, *args(0.0, 1.0), greedy_only=True)[0, 0]
    ) == V - 1
    assert int(sample_tokens(logits, *args(0.7, 1e-6))[0, 0]) == V - 1
    # with top_p = 0.5 over a peaked distribution only the top tokens can
    # be drawn; sweep draw indices to exercise many keys
    peaked = jnp.asarray(
        np.asarray([0, 0, 0, 8, 9], np.float32)[None, None, :]
    )
    for idx in range(24):
        tok = int(sample_tokens(
            peaked,
            jnp.asarray([1.0], jnp.float32), jnp.asarray([0.9], jnp.float32),
            jnp.asarray([5], jnp.int32), jnp.asarray([idx], jnp.int32),
        )[0, 0])
        assert tok in (3, 4)


def test_device_sampling_survives_eviction_replay():
    """A device-sampled request evicted mid-stream and replayed emits the
    exact stream of an uncontended run: every draw — including the
    prefill-boundary one — is the same pure function of
    (seed, emission_index)."""
    from repro.serve.scheduler import SamplingParams

    cfg = _smoke_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    adapter = CachedDecoder.from_model(model, params)
    prompts = make_calibration(cfg.vocab, n_segments=3, seg_len=8, seed=4).tokens
    gen = 8
    sps = [SamplingParams(temperature=0.9, top_p=0.9, seed=40 + i)
           for i in range(3)]

    def run(n_pages):
        engine = Engine(adapter, EngineConfig(
            max_seq_len=prompts.shape[1] + gen, n_slots=3, page_size=4,
            n_pages=n_pages, token_budget=32, prefill_chunk=8,
            paged_decode=True, device_sample=True,
        ))
        reqs = [engine.submit(np.asarray(p), max_new=gen, sampling=sp)
                for p, sp in zip(prompts, sps)]
        engine.run()
        return engine, [np.asarray(r.out_tokens) for r in reqs]

    _, calm = run(None)  # uncontended (no overcommit)
    engine, pressured = run(10)  # overcommitted: forces eviction/replay
    assert engine.stats["evictions"] > 0
    for a, b in zip(calm, pressured):
        np.testing.assert_array_equal(a, b)


def _literal_sizes(hlo_text: str) -> list:
    """Element counts of the non-splat ``dense<...>`` constants in a
    lowered program (a splat — one repeated value — carries no data)."""
    import math
    import re

    sizes = []
    for body, dims in re.findall(
        r"dense<(\[|\"0x)[^>]*> : tensor<((?:\d+x)*)", hlo_text
    ):
        sizes.append(math.prod(int(d) for d in dims.split("x") if d))
    return sizes


def test_decode_program_takes_weights_as_arguments(quantized_smoke):
    """No serving program embeds weight arrays as constants: the weights
    are the program's first argument.  Weights compiled in as literals
    would not fit a protobuf (2 GB) or the chip's memory at full width,
    and every adapter would compile its own copy."""
    cfg, qm, _ = quantized_smoke
    ad = CachedDecoder.from_quantized(qm)
    pool = ad.make_pool(n_pages=5, page_size=4, n_slots=2,
                        max_pages_per_seq=2)
    z = lambda *s: np.zeros(s, np.int32)
    B = 2
    text = ad._fwd_paged_s.lower(
        ad.weights, z(B, 1), z(B, 1), z(B, 2), z(B), z(B), z(B),
        np.zeros(B, np.float32), np.ones(B, np.float32), z(B), z(B),
        pool.arrays, True,
    ).as_text()
    assert "quant_matmul" in text or "dot_general" in text
    smallest_weight = cfg.head_dim  # the q/k norm scales
    assert max(_literal_sizes(text), default=0) < smallest_weight
    # the same check catches a closure: a constant-folded weight shows up
    baked = jax.jit(lambda x: x @ qm.blocks[0]["attn.wq"].dequantize().T)
    assert max(_literal_sizes(baked.lower(np.zeros((1, cfg.d_model),
                                                   np.float32)).as_text())
               ) >= smallest_weight
