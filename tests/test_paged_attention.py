"""Paged-attention kernel parity sweeps: interpret-mode Pallas kernels
(decode + chunked prefill) vs the dense gather oracles, across ragged
context lengths, page-boundary-straddling contexts/chunks, GQA group
sizes, and int8 pages — plus the ValueError shape-check contract for the
Pallas kernel entry points (usable errors under ``python -O``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (
    paged_attention_kernel,
    paged_gqa_decode,
    paged_gqa_decode_ref,
    paged_gqa_prefill,
    paged_gqa_prefill_ref,
    paged_prefill_kernel,
)


def _setup(
    *, L=2, P=9, ps=4, KV=2, G=2, hd=16, B=3, Pa=3, int8=False, seed=0,
    dtype=jnp.float32,
):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    H = KV * G
    int8 = int8 or dtype == jnp.int8
    if int8:
        kp = jax.random.randint(ks[0], (L, P, KV, ps, hd), -127, 128, jnp.int8)
        vp = jax.random.randint(ks[1], (L, P, KV, ps, hd), -127, 128, jnp.int8)
        k_sc = jnp.abs(jax.random.normal(ks[4], (L, P, KV, ps))) * 0.02 + 1e-3
        v_sc = jnp.abs(jax.random.normal(ks[5], (L, P, KV, ps))) * 0.02 + 1e-3
    else:
        kp = jax.random.normal(ks[0], (L, P, KV, ps, hd)).astype(dtype)
        vp = jax.random.normal(ks[1], (L, P, KV, ps, hd)).astype(dtype)
        k_sc = v_sc = None
    q = jax.random.normal(ks[2], (B, H, hd), jnp.float32)
    kn = jax.random.normal(ks[3], (B, KV, hd), jnp.float32) * 0.5
    vn = jax.random.normal(ks[3], (B, KV, hd), jnp.float32) * 0.5
    # every lane gets a distinct page permutation (physical != logical)
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(
        np.stack([rng.permutation(np.arange(1, P))[:Pa] for _ in range(B)]),
        jnp.int32,
    )
    return q, kn, vn, kp, vp, bt, k_sc, v_sc


def _both(q, kn, vn, kp, vp, bt, cl, layer, k_sc=None, v_sc=None):
    out_k = paged_gqa_decode(
        q, kn, vn, kp, vp, bt, cl, layer=layer, k_scale=k_sc, v_scale=v_sc,
        interpret=True,
    )
    out_r = paged_gqa_decode_ref(
        q, kn, vn, kp, vp, bt, cl, layer=layer, k_scale=k_sc, v_scale=v_sc,
    )
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5
    )


# (G, KV): the first three keep the ids they had before the KV axis;
# qwen3-14b groups 5 query heads on each of 8 KV heads, and a two-way
# tensor-parallel shard holds 2 of them
GQA = [pytest.param(1, 2, id="1"), pytest.param(2, 2, id="2"),
       pytest.param(4, 2, id="4")] + [
    pytest.param(G, KV, id=f"G{G}-KV{KV}")
    for G in (1, 2, 4, 5) for KV in (1, 2, 8) if KV != 2 or G == 5
]


@pytest.mark.parametrize("G,KV", GQA)
def test_kernel_matches_oracle_gqa_groups(G, KV):
    q, kn, vn, kp, vp, bt, *_ = _setup(G=G, KV=KV, Pa=4, seed=G)
    cl = jnp.array([7, 4, 11], jnp.int32)  # ragged, mid-page
    for layer in range(kp.shape[0]):
        _both(q, kn, vn, kp, vp, bt, cl, layer)


# contexts per block-table width at ps 4: empty, exactly one block of
# pages, one token past a block edge, a full table, one short of it, and
# a lane inside its first page.  Pa 32 runs two 16-page blocks; Pa 20
# (the 320-page cap's shape, scaled) runs five 4-page blocks.
BLOCK_CTX = {32: [0, 64, 65, 128, 127, 3], 20: [0, 16, 17, 80, 79, 3]}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("Pa", [32, 20])
def test_kernel_page_blocks_ragged(Pa, dtype):
    q, kn, vn, kp, vp, bt, k_sc, v_sc = _setup(
        P=Pa + 8, Pa=Pa, B=6, dtype=dtype, seed=Pa
    )
    cl = jnp.asarray(BLOCK_CTX[Pa], jnp.int32)
    _both(q, kn, vn, kp, vp, bt, cl, 1, k_sc, v_sc)


def test_kernel_page_boundary_straddles():
    """ctx_len exactly at page edges, one past, empty, and full."""
    q, kn, vn, kp, vp, bt, *_ = _setup(ps=4, Pa=3, seed=11)
    for cl in ([4, 8, 12], [5, 9, 1], [0, 3, 12], [1, 4, 5]):
        _both(q, kn, vn, kp, vp, bt, jnp.asarray(cl, jnp.int32), 1)


def test_kernel_int8_pages():
    q, kn, vn, kp, vp, bt, k_sc, v_sc = _setup(int8=True, seed=5)
    cl = jnp.array([6, 2, 9], jnp.int32)
    _both(q, kn, vn, kp, vp, bt, cl, 0, k_sc, v_sc)


def _poisoned(kp, vp, k_sc, v_sc, bt, cl, ps):
    """The pool with NaN (int8: NaN scales) at every (page, position) no
    lane attends: pages past each lane's context, and the tail of each
    lane's last page past ``ctx_len``."""
    live = np.zeros((kp.shape[1], ps), bool)
    for row, c in zip(np.asarray(bt), np.asarray(cl)):
        for i in range(-(-int(c) // ps)):
            live[row[i], : min(ps, int(c) - i * ps)] = True
    dead = jnp.asarray(~live)[None, :, None, :]  # (1, P, 1, ps)
    if k_sc is not None:
        return (kp, vp, jnp.where(dead, jnp.nan, k_sc),
                jnp.where(dead, jnp.nan, v_sc))
    return (jnp.where(dead[..., None], jnp.nan, kp).astype(kp.dtype),
            jnp.where(dead[..., None], jnp.nan, vp).astype(vp.dtype),
            None, None)


POISON = [pytest.param(dt, KV, id=f"{name}-KV{KV}")
          for dt, name in ((jnp.bfloat16, "bf16"), (jnp.int8, "int8"))
          for KV in (1, 2, 8)]


@pytest.mark.parametrize("dtype,KV", POISON)
def test_kernel_ignores_unattended_page_contents(dtype, KV):
    """Pages past ctx_len (incl. scratch-page fill in the block table),
    and the positions past ctx_len inside a lane's last page, must not
    leak into the output, whatever they contain — NaN included."""
    ps, Pa, B = 4, 8, 4
    q, kn, vn, kp, vp, _, k_sc, v_sc = _setup(
        P=B * Pa + 1, Pa=Pa, B=B, KV=KV, dtype=dtype, seed=7
    )
    # disjoint pages per lane, so a page one lane leaves dead is no other
    # lane's live page; lane 3 is empty and its entry 0 points at a
    # poisoned page, not the scratch page
    bt = jnp.asarray(1 + np.arange(B * Pa).reshape(B, Pa)[:, ::-1].copy(),
                     jnp.int32)
    cl = jnp.array([3, 17, 32, 0], jnp.int32)  # one block = 32 tokens
    out1 = paged_gqa_decode(q, kn, vn, kp, vp, bt, cl, layer=0,
                            k_scale=k_sc, v_scale=v_sc, interpret=True)
    kp2, vp2, ks2, vs2 = _poisoned(kp, vp, k_sc, v_sc, bt, cl, ps)
    out2 = paged_gqa_decode(q, kn, vn, kp2, vp2, bt, cl, layer=0,
                            k_scale=ks2, v_scale=vs2, interpret=True)
    assert np.isfinite(np.asarray(out2)).all()
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    _both(q, kn, vn, kp, vp, bt, cl, 0, k_sc, v_sc)


@pytest.mark.parametrize("dtype,KV", POISON)
def test_short_lanes_never_dereference_dead_pages(dtype, KV):
    """Block tables are bucketed to the LONGEST live context, so short
    lanes carry dead trailing entries.  A page block at or past a lane's
    context is neither fetched nor computed, and inside a lane's last
    block the pages past its context are skipped — so dead entries,
    entry 0 of an EMPTY lane included, may point at any page, even one
    full of NaN, and the output stays bit-identical."""
    q, kn, vn, kp, vp, bt, k_sc, v_sc = _setup(
        P=9, ps=4, Pa=4, KV=KV, dtype=dtype, seed=13
    )
    cl = jnp.array([5, 0, 16], jnp.int32)  # lane 0 short, lane 1 EMPTY
    out1 = paged_gqa_decode(q, kn, vn, kp, vp, bt, cl, layer=1,
                            k_scale=k_sc, v_scale=v_sc, interpret=True)
    # point every dead entry at page 0, which no lane attends, and fill
    # page 0 with NaN: lane 0 keeps bt[0, :2], lane 1 keeps nothing (its
    # entry 0 too), lane 2 attends all four
    bt2 = np.asarray(bt).copy()
    bt2[0, 2:] = 0
    bt2[1, :] = 0
    if k_sc is None:
        kp, vp = kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan)
    else:
        k_sc, v_sc = k_sc.at[:, 0].set(jnp.nan), v_sc.at[:, 0].set(jnp.nan)
    out2 = paged_gqa_decode(q, kn, vn, kp, vp, jnp.asarray(bt2), cl,
                            layer=1, k_scale=k_sc, v_scale=v_sc,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # and the kernel still matches the oracle on the ragged batch
    _both(q, kn, vn, kp, vp, bt, cl, 1, k_sc, v_sc)


def test_epilogue_self_attention_dominates_empty_context():
    """ctx_len = 0 lanes reduce to pure self-attention: out == v_new."""
    q, kn, vn, kp, vp, bt, *_ = _setup(seed=3)
    cl = jnp.zeros((3,), jnp.int32)
    out = paged_gqa_decode(q, kn, vn, kp, vp, bt, cl, layer=0, interpret=True)
    B, H, hd = out.shape
    KV = vn.shape[1]
    want = jnp.repeat(vn, H // KV, axis=1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-6
    )


# ---------------------------------------------------------------------------
# Chunked-prefill kernel parity (interpret mode) vs the jnp oracle
# ---------------------------------------------------------------------------


def _setup_prefill(
    *, L=2, P=9, ps=4, KV=2, G=2, hd=16, B=3, Pa=3, C=5, int8=False, seed=0
):
    q, kn, vn, kp, vp, bt, k_sc, v_sc = _setup(
        L=L, P=P, ps=ps, KV=KV, G=G, hd=hd, B=B, Pa=Pa, int8=int8, seed=seed
    )
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 3)
    H = KV * G
    qc = jax.random.normal(ks[0], (B, C, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, C, KV, hd), jnp.float32) * 0.5
    vc = jax.random.normal(ks[2], (B, C, KV, hd), jnp.float32) * 0.5
    return qc, kc, vc, kp, vp, bt, k_sc, v_sc


def _both_prefill(q, kc, vc, kp, vp, bt, cl, layer, k_sc=None, v_sc=None):
    out_k = paged_gqa_prefill(
        q, kc, vc, kp, vp, bt, cl, layer=layer, k_scale=k_sc, v_scale=v_sc,
        interpret=True,
    )
    out_r = paged_gqa_prefill_ref(
        q, kc, vc, kp, vp, bt, cl, layer=layer, k_scale=k_sc, v_scale=v_sc,
    )
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("G", [1, 2, 4])
def test_prefill_kernel_matches_oracle_gqa_groups(G):
    q, kc, vc, kp, vp, bt, *_ = _setup_prefill(G=G, seed=G)
    cl = jnp.array([7, 4, 11], jnp.int32)  # ragged, mid-page
    for layer in range(kp.shape[0]):
        _both_prefill(q, kc, vc, kp, vp, bt, cl, layer)


def test_prefill_kernel_ragged_and_empty_contexts():
    """Fresh admissions (ctx 0), page-edge starts, one past the edge, and
    chunks straddling a page boundary mid-batch."""
    q, kc, vc, kp, vp, bt, *_ = _setup_prefill(ps=4, Pa=3, C=6, seed=11)
    for cl in ([0, 0, 0], [4, 8, 12], [5, 9, 1], [0, 3, 11]):
        _both_prefill(q, kc, vc, kp, vp, bt, jnp.asarray(cl, jnp.int32), 1)


def test_prefill_kernel_chunk_wider_than_page():
    """A chunk spanning multiple pages' worth of tokens (C > ps) keeps its
    intra-chunk causal structure."""
    q, kc, vc, kp, vp, bt, *_ = _setup_prefill(ps=4, Pa=4, P=17, C=9, seed=2)
    cl = jnp.array([3, 0, 7], jnp.int32)
    _both_prefill(q, kc, vc, kp, vp, bt, cl, 0)


def test_prefill_kernel_int8_pages():
    q, kc, vc, kp, vp, bt, k_sc, v_sc = _setup_prefill(int8=True, seed=5)
    cl = jnp.array([6, 2, 9], jnp.int32)
    _both_prefill(q, kc, vc, kp, vp, bt, cl, 0, k_sc, v_sc)


def test_prefill_kernel_single_token_chunk_matches_decode():
    """A C=1 chunk is exactly a decode step: the prefill kernel's causal
    block degenerates to the decode epilogue's self-token merge."""
    q, kn, vn, kp, vp, bt, *_ = _setup(seed=9)
    cl = jnp.array([7, 4, 11], jnp.int32)
    dec = paged_gqa_decode(
        q, kn, vn, kp, vp, bt, cl, layer=0, interpret=True
    )
    pre = paged_gqa_prefill(
        q[:, None], kn[:, None], vn[:, None], kp, vp, bt, cl, layer=0,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(pre[:, 0]), np.asarray(dec), rtol=1e-5, atol=1e-5
    )


def test_prefill_kernel_ignores_unattended_page_contents():
    """Context pages past ctx_len must not leak into any chunk row."""
    q, kc, vc, kp, vp, bt, *_ = _setup_prefill(seed=7)
    bt = jnp.broadcast_to(bt[:1], bt.shape)
    cl = jnp.array([3, 4, 2], jnp.int32)  # only the first page matters
    out1 = paged_gqa_prefill(
        q, kc, vc, kp, vp, bt, cl, layer=0, interpret=True
    )
    poisoned = kp.at[:, np.asarray(bt[0, 1:])].set(1e4)
    out2 = paged_gqa_prefill(
        q, kc, vc, poisoned, vp, bt, cl, layer=0, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# ---------------------------------------------------------------------------
# shape-check contract (ValueError with named dims, survives python -O)
# ---------------------------------------------------------------------------


def test_paged_prefill_kernel_shape_errors():
    q, kc, vc, kp, vp, bt, *_ = _setup_prefill(C=4)
    cl = jnp.array([1, 1, 1], jnp.int32)
    B, C, H, hd = q.shape
    KV = kc.shape[2]
    qg = q.reshape(B, C, KV, H // KV, hd).transpose(0, 2, 3, 1, 4)
    with pytest.raises(ValueError, match="grouped chunk queries"):
        paged_prefill_kernel(q, kc, vc, kp, vp, bt, cl, layer=0,
                             interpret=True)
    with pytest.raises(ValueError, match="k_chunk"):
        paged_prefill_kernel(qg, kc[:, :2], vc, kp, vp, bt, cl, layer=0,
                             interpret=True)
    with pytest.raises(ValueError, match="layer"):
        paged_prefill_kernel(qg, kc, vc, kp, vp, bt, cl, layer=99,
                             interpret=True)
    with pytest.raises(ValueError, match="block_tables"):
        paged_prefill_kernel(qg, kc, vc, kp, vp, bt[:2], cl, layer=0,
                             interpret=True)
    with pytest.raises(ValueError, match="int8"):
        qq, kcc, vcc, kq, vq, btq, ksc, vsc = _setup_prefill(int8=True, C=4)
        qqg = qq.reshape(B, C, KV, H // KV, hd).transpose(0, 2, 3, 1, 4)
        paged_prefill_kernel(qqg, kcc, vcc, kq, vq, btq, cl, layer=0,
                             interpret=True)


def test_paged_kernel_shape_errors():
    q, kn, vn, kp, vp, bt, *_ = _setup()
    cl = jnp.array([1, 1, 1], jnp.int32)
    with pytest.raises(ValueError, match="KV"):
        paged_attention_kernel(
            q.reshape(3, 2, 2, 16)[:, :1], kp, vp, bt, cl, layer=0,
            interpret=True,
        )
    with pytest.raises(ValueError, match="layer"):
        paged_attention_kernel(
            q.reshape(3, 2, 2, 16), kp, vp, bt, cl, layer=99, interpret=True
        )
    with pytest.raises(ValueError, match="block_tables"):
        paged_attention_kernel(
            q.reshape(3, 2, 2, 16), kp, vp, bt[:2], cl, layer=0,
            interpret=True,
        )
    with pytest.raises(ValueError, match="ctx_len"):
        paged_attention_kernel(
            q.reshape(3, 2, 2, 16), kp, vp, bt, cl[:2], layer=0,
            interpret=True,
        )
    with pytest.raises(ValueError, match="int8"):
        qq, knn, vnn, kq, vq, btq, ksc, vsc = _setup(int8=True)
        paged_attention_kernel(
            qq.reshape(3, 2, 2, 16), kq, vq, btq, cl, layer=0, interpret=True
        )


def test_quant_matmul_kernel_shape_errors():
    from repro.core import packing
    from repro.kernels.quant_matmul.kernel import quant_matmul_kernel

    packed = packing.pack(jnp.zeros((128, 128), jnp.int32), 2)
    x = jnp.zeros((8, 128), jnp.float32)
    with pytest.raises(ValueError, match="reduction dim"):
        quant_matmul_kernel(
            x, packed[:4], bits=2, bB=8, bM=128, bK=128, interpret=True
        )
    with pytest.raises(ValueError, match="multiples of tiles"):
        quant_matmul_kernel(
            jnp.zeros((10, 128), jnp.float32), packed, bits=2, bB=8, bM=128,
            bK=128, interpret=True,
        )
    with pytest.raises(ValueError, match="vals-per-word"):
        quant_matmul_kernel(
            x, packed, bits=2, bB=8, bM=128, bK=8, interpret=True
        )


def test_other_kernel_entry_shape_errors():
    from repro.kernels.hadamard.kernel import hadamard_kernel, sylvester
    from repro.kernels.kron_mul.kernel import kron_mul_kernel
    from repro.kernels.ldlq.kernel import ldlq_block_kernel

    with pytest.raises(ValueError, match="power of two"):
        sylvester(12)
    with pytest.raises(ValueError, match="a\\*b"):
        hadamard_kernel(
            jnp.zeros((8, 64)), jnp.ones((64,)), jnp.ones((4, 4)),
            jnp.ones((8, 8)), a=4, b=8, bB=8, interpret=True,
        )
    with pytest.raises(ValueError, match="p\\*q"):
        kron_mul_kernel(
            jnp.zeros((8, 64)), jnp.ones((4, 4)), jnp.ones((8, 8)),
            p=4, q=8, bB=8, interpret=True,
        )
    with pytest.raises(ValueError, match="columns"):
        ldlq_block_kernel(
            jnp.zeros((8, 64)), jnp.zeros((8, 64)), jnp.zeros((128, 128)),
            nb=128, bM=8, interpret=True,
        )
