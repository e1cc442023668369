"""The plain reference: the model's forward pass in float32 ``jax.numpy``.

It imports nothing of the program and reads only the arrays the harness
made from the seed (``weights.build_arrays``).  Each linear is
``y = U^T deq(W) V (x / D)`` with ``deq(q) = s (2q/3 - 1)`` for the 2-bit
codes unpacked here, and ``V``, ``U`` the seeded Kronecker transforms
``(A kron B) P``; attention is dense and causal, computed in query blocks
so a long sequence fits; RMSNorm, RoPE (rotate-half), qk-norm where the
model has it, SwiGLU, and the untied head follow the published
architecture.  Matmuls run at ``precision="highest"``.

``precision="int8"`` is the control: the same forward with both
operands of every matmul rounded to int8 under a per-tensor scale, the
lower precision a later change might be tempted to serve in on a chip
whose matrix unit takes int8 (TPU v5e does; it has no float8 path).
``precision="fp8"`` rounds them to float8 e4m3 instead, for comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn
I8_MAX = 127.0
Q_BLOCK = 512  # query rows of one attention block
S_BUCKET = 1024  # sequences pad to a multiple of this (few compiles)


def _round(x, precision):
    """An operand of a matmul as the given precision holds it."""
    x = x.astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    if precision == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / I8_MAX
        return jnp.round(x / scale) * scale
    return x


def _mm(a, b, precision, spec="...k,kn->...n"):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def unpack(packed, n):
    """(n / 16, m) int32 words -> (n, m) float32 codes in [0, 3]: value
    ``j`` of word ``i`` is input ``16 i + j``, in bits ``2j .. 2j+1``."""
    words = jax.lax.bitcast_convert_type(packed, jnp.uint32)
    shifts = (2 * jnp.arange(16, dtype=jnp.uint32))[None, :, None]
    codes = (words[:, None, :] >> shifts) & jnp.uint32(3)
    return codes.reshape(n, -1).astype(jnp.float32)


def kron_apply(t, x, inverse=False):
    """y = (A kron B) P x, or its transpose, along the last axis."""
    n = x.shape[-1]
    q = t["B"].shape[0]
    p = n // q
    if not inverse:
        x = jnp.take(x, t["perm"], axis=-1)
    xm = x.reshape(*x.shape[:-1], p, q)
    hi = jax.lax.Precision.HIGHEST
    if t["A"] is not None:
        eq = "ji,...jq->...iq" if inverse else "ij,...jq->...iq"
        xm = jnp.einsum(eq, t["A"], xm, precision=hi)
    eq = "...pq,qk->...pk" if inverse else "...pq,kq->...pk"
    y = jnp.einsum(eq, xm, t["B"], precision=hi).reshape(x.shape)
    return jnp.take(y, t["inv_perm"], axis=-1) if inverse else y


def linear(w, x, precision):
    n = w["D"].shape[0]
    h = kron_apply(w["V"], x / w["D"])
    deq = w["s"] * (unpack(w["packed"], n) * (2.0 / 3.0) - 1.0)  # (n, m)
    return kron_apply(w["U"], _mm(h, deq, precision), inverse=True)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (B, S, H, hd), pos (S,): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, precision):
    """Causal GQA. q (B, S, H, hd); k, v (B, S, KV, hd) -> (B, S, H*hd).
    Query head h reads KV head h // (H / KV)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    q = q.reshape(B, S, KV, H // KV, hd)
    out = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        s = _mm(qb, k, precision, "bqkgd,bskd->bkgqs") * hd**-0.5
        rows = q0 + jnp.arange(qb.shape[1])
        mask = jnp.arange(S)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        out.append(_mm(p, v, precision, "bkgqs,bskd->bqkgd"))
    return jnp.concatenate(out, axis=1).reshape(B, S, H * hd)


@functools.partial(jax.jit, static_argnames=("mi", "precision"))
def _block(lay, x, pos, mi, precision):
    m = dict(mi)
    B, S, _ = x.shape
    hd, H, KV = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    eps = m["norm_eps"]
    h = rms_norm(x, lay["ln1"], eps)
    q = linear(lay["attn.wq"], h, precision).reshape(B, S, H, hd)
    k = linear(lay["attn.wk"], h, precision).reshape(B, S, KV, hd)
    v = linear(lay["attn.wv"], h, precision).reshape(B, S, KV, hd)
    if m["qk_norm"]:
        q = rms_norm(q, lay["q_norm"], eps)
        k = rms_norm(k, lay["k_norm"], eps)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    x = x + linear(lay["attn.wo"], attention(q, k, v, precision), precision)
    h = rms_norm(x, lay["ln2"], eps)
    up = jax.nn.silu(linear(lay["mlp.wi"], h, precision))
    up = up * linear(lay["mlp.wg"], h, precision)
    return x + linear(lay["mlp.wo"], up, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(final_norm, head, x, cand, eps, precision):
    """Per position: the best logit, the logits of the candidate tokens
    ``cand`` (S, k), and the argmax; rows go through the head
    ``Q_BLOCK`` at a time, so the logits never exist all at once."""
    best, got, arg = [], [], []
    for r0 in range(0, x.shape[1], Q_BLOCK):
        h = rms_norm(x[0, r0:r0 + Q_BLOCK], final_norm, eps)
        lg = _mm(h, head, precision)
        best.append(lg.max(axis=-1))
        got.append(jnp.take_along_axis(lg, cand[r0:r0 + Q_BLOCK], axis=-1))
        arg.append(jnp.argmax(lg, axis=-1))
    return (jnp.concatenate(best), jnp.concatenate(got),
            jnp.concatenate(arg))


@jax.jit
def _embed(tok, ids):
    return jnp.take(tok, ids, axis=0).astype(jnp.float32)


def score(arrays, m, seq, cand, precision="f32"):
    """Teacher-forced forward over the token sequence ``seq``, layer by
    layer.  ``cand`` (len(seq), k) int: candidate next tokens at every
    position.  Returns numpy ``(best (S,), got (S, k), argmax (S,))``.
    The sequence pads on the right to a multiple of ``S_BUCKET``:
    attention is causal, so the padding never reaches a real position."""
    mi = tuple(sorted(m.items()))
    n = len(seq)
    S = -(-n // S_BUCKET) * S_BUCKET
    ids = np.zeros((1, S), np.int32)
    ids[0, :n] = seq
    cp = np.zeros((S, cand.shape[1]), np.int32)
    cp[:n] = cand
    with jax.default_matmul_precision("highest"):
        x = _embed(arrays["tok"], ids)
        pos = jnp.arange(S, dtype=jnp.int32)
        for lay in arrays["layers"]:
            x = _block(lay, x, pos, mi, precision)
        best, got, arg = _head(arrays["final_norm"], arrays["head"], x, cp,
                               m["norm_eps"], precision)
        return (np.asarray(best)[:n], np.asarray(got)[:n],
                np.asarray(arg)[:n])
