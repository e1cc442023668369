"""Seeded 2-bit weights, made on the device in one jitted call.

The layout is the one ``repro.serve.artifacts.save_random_quantized``
writes and ``load_quantized`` reads back: per layer the norm scales and,
per linear, the packed 2-bit codes ``(n / 16, m)`` int32, the scalar
range ``s``, the diagonal rescale ``D`` and two seeded Kronecker
transforms (U on the output side, V on the input side); the embedding,
the untied head and the final norm in the served dtype.  Nothing is
written to disk or read from it.

``build_arrays`` returns plain arrays (what the reference reads);
``to_program`` wraps them in the program's pytree classes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
           "mlp.wi", "mlp.wg", "mlp.wo")
BITS = 2
MAXQ = 2**BITS - 1
VALS = 32 // BITS


def kron_factors(n: int) -> tuple[int, int]:
    """n = p * q, p the largest divisor of n not above sqrt(n)."""
    p = max(d for d in range(1, math.isqrt(n) + 1) if n % d == 0)
    return p, n // p


def linear_shapes(cfg: dict) -> dict:
    """(out m, in n) of each linear of one block."""
    d, q, kv, ff = cfg["d_model"], cfg["q_dim"], cfg["kv_dim"], cfg["d_ff"]
    return {"attn.wq": (q, d), "attn.wk": (kv, d), "attn.wv": (kv, d),
            "attn.wo": (d, q), "mlp.wi": (ff, d), "mlp.wg": (ff, d),
            "mlp.wo": (d, ff)}


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, 64-bit ones included."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


# multipliers of the affine permutations i -> (a i + b) mod n: primes
# above every prime factor of the widths served here, so each is a unit
PRIMES = (19, 23, 29, 31, 37, 41, 43, 47, 53, 59, 61, 67, 71, 73, 79, 83)


def _orthogonal(key, n: int, batch: int) -> jax.Array:
    """``batch`` random orthogonal n x n matrices: the orthonormal DCT-II
    matrix between two random sign flips.  Elementwise arithmetic only,
    so the weight program compiles in seconds (a QR of every factor took
    minutes)."""
    k = jnp.arange(n, dtype=jnp.float32)[:, None]
    i = jnp.arange(n, dtype=jnp.float32)[None, :]
    dct = jnp.cos(jnp.pi * (i + 0.5) * k / n) * jnp.sqrt(2.0 / n)
    dct = dct.at[0].multiply(0.5**0.5)
    signs = 2.0 * (_bits(key, (2, batch, n)) & 1).astype(jnp.float32) - 1.0
    left, right = signs[0], signs[1]
    return left[:, :, None] * dct[None] * right[:, None, :]


def _transforms(key, n: int, batch: int) -> dict:
    """``batch`` seeded Kronecker transforms on R^n, stacked: factors A
    (p x p, absent when p is 1) and B (q x q), and a permutation, the
    affine map ``i -> (a i + b) mod n`` with ``a`` a prime not dividing
    ``n`` (its inverse is exact modular arithmetic)."""
    p, q = kron_factors(n)
    ka, kb, kp, kq = jax.random.split(key, 4)
    units = [a for a in PRIMES if n % a]
    a_tab = jnp.asarray(units, jnp.int32)
    inv_tab = jnp.asarray([pow(a, -1, n) for a in units], jnp.int32)
    pick = jax.random.randint(kp, (batch, 1), 0, len(units))
    b = jax.random.randint(kq, (batch, 1), 0, n)
    i = jnp.arange(n, dtype=jnp.int32)[None, :]
    perm = (a_tab[pick] * i + b) % n
    inv = (inv_tab[pick] * (i - b + n)) % n
    return {"A": _orthogonal(ka, p, batch) if p > 1 else None,
            "B": _orthogonal(kb, q, batch), "perm": perm, "inv_perm": inv}


def _uniform(key, shape, std):
    """Zero-mean uniform values of standard deviation ``std``, from the
    fast RBG bit generator (a normal draw of the same size compiles
    for seconds)."""
    rk = jax.random.wrap_key_data(jax.random.bits(key, (4,), jnp.uint32),
                                  impl="rbg")
    u = jax.random.uniform(rk, shape, jnp.float32, -1.0, 1.0)
    return u * (std * 3.0**0.5)


def _bits(key, shape):
    rk = jax.random.wrap_key_data(jax.random.bits(key, (4,), jnp.uint32),
                                  impl="rbg")
    return jax.random.bits(rk, shape, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _build(key, cfg_items):
    cfg = dict(cfg_items)
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab"]
    dt = jnp.dtype(cfg["dtype"])
    k_tok, k_head, k_lin, k_tr = jax.random.split(key, 4)
    out = {
        "tok": _uniform(k_tok, (V, d), 0.02).astype(dt),
        "head": _uniform(k_head, (d, V), d**-0.5).astype(dt),
        "final_norm": jnp.ones((d,), dt),
    }
    layers = [{"ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt)}
              for _ in range(L)]
    if cfg["qk_norm"]:
        for lay in layers:
            lay["q_norm"] = jnp.ones((cfg["head_dim"],), dt)
            lay["k_norm"] = jnp.ones((cfg["head_dim"],), dt)
    shapes = linear_shapes(cfg)
    # every transform of one width is made in one batch: (linear, side)
    # pairs in order, L layers each
    sides = {}
    for name, (m, n) in shapes.items():
        sides.setdefault(m, []).append((name, "U"))
        sides.setdefault(n, []).append((name, "V"))
    made = {}
    for dim, uses in sides.items():
        t = _transforms(jax.random.fold_in(k_tr, dim), dim, L * len(uses))
        for u, use in enumerate(uses):
            made[use] = {k: None if v is None else v[u * L:(u + 1) * L]
                         for k, v in t.items()}
    for j, (name, (m, n)) in enumerate(shapes.items()):
        kw, kd = jax.random.split(jax.random.fold_in(k_lin, j))
        packed = jax.lax.bitcast_convert_type(
            _bits(kw, (L, n // VALS, m)), jnp.int32)
        D = jnp.exp(_uniform(kd, (L, n), 0.1))
        # uniform codes on s*(2q/maxq - 1) have mean square
        # s^2 (maxq+2) / (3 maxq); this s keeps each linear's RMS
        s = jnp.float32((n * (MAXQ + 2) / (3 * MAXQ)) ** -0.5)
        for i, lay in enumerate(layers):
            pick = lambda t: {k: None if v is None else v[i]
                              for k, v in t.items()}
            lay[name] = {"packed": packed[i], "s": s, "D": D[i],
                         "U": pick(made[(name, "U")]),
                         "V": pick(made[(name, "V")])}
    out["layers"] = layers
    return out


def build_arrays(cfg: dict, seed: int) -> dict:
    """Every weight of the model, layer by layer, from ``seed``."""
    for name, (m, n) in linear_shapes(cfg).items():
        if n % VALS:
            raise ValueError(f"{name}: n={n} is not a multiple of {VALS}")
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return _build(root_key(seed), items)


def abstract_arrays(cfg: dict) -> dict:
    """Shapes and dtypes of :func:`build_arrays` without making them."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return jax.eval_shape(lambda k: _build(k, items), root_key(0))


def to_program(arrays: dict, arch) -> dict:
    """The program's weights pytree (``CachedDecoder.weights``) over the
    same device buffers: per layer, norm dicts and ``QuantizedLinear``
    layers.  Transform seeds are metadata the program never reads; each
    layer and linear gets a fixed one so every seed builds the same
    pytree structure."""
    from repro.core.incoherence import OrthogonalTransform, PreprocessState
    from repro.core.quantizer import QuantizedLinear

    def transform(t, n, tseed):
        return OrthogonalTransform("kronecker", n, tseed, t["A"], t["B"],
                                   None, t["perm"], t["inv_perm"])

    blocks = []
    for i, lay in enumerate(arrays["layers"]):
        blk = {"ln1": {"scale": lay["ln1"]}, "ln2": {"scale": lay["ln2"]}}
        if "q_norm" in lay:
            blk["q_norm"], blk["k_norm"] = lay["q_norm"], lay["k_norm"]
        for j, name in enumerate(LINEARS):
            w = lay[name]
            n, m = w["D"].shape[0], w["packed"].shape[1]
            tseed = i * 10 + j
            state = PreprocessState(
                U=transform(w["U"], m, 2 * tseed + 1),
                V=transform(w["V"], n, 2 * tseed + 2),
                D=w["D"], s=w["s"], maxq=MAXQ)
            blk[name] = QuantizedLinear(packed=w["packed"], bits=BITS,
                                        m=m, n=n, state=state,
                                        use_kernel=True)
        blocks.append(blk)
    return {"embed": {"tok": arrays["tok"], "head": arrays["head"]},
            "final_norm": {"scale": arrays["final_norm"]},
            "blocks": blocks}
