"""QuIP Algorithm 3: full per-layer quantization pipeline + inference repr.

``quantize_layer`` = Alg.1 (incoherence pre-processing) → rounding method
(LDLQ et al.) → packing.  The result is a :class:`QuantizedLinear`: packed
2/3/4-bit integers plus O(√n)-sized transform factors regenerable from the
seed.  Inference never materializes the dequantized matrix:

    y = x·D^{-1} →(V)→ quant_matmul(packed) →(U^T)→ y

mirroring the paper's "multiply by W = U^T Ŵ V" factorization (Sec. 4.1).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import incoherence as inc
from repro.core import packing
from repro.core.hessian import damp
from repro.core.methods import round_weights
from repro.core.proxy import proxy_loss

__all__ = [
    "QuipConfig",
    "QuantizedLinear",
    "quantize_layer",
    "linear_to_arrays",
    "linear_from_arrays",
]


@dataclasses.dataclass(frozen=True)
class QuipConfig:
    bits: int = 2
    method: str = "ldlq"  # near | stoch | ldlq | ldlq_stoch | ldlq_rg | greedy
    incoherence: bool = True
    transform: inc.TransformKind = "kronecker"  # | "hadamard" | "none"
    rho: float = 2.4
    alpha: float = 0.01
    rescale: bool = True
    permute: bool = True
    spectrum_range: Optional[bool] = None  # default: == incoherence
    greedy_passes: int = 10
    block: int = 128
    use_kernel: bool = True  # Pallas quant_matmul on the inference path

    @property
    def maxq(self) -> int:
        return 2**self.bits - 1

    def label(self) -> str:
        return f"{self.method}{'+incp' if self.incoherence else ''}@{self.bits}b"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedLinear:
    """Inference-ready quantized linear layer: y = x @ W_eff^T.

    ``packed``: (packed_rows(n), m) int32 along the reduction dim.
    ``state``:  transforms + scales needed to apply/revert Alg. 2.

    A pytree whose arrays are leaves (``bits``/``m``/``n``/``use_kernel``
    static), so serving programs take layers as jit arguments instead of
    compiling their weights in as constants.
    """

    packed: jax.Array
    bits: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    state: inc.PreprocessState
    use_kernel: bool = dataclasses.field(default=True,
                                         metadata=dict(static=True))

    def dequantize(self) -> jax.Array:
        """Materialize W_eff (m, n) — tests/export only."""
        Wq = packing.unpack(self.packed, self.bits, self.n).astype(jnp.float32)
        return inc.incoherence_postprocess(Wq, self.state)

    def __call__(
        self, x: jax.Array, *, use_kernel: Optional[bool] = None,
        matmul=None,
    ) -> jax.Array:
        """y = x @ W_eff^T with x (..., n) — structured inference path.

        ``use_kernel`` overrides the layer default for this call: the
        serving engine's paged decode passes ``True`` so every projection
        dispatches through the Pallas ``quant_matmul`` path (jnp oracle
        off-TPU) regardless of how the layer was built.  ``matmul``, when
        given, replaces the packed matmul ``h -> h @ deq(Wq)^T`` (the
        tensor-parallel adapter runs it per shard).

        The transforms run under the named scopes ``incoherence/rescale``,
        ``incoherence/v`` and ``incoherence/u_t``, so a profiler trace
        can tell their device time from the matmul's.
        """
        st = self.state
        with jax.named_scope("incoherence"):
            with jax.named_scope("rescale"):
                h = x if st.D is None else x / st.D
            with jax.named_scope("v"):
                h = inc.apply_transform(st.V, h)
        z = self._matmul(h, use_kernel=use_kernel) if matmul is None \
            else matmul(h)
        with jax.named_scope("incoherence"), jax.named_scope("u_t"):
            return inc.apply_transform(st.U, z, inverse=True)

    def _matmul(
        self, h: jax.Array, use_kernel: Optional[bool] = None
    ) -> jax.Array:
        """z = h @ deq(Wq)^T, deq(q) = (2s/maxq)·q − s."""
        uk = self.use_kernel if use_kernel is None else use_kernel
        if uk:
            from repro.kernels.quant_matmul import ops as qmm

            return qmm.quant_matmul(
                h, self.packed, self.bits, self.n, self.state.s, self.state.maxq
            )
        Wq = packing.unpack(self.packed, self.bits, self.n)
        Wd = inc.from_grid(Wq.astype(h.dtype), self.state.s.astype(h.dtype), self.state.maxq)
        return h @ Wd.T


# ---------------------------------------------------------------------------
# Serialization hooks (repro.serve.artifacts)
#
# A QuantizedLinear persists as packed ints + the data-dependent scale
# factors; the orthogonal transforms are NOT stored — they regenerate
# bit-identically from (kind, n, seed), which is what makes shipping
# quantized checkpoints nearly free (Sec. 4.1).
# ---------------------------------------------------------------------------


def _transform_meta(t: inc.OrthogonalTransform) -> dict:
    return {
        "kind": t.kind,
        "n": t.n,
        "seed": t.seed,
        "permute": t.perm is not None,
    }


def linear_to_arrays(layer: QuantizedLinear) -> tuple[dict, dict]:
    """Split a layer into (arrays-to-store, json-able metadata)."""
    arrays = {"packed": layer.packed, "s": layer.state.s}
    if layer.state.D is not None:
        arrays["D"] = layer.state.D
    meta = {
        "bits": layer.bits,
        "m": layer.m,
        "n": layer.n,
        "maxq": layer.state.maxq,
        "use_kernel": layer.use_kernel,
        "U": _transform_meta(layer.state.U),
        "V": _transform_meta(layer.state.V),
    }
    return arrays, meta


def linear_from_arrays(arrays: dict, meta: dict) -> QuantizedLinear:
    """Rebuild a QuantizedLinear; transforms regenerate from their seeds."""
    m, n, bits = meta["m"], meta["n"], meta["bits"]
    packed = jnp.asarray(arrays["packed"], jnp.int32)
    if packed.shape != packing.packed_shape(m, n, bits):
        raise ValueError(
            f"packed weight shape {packed.shape} != expected "
            f"{packing.packed_shape(m, n, bits)} for ({m}, {n}) @ {bits}b"
        )
    mk = lambda d: inc.make_transform(
        d["kind"], d["n"], d["seed"], permute=d["permute"]
    )
    state = inc.PreprocessState(
        U=mk(meta["U"]),
        V=mk(meta["V"]),
        D=None if "D" not in arrays else jnp.asarray(arrays["D"], jnp.float32),
        s=jnp.asarray(arrays["s"], jnp.float32),
        maxq=meta["maxq"],
    )
    return QuantizedLinear(
        packed=packed, bits=bits, m=m, n=n, state=state,
        use_kernel=meta.get("use_kernel", False),
    )


def quantize_layer(
    W: jax.Array,
    H: jax.Array,
    cfg: QuipConfig,
    *,
    seed: int = 0,
    key: Optional[jax.Array] = None,
    collect_stats: bool = True,
) -> tuple[QuantizedLinear, dict]:
    """Algorithm 3 on one layer.  W: (m, n), H: (n, n) SPD proxy Hessian.

    With ``collect_stats`` the returned dict is a per-layer *quality
    report* (DESIGN.md §13): the incoherence µ(W)/µ(H) before and after
    preprocessing, the raw Hessian's spectrum extremes and condition
    number, the absolute and H-relative proxy loss, weight-error norms,
    and the wall-clock spent in this call.  These are the numbers QuIP's
    guarantees are stated in — recording them is what makes a bad
    Hessian or a silently-skipped transform visible at quantize time
    instead of at perplexity time.  The µ(H) measurements eigendecompose
    H twice; at smoke scale that is free, at cluster scale pass
    ``collect_stats=False`` on the hot path and audit a layer sample.
    """
    t0 = time.perf_counter()
    m, n = W.shape
    W = W.astype(jnp.float32)
    H = H.astype(jnp.float32)
    spectrum = (
        cfg.spectrum_range if cfg.spectrum_range is not None else cfg.incoherence
    )
    if cfg.incoherence:
        Wg, Ht, state = inc.incoherence_preprocess(
            W,
            H,
            bits=cfg.bits,
            seed=seed,
            rho=cfg.rho,
            alpha=cfg.alpha,
            kind=cfg.transform,
            rescale=cfg.rescale,
            permute=cfg.permute,
            spectrum_range=spectrum,
        )
    else:
        # Baseline processing: damping only, identity transforms.
        Ht = damp(H, cfg.alpha)
        s = (
            inc.quant_range(W, cfg.rho)
            if spectrum
            else jnp.max(jnp.abs(W))
        )
        state = inc.PreprocessState(
            U=inc.make_transform("none", m, 0),
            V=inc.make_transform("none", n, 0),
            D=None,
            s=s,
            maxq=cfg.maxq,
        )
        Wg = inc.to_grid(W, s, cfg.maxq)

    kw = {}
    if cfg.method in ("ldlq", "ldlq_stoch"):
        kw["block"] = cfg.block
    if cfg.method in ("ldlq_rg", "greedy"):
        kw["greedy_passes"] = cfg.greedy_passes
    if key is None:
        key = jax.random.PRNGKey(seed ^ 0x5EED)
    Wq = round_weights(cfg.method, Wg, Ht, cfg.maxq, key, **kw)

    packed = packing.pack(Wq.astype(jnp.int32), cfg.bits)
    layer = QuantizedLinear(
        packed=packed, bits=cfg.bits, m=m, n=n, state=state,
        use_kernel=cfg.use_kernel,
    )
    stats: dict = {}
    if collect_stats:
        What = layer.dequantize()
        err = What - W
        # post-incoherence W on its native scale: invert only the grid
        # map (to_grid is affine), leaving the U·W·Vᵀ conjugation in
        # place — µ of exactly what the rounding method saw
        W_post = inc.from_grid(Wg, state.s, state.maxq)
        evals_pre, Q_pre = jnp.linalg.eigh(H)
        _, Q_post = jnp.linalg.eigh(Ht)
        lmin = float(jnp.min(evals_pre))
        lmax = float(jnp.max(evals_pre))
        ploss = float(proxy_loss(What, W, H))
        # H-relative proxy loss: normalize by tr(W H Wᵀ), the proxy value
        # of quantizing everything to zero — scale-free across layers
        whw = float(jnp.einsum("ij,jk,ik->", W, H, W))
        stats = {
            "proxy_loss": ploss,
            "proxy_rel": ploss / whw if whw > 0 else 0.0,
            "frob_rel_err": float(
                jnp.linalg.norm(err) / jnp.linalg.norm(W)
            ),
            "max_abs_err": float(jnp.max(jnp.abs(err))),
            "s": float(state.s),
            "mu_w_pre": float(inc.mu_weight(W)),
            "mu_w_post": float(inc.mu_weight(W_post)),
            "mu_h_pre": float(
                jnp.max(jnp.abs(Q_pre)) * jnp.sqrt(float(n))
            ),
            "mu_h_post": float(
                jnp.max(jnp.abs(Q_post)) * jnp.sqrt(float(n))
            ),
            "h_lambda_min": lmin,
            "h_lambda_max": lmax,
            "h_cond": lmax / max(lmin, 1e-30),
            "m": m,
            "n": n,
            "bits": cfg.bits,
            "method": cfg.label(),
            "wall_s": time.perf_counter() - t0,
        }
    return layer, stats
