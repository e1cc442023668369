"""Persistent quantized artifacts: quantize once, serve forever.

``save_quantized`` persists a ``QuantizedModel`` through the atomic
checkpoint store (checkpoint/store.py): packed int32 weights, per-layer
scales and diagonal rescales, and fp embed/norm params go into npz shards;
everything regenerable — the incoherence transforms — is stored only as
(kind, n, seed) metadata in the manifest, alongside the full ``ArchConfig``
and ``QuipConfig``.  ``load_quantized`` rebuilds the model without touching
the QuIP pipeline, so ``launch/serve.py --load-quantized <dir>`` starts
serving packed 2-bit weights with no calibration pass.

Layout::

    <dir>/step_00000000/shard_*.npz + manifest.json

with array keys ``embed/tok``, ``final_norm/scale``,
``blocks/<i>/<ln1|ln2|q_norm|k_norm>/...`` and
``blocks/<i>/<linear>/{packed,s,D}``.
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import ArtifactCorruption, load_arrays, save_checkpoint
from repro.configs.base import ArchConfig
from repro.core import packing
from repro.core.quantizer import (
    QuantizedLinear,
    QuipConfig,
    linear_from_arrays,
    linear_to_arrays,
)

__all__ = [
    "save_quantized",
    "save_random_quantized",
    "load_quantized",
    "ArtifactCorruption",
    "ARTIFACT_FORMAT",
]

ARTIFACT_FORMAT = 1
_NORM_KEYS = ("ln1", "ln2", "q_norm", "k_norm")


def save_quantized(
    directory, qm, qcfg: QuipConfig, *, extra_meta: Optional[dict] = None
) -> pathlib.Path:
    """Persist a ``launch.quantize.QuantizedModel`` (+ its QuipConfig)."""
    blocks = []
    linear_meta: dict[str, dict] = {}
    for i, blk in enumerate(qm.blocks):
        bt: dict = {}
        for name, val in blk.items():
            if isinstance(val, QuantizedLinear):
                arrays, meta = linear_to_arrays(val)
                bt[name] = arrays
                linear_meta[f"{i}/{name}"] = meta
            else:
                bt[name] = val
        blocks.append(bt)
    return _write(directory, qm.cfg, qcfg, qm.embed, qm.final_norm, blocks,
                  linear_meta, extra_meta)


def _write(directory, cfg: ArchConfig, qcfg: QuipConfig, embed, final_norm,
           blocks: list, linear_meta: dict, extra_meta: Optional[dict]):
    tree = {"embed": embed, "final_norm": final_norm, "blocks": blocks}
    meta = {
        "kind": "quip_quantized_model",
        "format": ARTIFACT_FORMAT,
        "arch_config": dataclasses.asdict(cfg),
        "quip_config": dataclasses.asdict(qcfg),
        "n_blocks": len(blocks),
        "linears": linear_meta,
        **(extra_meta or {}),
    }
    return save_checkpoint(directory, 0, tree, extra_meta=meta)


def save_random_quantized(directory, cfg: ArchConfig, *, bits: int = 2,
                          seed: int = 0) -> pathlib.Path:
    """Write a seeded random quantized artifact for ``cfg`` in exactly the
    format :func:`save_quantized` writes — random packed codes, per-linear
    scale ``s`` and diagonal rescale ``D``, transform seeds, and fp
    embedding/head/norms in ``cfg.dtype`` — so a published-width model can
    be served without downloading weights or quantizing billions of
    parameters.  ``s`` is chosen so each linear keeps the RMS of its input
    (uniform codes on the grid ``s·(2q/maxq − 1)`` have mean square
    ``s²(maxq+2)/(3·maxq)``; the transforms are orthogonal), which keeps
    the residual stream finite through every layer."""
    from repro.launch.quantize import _DENSE_LINEARS, _block_linears

    qcfg = QuipConfig(bits=bits)
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(cfg.dtype)

    def normal(shape, std):
        return (rng.standard_normal(shape, np.float32) * std).astype(dt)

    embed = {"tok": normal((cfg.vocab, cfg.d_model), 0.02)}
    if not cfg.tie_embeddings:
        embed["head"] = normal((cfg.d_model, cfg.vocab), cfg.d_model**-0.5)
    ones = lambda n: np.ones((n,), dt)
    shapes = {  # (out m, in n) per linear
        "attn.wq": (cfg.q_dim, cfg.d_model),
        "attn.wk": (cfg.kv_dim, cfg.d_model),
        "attn.wv": (cfg.kv_dim, cfg.d_model),
        "attn.wo": (cfg.d_model, cfg.q_dim),
        "mlp.wi": (cfg.d_ff, cfg.d_model),
        "mlp.wg": (cfg.d_ff, cfg.d_model),
        "mlp.wo": (cfg.d_model, cfg.d_ff),
    }
    blocks, linear_meta = [], {}
    for i in range(cfg.n_layers):
        blk: dict = {"ln1": {"scale": ones(cfg.d_model)},
                     "ln2": {"scale": ones(cfg.d_model)}}
        if cfg.qk_norm:
            blk["q_norm"] = blk["k_norm"] = ones(cfg.head_dim)
        for name in _block_linears(cfg):
            m, n = shapes[name]
            words = packing.packed_shape(m, n, bits)
            # the seed formula of launch.quantize.quantize_dense_model
            lseed = seed * 1000 + i * 10 + _DENSE_LINEARS.index(name)
            blk[name] = {
                "packed": rng.integers(0, 2**32, words, np.uint32).view(
                    np.int32),
                "s": np.float32(
                    (n * (qcfg.maxq + 2) / (3 * qcfg.maxq)) ** -0.5),
                "D": np.exp(0.1 * rng.standard_normal(n, np.float32)),
            }
            tmeta = lambda dim, ts: {"kind": qcfg.transform, "n": dim,
                                     "seed": ts, "permute": qcfg.permute}
            linear_meta[f"{i}/{name}"] = {
                "bits": bits, "m": m, "n": n, "maxq": qcfg.maxq,
                "use_kernel": True,
                "U": tmeta(m, lseed * 2 + 1), "V": tmeta(n, lseed * 2 + 2),
            }
        blocks.append(blk)
    return _write(directory, cfg, qcfg, embed,
                  {"scale": ones(cfg.d_model)}, blocks, linear_meta,
                  {"synthetic": True, "seed": seed})


def load_quantized(directory, *, placer=None, verify=True, faults=None):
    """-> (QuantizedModel, meta).  No re-quantization: packed weights load
    directly and transforms regenerate from their stored seeds.

    ``placer``: optional ``f(key, np_array) -> array`` applied per leaf on
    the way out of the store — ``serve.distributed.artifact_placer`` uses
    it to commit packed codes straight to their mesh sharding.

    ``verify``: check shard SHA-256 digests against the manifest; a
    mismatch raises :class:`ArtifactCorruption` naming the shard
    (manifests written before digests existed load with a warning).
    ``faults``: optional :class:`~repro.serve.faults.FaultPlan` whose
    armed ``corrupt_shard`` rules force digest mismatches — the
    integrity path is testable without rotting bytes on disk."""
    from repro.launch.quantize import QuantizedModel  # deferred: avoid cycle

    corrupt = faults.corrupt_shards() if faults is not None else ()
    arrays, _step, meta = load_arrays(
        directory, placer=placer, verify=verify, _corrupt_shards=corrupt)
    if meta.get("kind") != "quip_quantized_model":
        raise ValueError(
            f"{directory} is not a quantized artifact "
            f"(manifest kind={meta.get('kind')!r})"
        )
    cfg_dict = dict(meta["arch_config"])
    cfg_dict["shape_skips"] = tuple(cfg_dict.get("shape_skips", ()))  # json list
    cfg = ArchConfig(**cfg_dict)

    def subtree(prefix: str) -> dict:
        out: dict = {}
        plen = len(prefix)
        for key, arr in arrays.items():
            if key.startswith(prefix):
                out[key[plen:]] = jnp.asarray(arr)
        return out

    blocks = []
    for i in range(meta["n_blocks"]):
        blk: dict = {}
        for norm in _NORM_KEYS:
            sub = subtree(f"blocks/{i}/{norm}/")
            if sub:
                blk[norm] = sub
            elif f"blocks/{i}/{norm}" in arrays:  # bare array (q/k_norm)
                blk[norm] = jnp.asarray(arrays[f"blocks/{i}/{norm}"])
        for lkey, lmeta in meta["linears"].items():
            idx, name = lkey.split("/", 1)
            if int(idx) != i:
                continue
            blk[name] = linear_from_arrays(
                subtree(f"blocks/{i}/{name}/"), lmeta
            )
        blocks.append(blk)
    qm = QuantizedModel(
        cfg=cfg,
        embed=subtree("embed/"),
        final_norm=subtree("final_norm/"),
        blocks=blocks,
        stats=meta.get("stats", []),
    )
    return qm, meta
