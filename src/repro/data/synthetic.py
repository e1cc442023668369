"""Deterministic synthetic data: a learnable LM stream + calibration sets.

No C4 on this offline box (DESIGN.md §8); the pipeline is source-agnostic:
``token_batches`` is the contract every driver consumes ((step -> batch)
pure function of (seed, step), which is ALSO the straggler/fault-tolerance
mechanism — any host can recompute any shard of any step without
coordination).

The LM stream is a k-th order Markov chain over the vocab with a few
hundred "motif" templates, giving a real gap between an untrained and a
trained model (used by benchmarks/quality_grid to reproduce the paper's
perplexity orderings).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["SyntheticLM", "token_batches", "CalibrationSet", "make_calibration"]


@dataclasses.dataclass
class SyntheticLM:
    """Order-1 Markov token source with motif insertions (deterministic)."""

    vocab: int
    seed: int = 0
    n_motifs: int = 64
    motif_len: int = 8

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        v = self.vocab
        # sparse-ish transition structure: each token has 32 likely successors
        self.n_succ = min(32, v)
        self.succ = rng.integers(0, v, size=(v, self.n_succ), dtype=np.int32)
        self.succ_p = rng.dirichlet(np.ones(self.n_succ) * 0.5, size=v).astype(
            np.float32
        )
        self.motifs = rng.integers(
            0, v, size=(self.n_motifs, self.motif_len), dtype=np.int32
        )

    def sample(self, rng: np.random.Generator, batch: int, seq: int) -> np.ndarray:
        out = np.empty((batch, seq), dtype=np.int32)
        tok = rng.integers(0, self.vocab, size=batch).astype(np.int32)
        for t in range(seq):
            u = rng.random(batch)
            cdf = np.cumsum(self.succ_p[tok], axis=-1)
            idx = (u[:, None] > cdf).sum(-1).clip(0, self.n_succ - 1)
            tok = self.succ[tok, idx]
            out[:, t] = tok
        # splice motifs (they give n-gram structure worth >0 bits)
        n_splice = max(1, seq // (4 * self.motif_len))
        for b in range(batch):
            for _ in range(n_splice):
                m = rng.integers(0, self.n_motifs)
                p = rng.integers(0, max(1, seq - self.motif_len))
                out[b, p : p + self.motif_len] = self.motifs[m]
        return out


def token_batches(
    vocab: int,
    global_batch: int,
    seq_len: int,
    *,
    seed: int = 0,
    start_step: int = 0,
) -> Iterator[dict]:
    """Deterministic (seed, step) -> batch stream.

    Restart/recompute contract: batch(step) depends only on (seed, step),
    so resume-from-checkpoint replays the exact stream and any host can
    regenerate any shard (straggler hot-spare semantics, DESIGN.md §4).
    """
    src = SyntheticLM(vocab, seed)
    step = start_step
    while True:
        rng = np.random.default_rng((seed << 20) ^ step)
        toks = src.sample(rng, global_batch, seq_len + 1)
        yield {
            "tokens": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:]),
        }
        step += 1


@dataclasses.dataclass
class CalibrationSet:
    """Paper Sec. 6: 128 random segments of 2048 tokens (scaled-down knobs)."""

    tokens: np.ndarray  # (n_seg, seg_len) int32, host memory

    @property
    def n_segments(self) -> int:
        return self.tokens.shape[0]


def make_calibration(
    vocab: int,
    *,
    n_segments: int = 128,
    seg_len: int = 2048,
    seed: int = 1234,
    source_seed: int = 0,
) -> CalibrationSet:
    """Calibration/eval segments.

    ``source_seed`` picks the LANGUAGE (the Markov source — must match the
    training stream's seed for held-out evaluation, exactly as the paper's
    calibration and eval text come from the same corpus); ``seed`` picks
    the SAMPLES (held-out randomness).
    """
    src = SyntheticLM(vocab, source_seed)
    rng = np.random.default_rng(seed)
    toks = src.sample(rng, n_segments, seg_len)
    # numpy, not a device array: a process that only builds prompts (a
    # fleet parent) must not start a backend and take the chip
    return CalibrationSet(tokens=np.asarray(toks, np.int32))
