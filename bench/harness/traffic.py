"""The one traffic generator: a mix's data file in, a request list out.

A mix file (``bench/traffic/<name>.json``) gives the prompt and output
length distributions, the arrival kind and the sampling settings.  The
cell file gives what depends on the model: the arrival rate of an
open-loop mix, the backlog of a closed one.

Every seed sends the same requests in the same order: their sizes,
which of them are check requests, how much of a staged request's answer
is already served, and the arrival times all come from the mix's own
``size_seed``.  The seed draws the token ids and the per-request
sampling seeds (and, elsewhere, the weights).  So two seeds do the same
work: a seed that also chose which lengths were staged, or in what
order, changed which lanes were still decoding in the window, and the
tokens served there with it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the driver will submit it."""

    prompt: np.ndarray  # (S,) int32
    max_new: int
    arrival: float  # seconds after the stream starts (0 for a backlog)
    temperature: float
    top_p: float
    sample_seed: int
    check: bool  # served tokens are the argmax: compared to the reference
    served: int = 0  # of a staged request: answer tokens already served


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from a length distribution of a mix file."""
    if dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    elif dist["dist"] == "fixed":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def generate(mix: dict, cell: dict, vocab: int, seed: int,
             n: int) -> list[Spec]:
    """``n`` requests of ``mix`` for the cell, in the order of ``seed``.

    Every ``mix["check_every"]``-th request drawn is a check request: the
    mix's temperature with a top-p below 1/vocab, so the nucleus holds
    only the head and the draw is the argmax of the served logits,
    through the same sampler as every other request.  The first
    ``cell["in_flight"]`` are staged: each has a uniform share of its
    answer already served (``Spec.served``, applied by ``stagger``), and
    they come most-served first, as the oldest of them would have been
    admitted first."""
    sizes = np.random.default_rng(mix["size_seed"])
    prompts = lengths(mix["prompt"], n, sizes)
    outputs = lengths(mix["output"], n, sizes)
    staged = min(cell["in_flight"], n)
    served = np.zeros(n, np.int64)
    served[:staged] = np.floor(sizes.random(staged) * outputs[:staged])
    check = np.arange(n) % mix["check_every"] == 0
    arrivals = np.zeros(n)
    kind = mix["arrivals"]
    if kind == "poisson":
        arrivals = np.cumsum(sizes.exponential(1.0 / cell["rate_rps"], n))
    elif kind != "backlog":
        raise ValueError(f"unknown arrival kind {kind!r}")
    order = np.concatenate([np.argsort(-served[:staged], kind="stable"),
                            np.arange(staged, n)])
    rng = np.random.default_rng(int(seed) % (1 << 64))
    sp = mix["sampling"]
    out = []
    for i, j in enumerate(order):
        out.append(Spec(
            prompt=rng.integers(0, vocab, prompts[j], dtype=np.int32),
            max_new=int(outputs[j]), arrival=float(arrivals[i]),
            temperature=sp["temperature"],
            top_p=0.5 / vocab if check[j] else sp["top_p"],
            sample_seed=int(rng.integers(0, 2**31 - 1)),
            check=bool(check[j]), served=int(served[j])))
    return out


def stagger(specs: list[Spec], n: int, seed: int, vocab: int) -> None:
    """Open the stream part-way through: the first ``n`` requests are
    already in flight at its start (arrival 0), each with its ``served``
    share of its answer already made.  That share (token ids drawn from
    ``seed``) joins its prompt as context, which set-up prefills, and
    leaves its budget; the requests after them keep their spacing from
    the stream's start."""
    rng = np.random.default_rng((int(seed) % (1 << 64), 7))
    shift = specs[n - 1].arrival if n else 0.0
    for i, s in enumerate(specs):
        if i >= n:
            s.arrival -= shift
            continue
        s.arrival = 0.0
        s.prompt = np.concatenate([s.prompt, rng.integers(
            0, vocab, s.served, dtype=np.int32)])
        s.max_new -= s.served
