"""Whether the window served the right tokens.

After the window, a sample (drawn from the seed) of the check requests
the window served, the longest among them, is replayed through the
plain reference teacher-forced on each prompt and every token served to
it so far, finished or not.  A check
request's tokens are the argmax of the served logits, so each should be
the reference's best token or a near-tie of it.  The number compared is
the widest gap, over every served position, between the reference's
best logit and its logit for the served token.
"""
from __future__ import annotations

import numpy as np

from harness import reference


def sample(driver, window, seed: int, max_tokens: int) -> list:
    """Check requests the window served a token to, finished or still
    decoding: the longest, then others in an order drawn from ``seed``,
    each that keeps the replay within ``max_tokens`` positions."""
    t0, t1 = window
    done = [r for r in driver.reqs
            if driver.specs[r.rid].check and r.out_tokens
            and any(t0 < t <= t1 for t in driver.times[r.rid])]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.out_tokens), r.rid))
    picked = [done.pop()]
    rng = np.random.default_rng((int(seed) % (1 << 64), 11))
    total = len(picked[0].prompt) + len(picked[0].out_tokens)
    for i in rng.permutation(len(done)):
        r = done[i]
        n = len(r.prompt) + len(r.out_tokens)
        if total + n > max_tokens:
            continue
        picked.append(r)
        total += n
    return picked


def _rows(r):
    seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
    first = len(r.prompt) - 1
    return seq, np.arange(first, first + len(r.out_tokens))


def served_gaps(arrays, m, reqs) -> np.ndarray:
    """Per served position of ``reqs``: reference best minus reference
    logit of the served token."""
    out = []
    for r in reqs:
        seq, rows = _rows(r)
        cand = np.zeros((len(seq), 1), np.int32)
        cand[rows, 0] = r.out_tokens
        best, got, _ = reference.score(arrays, m, seq, cand)
        out.append(best[rows] - got[rows, 0])
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(arrays, m, reqs, precision="int8") -> np.ndarray:
    """The control: the reference in a lower ``precision`` picks its best
    token at each served position of ``reqs`` (same prompts, same served
    tokens as context); returns how far below the float32 reference's
    best each of those picks lies."""
    out = []
    for r in reqs:
        seq, rows = _rows(r)
        _, _, arg8 = reference.score(arrays, m, seq,
                                     np.zeros((len(seq), 1), np.int32),
                                     precision=precision)
        cand = np.zeros((len(seq), 1), np.int32)
        cand[rows, 0] = arg8[rows]
        best, got, _ = reference.score(arrays, m, seq, cand)
        out.append(best[rows] - got[rows, 0])
    return np.concatenate(out) if out else np.zeros(0)
