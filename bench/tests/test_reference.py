"""The plain reference agrees with the program's own float32 path.

At a tiny size, for a qwen3-like block (qk-norm, GQA group 5) and a
mistral-like one (no qk-norm, GQA group 12), the reference's logits
match ``QuantizedModel.reference()`` (every linear dequantized in jnp,
dense attention) run in float32 on the same harness-made weights.  Two
independent writings of the same mathematics agreeing is what lets the
reference judge the served tokens.
"""
import dataclasses

import jax
import numpy as np
import pytest

from harness import reference, weights
from harness.spec import arch
from tiny import MISTRAL_LIKE, QWEN_LIKE


@pytest.mark.parametrize("model", [QWEN_LIKE, MISTRAL_LIKE],
                         ids=["qwen3-like", "mistral-like"])
def test_reference_matches_program_float32(model):
    from repro.launch.quantize import QuantizedModel

    m = {**model, "dtype": "float32"}
    arrays = weights.build_arrays(m, 2**33 + 5)
    prog = weights.to_program(arrays, arch("tiny", m))
    qm = QuantizedModel(cfg=arch("tiny", m), embed=prog["embed"],
                        final_norm=prog["final_norm"],
                        blocks=prog["blocks"], stats=[]).reference()
    seq = np.random.default_rng(0).integers(0, m["vocab"], 70, np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(qm.logits(seq[None]))[0]
    cand = np.tile(np.arange(m["vocab"], dtype=np.int32), (len(seq), 1))
    best, got, arg = reference.score(arrays, m, seq, cand)
    scale = np.sqrt(np.mean(want**2))
    assert np.max(np.abs(got - want)) < 1e-4 * scale * 10
    np.testing.assert_array_equal(arg, np.argmax(want, -1))
    np.testing.assert_allclose(best, want.max(-1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_control_is_lower_precision(precision):
    """Each lower-precision control differs from float32 by far more
    than float32 differs from itself, and its argmax departs somewhere."""
    m = dict(QWEN_LIKE)
    arrays = weights.build_arrays(m, 3)
    seq = np.random.default_rng(1).integers(0, m["vocab"], 300, np.int32)
    cand = np.zeros((len(seq), 1), np.int32)
    b32, _, a32 = reference.score(arrays, m, seq, cand)
    b8, _, a8 = reference.score(arrays, m, seq, cand, precision=precision)
    assert np.max(np.abs(b8 - b32)) > 1e-3
    assert (a8 != a32).any()


def test_unpack_matches_the_layout():
    """Value j of word i is input 16 i + j, in bits 2j .. 2j+1."""
    codes = np.random.default_rng(2).integers(0, 4, (64, 3))  # (n, m)
    words = np.zeros((4, 3), np.uint32)
    for k in range(64):
        words[k // 16] |= codes[k].astype(np.uint32) << (2 * (k % 16))
    got = np.asarray(reference.unpack(words.view(np.int32), 64))
    np.testing.assert_array_equal(got, codes)
