"""A run with the timed path broken underneath reads ``correct`` false.

Each test drives a whole run of a tiny cell (``run.run_cell``: weights,
programs, warm traffic, the window, the reference check), skipping only
the look for a chip.  The sound run passes; the control (the reference
in int8 put in the program's place) and each fault a served model can
have fail: a token altered where it is produced, and a step that
returns its state unchanged (the KV write left out).  The limit of the
tiny cell sits between what sound runs and the control read at this
size (program 0 to 0.008, control 0.26 to 0.35 on four runs).
"""
import numpy as np
import pytest

import run
import tiny
from harness import check, weights

SECONDS = 3.0


def serve_tiny(model, seed):
    c = tiny.cell(model)
    r = run.run_cell(c, seed, SECONDS, False)
    return c, r


def test_sound_run_is_correct():
    _, r = serve_tiny(tiny.QWEN_LIKE, 5)
    assert r["checks"]["served_tokens_checked"]["value"] >= 40
    assert r["correct"], r["checks"]
    assert r["compiles_in_window"] == 0


def test_control_fails():
    """The reference in int8, picking the tokens at the served
    positions, lies past the limit."""
    c = tiny.cell(tiny.MISTRAL_LIKE)
    seed = 6
    arrays = weights.build_arrays(c["model"], seed)
    adapter = run.make_adapter(c, arrays)
    specs, progs = run.plan(c, seed, SECONDS)
    from harness import programs
    from harness.clock import CompileClock

    clock = CompileClock()
    engine = run.new_engine(c, adapter, False)
    programs.warm(adapter, engine.pool, progs, c["cell"]["prefill_chunk"],
                  0.7, clock, lambda *a: None)
    r = run.serve(c, engine, specs, SECONDS, clock)
    checks = run.check_tokens(c, arrays, r, seed)
    assert run.passed({k: v for k, v in checks.items() if k != "_reqs"})
    gaps = check.control_gaps(arrays, c["model"], checks["_reqs"])
    assert gaps.max() > c["cell"]["check"]["widest_gap_limit"]


def test_altered_token_fails(monkeypatch):
    import repro.serve.adapter as ad
    import repro.serve.engine as en

    orig = ad.sample_tokens

    def off_by_one(logits, *a, **k):
        return (orig(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(ad, "sample_tokens", off_by_one)
    monkeypatch.setattr(en, "sample_tokens", off_by_one)
    _, r = serve_tiny(tiny.QWEN_LIKE, 7)
    assert not r["correct"]
    assert r["checks"]["widest_gap"]["value"] > \
        r["checks"]["widest_gap"]["limit"]


def test_half_batch_left_out_fails(monkeypatch):
    """The decode program serves only the second half of its lanes: the
    first half, where the lowest free slots put every admitted request
    first, get token 0 instead of their own."""
    import repro.serve.adapter as ad

    orig = ad.CachedDecoder.decode_paged_sample

    def half(self, *a, **k):
        sel, rest = orig(self, *a, **k)
        sel = np.asarray(sel).copy()
        sel[:sel.shape[0] // 2] = 0
        return sel, rest

    monkeypatch.setattr(ad.CachedDecoder, "decode_paged_sample", half)
    _, r = serve_tiny(tiny.QWEN_LIKE, 9)
    assert not r["correct"]


def test_state_left_unchanged_fails(monkeypatch):
    import repro.serve.adapter as ad

    monkeypatch.setattr(ad, "write_kv",
                        lambda k, v, ks, vs, *rest: (k, v, ks, vs))
    _, r = serve_tiny(tiny.QWEN_LIKE, 8)
    assert not r["correct"]
