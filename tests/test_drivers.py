"""Driver integration tests: train (fault-tolerant), quantize, serve."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import quantize as qz
from repro.launch import serve as sv
from repro.launch import train as tr


@pytest.mark.slow
def test_train_driver_failure_recovery(tmp_path):
    """Injected failure -> restore from checkpoint -> identical replay."""
    rc = tr.main([
        "--arch", "qwen3-14b", "--smoke", "--steps", "8",
        "--global-batch", "2", "--seq-len", "16",
        "--save-every", "3", "--fail-at", "5",
        "--ckpt-dir", str(tmp_path), "--log-every", "2",
    ])
    assert rc == 0
    from repro.checkpoint import latest_step

    assert latest_step(tmp_path) == 8


@pytest.mark.slow
def test_train_driver_resume(tmp_path):
    """A second invocation resumes from the final checkpoint."""
    args = [
        "--arch", "qwen3-14b", "--smoke", "--steps", "4",
        "--global-batch", "2", "--seq-len", "16",
        "--save-every", "2", "--ckpt-dir", str(tmp_path),
    ]
    assert tr.main(args) == 0
    # extend to 6 steps: resumes at 4, not 0
    args[args.index("--steps") + 1] = "6"
    assert tr.main(args) == 0
    from repro.checkpoint import latest_step

    assert latest_step(tmp_path) == 6


@pytest.mark.slow
def test_quantize_driver_2bit_close_to_fp(tmp_path):
    out = tmp_path / "q.json"
    rc = qz.main([
        "--arch", "mistral-large-123b", "--smoke", "--bits", "2",
        "--calib-segments", "8", "--calib-len", "64",
        "--out", str(out),
    ])
    assert rc == 0
    import json

    rec = json.loads(out.read_text())
    # 2-bit with IncP stays within 25% relative ppl of fp on the smoke model
    assert rec["ppl_quant"] < rec["ppl_fp16"] * 1.25


def test_streaming_hessians_bit_identical():
    """Regression for the streaming calibration path: block Hessians (and
    thus every downstream packed weight) must be BIT-identical for every
    chunk size, including the one-shot whole-batch path (chunk=0)."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.models.transformer import unstack_layers
    from repro.models import layers as L
    from repro.data import make_calibration

    cfg = get_smoke_config("qwen3-14b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    calib = make_calibration(cfg.vocab, n_segments=6, seg_len=16, seed=7)
    positions = jnp.arange(calib.tokens.shape[1], dtype=jnp.int32)
    x = L.embed(params["embed"], calib.tokens)
    lp = unstack_layers(params)[0]
    ref = qz.block_hessians(lp, x, cfg, positions, chunk=0)
    for chunk in (1, 2, 4, 5):
        got = qz.block_hessians(lp, x, cfg, positions, chunk=chunk)
        assert set(got) == set(ref)
        for name in ref:
            np.testing.assert_array_equal(
                np.asarray(got[name]), np.asarray(ref[name]),
                err_msg=f"{name} @ chunk={chunk}",
            )


@pytest.mark.slow
def test_streaming_quantize_bit_identical_model():
    """End-to-end: quantize_dense_model with streaming chunks emits the
    exact packed codes of the one-shot path (activation advance included)."""
    from repro.configs import get_smoke_config
    from repro.core.quantizer import QuipConfig
    from repro.data import make_calibration
    from repro.models import build_model

    cfg = get_smoke_config("qwen3-14b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    calib = make_calibration(cfg.vocab, n_segments=4, seg_len=24, seed=7)
    qcfg = QuipConfig(bits=2, method="ldlq", use_kernel=False)
    qms = [
        qz.quantize_dense_model(params, cfg, qcfg, calib.tokens, seed=0,
                                verbose=False, calib_chunk=chunk)
        for chunk in (0, 1)
    ]
    for blk0, blk1 in zip(qms[0].blocks, qms[1].blocks):
        for name, val in blk0.items():
            if hasattr(val, "packed"):
                np.testing.assert_array_equal(
                    np.asarray(val.packed), np.asarray(blk1[name].packed),
                    err_msg=name,
                )


@pytest.mark.slow
def test_serve_driver_quantized_generation():
    """In-process quantize -> engine serve; --check verifies the cached
    decode against the recompute oracle (rc != 0 on divergence)."""
    rc = sv.main([
        "--arch", "qwen3-14b", "--smoke", "--requests", "2",
        "--prompt-len", "16", "--gen", "4", "--quantize", "--bits", "4",
        "--check",
    ])
    assert rc == 0


@pytest.mark.slow
def test_quantize_artifact_then_serve(tmp_path):
    """quantize --out-dir -> serve --load-quantized, no re-quantization."""
    rc = qz.main([
        "--arch", "qwen3-14b", "--smoke", "--bits", "2",
        "--calib-segments", "4", "--calib-len", "32",
        "--out-dir", str(tmp_path / "art"),
    ])
    assert rc == 0
    rc = sv.main([
        "--arch", "qwen3-14b", "--smoke", "--requests", "4",
        "--prompt-len", "16", "--gen", "4",
        "--load-quantized", str(tmp_path / "art"), "--check",
    ])
    assert rc == 0


def test_random_artifact_serves_ragged_prompts(tmp_path):
    """A seeded random 2-bit artifact (how the published-width bring-up
    gets weights, here at smoke width) loads through the unchanged
    ``load_quantized`` and serves ragged prompts on the paged path;
    ``--check`` holds the engine to the kernel-free quantized recompute
    oracle, one batch per prompt length."""
    from repro.configs import get_smoke_config
    from repro.core.quantizer import QuantizedLinear
    from repro.serve.artifacts import load_quantized, save_random_quantized

    cfg = get_smoke_config("qwen3-14b")
    save_random_quantized(tmp_path, cfg, seed=1)
    qm, _ = load_quantized(tmp_path)
    assert len(qm.blocks) == cfg.n_layers
    lins = [v for blk in qm.reference().blocks for v in blk.values()
            if isinstance(v, QuantizedLinear)]
    assert lins and not any(q.use_kernel for q in lins)
    seen = {}
    rc = sv.main([
        "--load-quantized", str(tmp_path), "--paged", "--paged-prefill",
        "--requests", "3", "--prompt-len", "5,19", "--gen", "4",
        "--arrival-gap", "0", "--check",
    ], on_done=lambda eng, sub: seen.update(reqs=[r for _, r in sub]))
    assert rc == 0
    assert [len(r.prompt) for r in seen["reqs"]] == [5, 19, 5]
    for r in seen["reqs"]:
        logits = np.stack(r.step_logits)
        assert logits.shape == (4, cfg.vocab) and np.isfinite(logits).all()


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads the variable; the helper sets nothing), and otherwise
    in one fixed directory of the checkout, the same on every call."""
    import pathlib

    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            d = compile_cache.use_compile_cache()
            assert d == compile_cache.use_compile_cache()
            assert jax.config.jax_compilation_cache_dir == d
            root = pathlib.Path(d).parent
            assert pathlib.Path(d).name == ".jax_cache"
            assert (root / "src" / "repro" / "runtime").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
