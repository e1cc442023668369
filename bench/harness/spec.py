"""What a cell is made of, found by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` reads four files, each found by name:

* ``bench/configs/<config>.json`` — the model as published (the keys of
  its ``config.json``), what was cut (``reduced``), what was assumed, and
  the deployment it stands for;
* ``bench/traffic/<traffic>.json`` — the mix: lengths, arrivals, sampling;
* ``bench/cells/<cell>.json`` — the engine's geometry for this model
  under this mix (slots, pages, chunk, budget), the rate or backlog, and
  the warm-up;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric.
"""
from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_file(name: str) -> pathlib.Path:
    return BENCH / "configs" / f"{name}.json"


def traffic_file(name: str) -> pathlib.Path:
    return BENCH / "traffic" / f"{name}.json"


def cell_file(name: str) -> pathlib.Path:
    return BENCH / "cells" / f"{name}.json"


def metric_file(name: str) -> pathlib.Path:
    return BENCH / "metrics" / f"{name}.py"


def model(cfg: dict) -> dict:
    """The sizes the harness and the reference use, from a config file
    that holds a published ``config.json``'s keys."""
    if cfg.get("hidden_act") != "silu":
        raise ValueError("only SwiGLU (hidden_act silu) blocks are supported")
    if cfg.get("tie_word_embeddings") or cfg.get("sliding_window"):
        raise ValueError("tied heads and sliding windows are not supported")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    return {
        "n_layers": cfg["num_hidden_layers"], "d_model": d,
        "n_heads": heads, "n_kv_heads": kv, "head_dim": hd,
        "q_dim": heads * hd, "kv_dim": kv * hd,
        "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "qk_norm": bool(cfg["qk_norm"]), "qkv_bias": bool(
            cfg.get("attention_bias", False)),
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"], "bits": cfg["weight_bits"],
    }


def arch(name: str, m: dict):
    """The program's ``ArchConfig`` for the same sizes."""
    from repro.configs.base import ArchConfig

    if m["qkv_bias"]:
        raise ValueError("attention biases are not built by the harness")
    return ArchConfig(
        name=name, family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"], vocab=m["vocab"],
        head_dim=m["head_dim"], qk_norm=m["qk_norm"],
        rope_theta=m["rope_theta"], mlp="swiglu", norm_eps=m["norm_eps"],
        tie_embeddings=False, dtype=m["dtype"], weight_bits=m["bits"])


def cell(name: str) -> dict:
    """Everything one cell needs: its workload entry, the model sizes,
    the mix and the geometry."""
    w = workload(name)
    cfg = load_json(config_file(w["config"]))
    return {"workload": w, "config": cfg, "model": model(cfg),
            "mix": load_json(traffic_file(w["traffic"])),
            "cell": load_json(cell_file(name))}
