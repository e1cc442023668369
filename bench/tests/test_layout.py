"""Every cell and metric ``BENCHMARK.json`` names resolves to its files,
and the file keeps to the shape the benchmark's check expects."""
import ast
import json
import re

import pytest

from harness import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def reported(cell, kind):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", CELLS)}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (spec.ROOT / p).is_dir()
    for word in BENCH["command"][1:]:
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=CELLS)
def test_cell_resolves(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    c = spec.cell(w["name"])  # config, mix and cell files load
    assert c["model"]["n_layers"] > 0
    for k in ("n_slots", "n_pages", "prefill_chunk", "token_budget",
              "max_seq_len", "requests", "in_flight", "warm_s",
              "plan_seeds", "tick_model", "check"):
        assert k in c["cell"], k
    mix = c["mix"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        c["cell"]["max_seq_len"]
    assert reported(w["name"], "end_to_end") >= {"setup_s"}
    assert len(reported(w["name"], "end_to_end")) >= 2
    assert reported(w["name"], "per_layer")


@pytest.mark.parametrize("cfg", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    f = spec.ROOT / cfg["file"]
    assert f == spec.config_file(cfg["name"])
    data = json.loads(f.read_text())
    assert data["reduced"] == cfg["reduced"]
    assert data["source"] == cfg["source"]
    for key in cfg["reduced"]:
        assert key in data.get("published", {}), key
        assert not key.endswith(("_dim", "_rank", "_size")), key


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=[m["name"] for m in
                              BENCH["end_to_end"] + BENCH["per_layer"]])
def test_metric_entry(m):
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if m in BENCH["end_to_end"] else {"layer", "moves"})
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_end_to_end_bounds():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("m", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_reader_and_moves(m):
    """A reader file with ``read(ctx)``; the metric it moves is an
    end-to-end metric of every cell that reports this one."""
    path = spec.metric_file(m["name"])
    tree = ast.parse(path.read_text())
    assert any(isinstance(n, ast.FunctionDef) and n.name == "read"
               for n in tree.body)
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    for cell in m.get("workloads", CELLS):
        assert m["moves"] in reported(cell, "end_to_end"), cell


def test_layers_named_alike():
    """A layer is one name, letter for letter, wherever it appears."""
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)
    assert len({x.lower() for x in layers}) == len(layers)


def test_file_is_small():
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
