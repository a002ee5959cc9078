"""BENCHMARK.json and the files it names: every one is found by its name
and keeps the contract's rules on names, units, keys and chips."""

import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_top_level_keys_and_command():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def _all_named():
    b = _bench()
    return [(kind, e) for kind in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in b[kind]]


@pytest.mark.parametrize("kind,entry", _all_named(),
                         ids=lambda x: x if isinstance(x, str)
                         else x["name"])
def test_names_units_and_keys(kind, entry):
    assert NAME.match(entry["name"])
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[kind]
    extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
    assert keys <= set(entry) <= keys | extra
    for k in ("why", "layer", "source"):
        if k in entry:
            assert 1 <= len(entry[k]) <= 200
            assert "\n" not in entry[k] and "\t" not in entry[k]
    if kind in ("end_to_end", "per_layer"):
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    if kind == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    if kind == "per_layer":
        assert 1 <= len(entry["layer"]) <= 200
        assert "\n" not in entry["layer"]
        assert entry["moves"] in {m["name"] for m in _bench()["end_to_end"]}
        if entry["name"].endswith("_roofline"):
            assert entry["unit"] == "%"


def test_no_duplicate_names_and_pairs():
    b = _bench()
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in b[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_chips_and_every_config_used():
    b = _bench()
    chips = [w["chips"] for w in b["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
    assert {c["name"] for c in b["configs"]} == {w["config"]
                                                 for w in b["workloads"]}
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])


@pytest.mark.parametrize("cfg", _bench()["configs"], ids=lambda c: c["name"])
def test_config_file_found_by_name(cfg):
    assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
    data = _json("configs", cfg["name"] + ".json")
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank)$|embd|inner|head", key)
    fam = data["family"]
    for kind in ("references", "flops"):
        assert os.path.isfile(os.path.join(BENCH, kind, fam + ".py"))
    assert isinstance(data["assumed"], list) and data["assumed"]


@pytest.mark.parametrize("w", _bench()["workloads"], ids=lambda w: w["name"])
def test_workload_files_found_by_name(w):
    traffic = _json("traffic", w["traffic"] + ".json")
    cell = _json("workloads", w["name"] + ".json")
    assert traffic["data_parallel"] == w["chips"]
    assert os.path.isfile(os.path.join(BENCH, "recipes",
                                       cell["recipe"] + ".py"))
    assert set(cell["limits"]) == {"loss_gap", "grad_norm_gap",
                                   "change_norm_gap"}
    assert 1 <= len(w["why"]) <= 200
    b = _bench()
    e2e = {m["name"] for m in b["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(w["name"] in m.get("workloads", []) for m in b["per_layer"])


@pytest.mark.parametrize("m", _bench()["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_found_by_name(m):
    path = os.path.join(BENCH, "metrics", m["name"] + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
    cells = {w["name"] for w in _bench()["workloads"]}
    assert set(m["workloads"]) <= cells


def test_peaks_table_has_no_default():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import harness

    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 1.97e14
    with pytest.raises(KeyError):
        harness.peaks("cpu")
