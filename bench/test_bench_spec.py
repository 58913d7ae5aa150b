"""BENCHMARK.json keeps to its contract, and every name in it resolves to
its file."""
from __future__ import annotations

import json
import re

import pytest

from bench import spec

B = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|_dim$|_rank$|expansion|per_tok)", re.I)
METRICS = B["end_to_end"] + B["per_layer"]


def one_line(s: str) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_size():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(B)) <= 64 * 1024
    assert B["paths"] == ["bench"]
    assert 1 <= len(B["command"]) <= 32 and all(one_line(w) for w in B["command"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51


def test_entries_have_only_their_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert one_line(m["layer"])


def test_names_units_and_text():
    names = [x["name"] for x in B["configs"] + B["workloads"] + METRICS]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in B["workloads"]] + [k for c in B["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for x in B["configs"] + B["workloads"]:
        assert one_line(x["why"])
    for c in B["configs"]:
        assert one_line(c["source"]) and c["source"].startswith("https://")
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
    setup = next(m for m in B["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25


def test_every_name_resolves_to_its_file():
    for w in B["workloads"]:
        config = spec.config(B, w["config"])
        traffic = spec.traffic(w["traffic"])
        cat = {p["name"] for p in config["payloads"]}
        assert set(traffic["weights"]) <= cat
        for p in config["payloads"]:
            assert callable(spec.plugin("work", p["kernel"]).work)
            assert callable(spec.plugin("reference", p["kernel"]).outputs)
            assert p["limit"] > 0
    for m in METRICS:
        assert callable(spec.plugin("metrics", m["name"]).read)
    for c in B["configs"]:
        assert c["file"].startswith("bench/configs/")
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])


def test_cells_report_what_the_contract_asks():
    used_configs = {w["config"] for w in B["workloads"]}
    assert used_configs == {c["name"] for c in B["configs"]}
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) == len(B["workloads"])
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, len(B["workloads"]) // 2)
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"] for m in B["end_to_end"]}
    for m in METRICS:
        assert set(m.get("workloads", cells)) <= cells
    for m in B["per_layer"]:
        assert m["moves"] in e2e
    for cell in cells:
        reported = {m["name"] for m in spec.metrics_for(B, cell, trace=False)}
        assert "setup_s" in reported and len(reported) >= 2
        per_layer = spec.metrics_for(B, cell, trace=True)
        assert per_layer
        assert all(m["moves"] in reported for m in per_layer)


def test_layers_name_one_layer_each():
    layers = {m["layer"] for m in B["per_layer"]}
    assert layers == {"dispatcher", "executor", "kernel runtime", "kernels", "device"}


@pytest.mark.parametrize("name", [c["name"] for c in B["configs"]])
def test_config_files_state_their_cut(name):
    config = spec.config(B, name)
    assert {"source", "reduced", "assumed", "broker", "providers", "payloads"} <= set(config)
    for p in config["payloads"]:
        assert {"name", "kernel", "shape", "dtype", "source", "reduced", "assumed", "limit"} <= set(p)
        assert NAME.match(p["name"])
    offsets = {p["device_offset"] for p in config["providers"]}
    chips = next(w["chips"] for w in B["workloads"] if w["config"] == name)
    assert offsets <= set(range(chips))


def test_peaks_have_their_source():
    table = spec.load_json(spec.BENCH / "peaks.json")
    assert "Google Cloud" in table["source"]
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v0")
