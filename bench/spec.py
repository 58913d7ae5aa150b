"""Finds a cell's files by the names in ``BENCHMARK.json``.

    configs/<config>.json     the deployment: providers, broker settings, payloads
    traffic/<traffic>.json    the arrival process, rate, payload weights
    work/<kernel>.py          algorithmic FLOPs and bytes of one call
    reference/<kernel>.py     plain jax.numpy reference of the kernel
    metrics/<metric>.py       one reader per metric, end-to-end or per-layer

A new configuration, mix, kernel or metric is a new file; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {[c['name'] for c in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; have {sorted(table)}")
    return table[device_kind]


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench._loaded." + path.relative_to(BENCH).with_suffix("").as_posix().replace("/", ".")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_CACHE: dict = {}


def plugin(kind: str, name: str) -> ModuleType:
    """``work``, ``reference`` or ``metrics`` module of that name."""
    key = (kind, name)
    if key not in _CACHE:
        _CACHE[key] = _module(BENCH / kind / f"{name}.py")
    return _CACHE[key]


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of the cell reports: its end-to-end metrics with
    ``trace`` off, its per-layer metrics with it on.  A metric without a
    ``workloads`` key belongs to every cell (end-to-end) or to every cell
    that reports the end-to-end metric it moves (per-layer)."""
    e2e = [m for m in bench["end_to_end"] if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if cell_name in m.get("workloads", [cell_name] if m["moves"] in reported else [])
    ]
