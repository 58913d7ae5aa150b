"""Shared pieces of the benchmark's own CPU tests: a cell of BENCHMARK.json
cut to tiny shapes, run through the whole harness but its look for a chip."""
from __future__ import annotations

import copy
import sys

import pytest

from bench import run, spec

# published widths do not fit an interpreted kernel on a CPU: these keep each
# kernel's structure (GQA groups, causal mask, state size, blocks) and shrink
# the rest
TINY = {
    "flash_attention": {"B": 1, "H": 4, "KV": 2, "L": 128, "hd": 128, "causal": True, "window": None},
    "moe_gmm": {"E": 1, "C": 64, "D": 128, "F": 256},
    "selective_scan": {"B": 1, "chunk": 32, "di": 128, "N": 16},
}
SEED = 2**31 + 12345  # benchmark seeds run large: beyond 32 signed bits


def tiny_config(bench: dict, name: str) -> dict:
    config = copy.deepcopy(spec.config(bench, name))
    for p in config["payloads"]:
        p["shape"] = dict(TINY[p["kernel"]])
    return config


def run_tiny(cell_name: str, rate: float = 4.0, seconds: float = 2.0, trace: bool = False, seed: int = SEED):
    import jax

    bench = spec.benchmark()
    cell = spec.cell(bench, cell_name)
    traffic = dict(spec.traffic(cell["traffic"]), rate_per_s=rate)
    return run.run_cell(bench, cell, tiny_config(bench, cell["config"]), traffic, seed, seconds, trace,
                        jax.devices(), log=lambda s: print(s, file=sys.stderr))


@pytest.fixture
def tiny():
    return run_tiny
