"""Decides ``correct``: every task due in the window against the reference.

A kernel task's answer, as the broker hands it to its tenant, is the result
dict: the device it ran on and the float32 sum of its output (``checksum``).
Tasks that carry the same payload and operand seed must give the same
answer, so the reference runs once for each such pair, on operands it makes
itself from the seed (``reference/<kernel>.py``), and every task is judged
against it.

Numbers compared, each with its limit:

    not_done      tasks due in the window that did not end DONE        0
    retries       retries among them                                   0
    wrong_device  tasks that ran off their provider's chips            0
    execs_off     |kernel executions in the window - tasks attempted|  0
    gap.<payload> the widest |checksum - reference sum| / reference    the payload's
                  L2 norm over that payload's tasks                    ``limit``

Dividing by the L2 norm makes independent rounding errors read as their
relative RMS, whatever the output's size.  The control (``lower=True``)
rounds every floating operand to the next precision below the payload's
(float32 -> bfloat16, bfloat16 -> float8_e4m3fn) before the reference runs.
"""
from __future__ import annotations

import math

import numpy as np

from bench import spec

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}

_MAKERS: dict = {}


def _operands(kernel: str, shape: dict, dtype: str, seed: int, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    key = (kernel, tuple(sorted(shape.items())), dtype, device)
    if key not in _MAKERS:
        ref = spec.plugin("reference", kernel)
        _MAKERS[key] = jax.jit(
            lambda s: ref.operands(shape, dtype, s), out_shardings=SingleDeviceSharding(device)
        )
    return _MAKERS[key](jnp.int32(seed))


def _round_down(a):
    import jax.numpy as jnp

    name = str(a.dtype)
    if name not in LOWER:
        return a
    return a.astype(jnp.dtype(LOWER[name])).astype(a.dtype)


def answer(kernel: str, shape: dict, dtype: str, seed: int, device, lower: bool = False) -> tuple[float, float]:
    """(sum, L2 norm) of the reference output for these operands, in float64."""
    args = _operands(kernel, shape, dtype, seed, device)
    if lower:
        args = tuple(_round_down(a) for a in args)
    return summary(spec.plugin("reference", kernel).outputs(shape, args))


def summary(blocks) -> tuple[float, float]:
    """(sum, L2 norm) in float64 of an output given in blocks."""
    total = squares = 0.0
    for block in blocks:
        a = np.asarray(block).astype(np.float64)
        total += float(a.sum())
        squares += float(np.square(a).sum())
    return total, math.sqrt(squares)


def references(config: dict, pairs, device, lower: bool = False) -> dict:
    """{(payload name, operand seed): (sum, norm)} for every pair given."""
    cat = {p["name"]: p for p in config["payloads"]}
    return {
        (name, seed): answer(cat[name]["kernel"], cat[name]["shape"], cat[name]["dtype"], seed, device, lower)
        for name, seed in sorted(set(pairs))
    }


def gap(checksum: float, ref: tuple[float, float]) -> float:
    total, norm = ref
    return abs(checksum - total) / norm if norm > 0 else abs(checksum - total)


def _finished(t) -> bool:
    from repro.core import TaskState

    return t.tstate == TaskState.DONE and t.done() and t.exception() is None


def judge(config: dict, window: list, provider_devices: dict, platform: str,
          execs: int, refs: dict) -> tuple[dict, list[bool]]:
    """``window``: (arrival, task) pairs.  Returns the numbers compared,
    {name: (value, limit)}, and for each task whether it finished with an
    answer inside its payload's limit."""
    limits = {p["name"]: p["limit"] for p in config["payloads"]}
    not_done = retries = wrong = 0
    gaps: dict[str, float] = {}
    oks = []
    for arrival, t in window:
        retries += t.retries
        if not _finished(t):
            not_done += 1
            oks.append(False)
            continue
        r = t.result()
        if r.get("platform") != platform or r.get("device") not in provider_devices.get(t.provider, ()):
            wrong += 1
        g = gap(r["checksum"], refs[(arrival.payload, arrival.operand_seed)])
        gaps[arrival.payload] = max(gaps.get(arrival.payload, 0.0), g)
        oks.append(g <= limits[arrival.payload])
    checks = {
        "not_done": (not_done, 0),
        "retries": (retries, 0),
        "wrong_device": (wrong, 0),
        "execs_off": (abs(execs - len(window)), 0),
    }
    for name in sorted(gaps):
        checks[f"gap.{name}"] = (gaps[name], limits[name])
    return checks, oks


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())
