"""The deployment a configuration file describes, built through the broker's
public API: one ``Hydra`` with the file's broker settings and one provider
per entry of ``providers`` (``ProviderSpec`` fields as given)."""
from __future__ import annotations

import concurrent.futures
import time


def build(config: dict, workdir: str):
    from repro.core import Hydra, ProviderSpec

    h = Hydra(workdir=workdir, streaming=True, **config["broker"])
    for p in config["providers"]:
        h.register_provider(ProviderSpec(**p))
    return h


def payloads(config: dict) -> dict:
    return {p["name"]: p for p in config["payloads"]}


def task(payload: dict, operand_seed: int, provider: str | None = None):
    """One ``kind="kernel"`` task of a catalogue payload."""
    from repro.core import Task

    return Task(kind="kernel", provider=provider, payload={
        "kernel": payload["kernel"], "shape": dict(payload["shape"]),
        "dtype": payload["dtype"], "seed": operand_seed, "reps": 1,
    })


def warm(h, config: dict, names: list[str], operand_seed: int, timeout_s: float) -> int:
    """Runs every payload in ``names`` once on every provider, pinned, so
    that each (kernel, shape, dtype, device) program is built before the
    window.  Returns the number of tasks; raises if any did not finish."""
    cat = payloads(config)
    tasks = [
        task(cat[name], operand_seed, provider=p["name"])
        for p in config["providers"] for name in names
    ]
    h.dispatch(tasks)
    _, pending = concurrent.futures.wait(tasks, timeout=timeout_s)
    if pending:
        raise RuntimeError(f"warm-up: {len(pending)} of {len(tasks)} tasks unfinished after {timeout_s} s")
    for t in tasks:
        t.result()  # raises the task's own error
    # the broker counts an execution just after the task's future resolves
    deadline = time.monotonic() + timeout_s
    while h.kernel_execs < len(tasks) and time.monotonic() < deadline:
        time.sleep(0.01)
    return len(tasks)
