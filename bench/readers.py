"""Arithmetic the metric readers share (``metrics/<name>.py``)."""
from __future__ import annotations

import math


def first(events: list, name: str) -> float | None:
    for e, t in events:
        if e == name:
            return t
    return None


def last(events: list, name: str) -> float | None:
    out = None
    for e, t in events:
        if e == name:
            out = t
    return out


def mean_ms(rec, start: str | None, end: str) -> float | None:
    """Mean over the window's tasks of the first ``end`` stamp minus the
    first ``start`` stamp (``None``: the task's due time), in ms."""
    spans = []
    for t in rec.tasks:
        a = t.due if start is None else first(t.events, start)
        b = last(t.events, end) if end == "exec_done" else first(t.events, end)
        if a is not None and b is not None:
            spans.append(b - a)
    return 1e3 * sum(spans) / len(spans) if spans else None


def percentile_ms(rec, q: float) -> float | None:
    """Nearest-rank percentile of due-to-completion latency over every task
    due in the window; a task that failed or never finished counts as
    finishing when the run gave up on it, beyond every other."""
    lat = sorted(t.done - t.due for t in rec.tasks)
    if not lat:
        return None
    return 1e3 * lat[max(0, math.ceil(q / 100 * len(lat)) - 1)]


def roofline_pct(rec, kernel: str) -> float | None:
    """Least time of the kernel's traced calls (the larger of FLOPs over the
    peak and bytes over the bandwidth, per call) over their device time."""
    d = rec.device
    if d is None or not rec.peaks:
        return None
    least = spent = 0.0
    for payload, (n, secs) in d["calls"].items():
        if rec.kernel_of[payload] != kernel or n == 0:
            continue
        flops, nbytes = rec.work[payload]
        least += n * max(flops / rec.peaks["flops_per_s"], nbytes / rec.peaks["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent > 0 else None


def idle_pct(rec) -> float | None:
    d = rec.device
    if d is None:
        return None
    return 100.0 * (1.0 - sum(d["busy_s"]) / len(d["busy_s"]) / d["window_s"])


def mfu_pct(rec) -> float | None:
    """Algorithmic FLOPs of every kernel call traced, over the peak of the
    cell's chips for the traced window."""
    d = rec.device
    if d is None or not rec.peaks or not d["calls"]:
        return None
    flops = sum(n * rec.work[p][0] for p, (n, _) in d["calls"].items())
    return 100.0 * flops / (rec.peaks["flops_per_s"] * rec.chips * d["window_s"])
