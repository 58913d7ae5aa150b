"""Profiler trace (``.xplane.pb``) to device busy time, per-kernel device time
and idle gaps.

    python bench/trace_reduce.py <file.xplane.pb>    # what the trace holds

On a TPU each operation on a device's "XLA Ops" line is named by its HLO
text (``%name = type custom-call(type %operand, ...)``).  Device time is
the union of those operations' intervals: overlapping operations count
once.  A kernel's calls are the custom calls (Pallas kernels lower to
``tpu_custom_call``) whose text names the payload's operand and result
types (``work/<kernel>.py`` ``hlo_types``); the payloads of a cell are
told apart by those types.  An idle gap is a stretch of the traced window
in which no operation ran on the device; it is labelled by the host event
that overlaps it most.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Event:
    name: str  # on a device's ops line, the op's HLO text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)  # device id -> [Event] on its ops line
    host: list = field(default_factory=list)  # [Event] of every host thread


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    """The device ops and host events of a ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name, e.start_ns, e.duration_ns) for e in line.events)
            tr.ops[int(m.group(1))] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.host.extend(Event(e.name, e.start_ns, e.duration_ns) for e in line.events if e.duration_ns > 0)
    return tr


def union(events) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of the events, in order."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy_ns(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of [lo, hi) outside every interval."""
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def matches(e: Event, types: tuple[list[str], list[str]]) -> bool:
    """The op is a custom call whose HLO text names each of the payload's
    types (``work/<kernel>.py`` ``hlo_types``) at least as often as they
    list it."""
    ins, outs = types
    if " custom-call(" not in e.name:
        return False
    call = e.name.split(", custom_call_target=")[0]  # the attributes repeat the types
    return all(call.count(t) >= (ins + outs).count(t) for t in set(ins + outs))


def kernel_calls(ops: list, types_by_payload: dict) -> dict:
    """{payload: (calls, device ns)}.  An op that several payloads' types
    match goes to the one that names the most types (the most specific)."""
    out: dict = defaultdict(lambda: [0, 0.0])
    for e in ops:
        hits = [p for p, types in types_by_payload.items() if matches(e, types)]
        if not hits:
            continue
        best = max(hits, key=lambda p: (len(types_by_payload[p][0]) + len(types_by_payload[p][1]), p))
        out[best][0] += 1
        out[best][1] += e.dur_ns
    return {k: (v[0], v[1]) for k, v in out.items()}


def top_ops(ops: list, n: int = 10) -> list[tuple[str, float]]:
    """Device seconds by op (``label``), largest first."""
    total: dict = defaultdict(float)
    for e in ops:
        total[label(e)] += e.dur_ns
    return [(k, v / 1e9) for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


_HLO = re.compile(r"^%?(?P<name>[^ =]+) = (?P<out>\(?[a-z0-9]+\[[0-9,]*\])")
_KIND = re.compile(r"[})\]] (?P<kind>[a-z][\w-]*)\(")


def label(e: Event) -> str:
    """The op as its HLO text names it, without layouts and operands:
    ``_lambda_.1 bf16[1,48,8192,128] custom-call``."""
    m = _HLO.match(e.name)
    if not m:
        return e.name
    k = _KIND.search(e.name, m.end())
    out = f"{m['out']}, ...)" if m["out"].startswith("(") else m["out"]
    return f"{m['name']} {out} {k['kind'] if k else ''}".rstrip()


def gap_labels(gs, host: list, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` longest gaps, each named by the host event that overlaps
    it most (shorter than the gap's tenfold, so that thread-long events do
    not name every gap)."""
    out = []
    for a, b in sorted(gs, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "no host event", 0.0
        for e in host:
            if e.dur_ns > 10 * (b - a):
                continue
            o = min(b, e.end_ns) - max(a, e.start_ns)
            if o > cover:
                best, cover = e.name, o
        out.append((f"idle during {best}", (b - a) / 1e9))
    return out


def summary(path: str) -> None:
    """Prints what the trace holds: planes, lines, event counts, and a few
    events of each device line with their stats (a look by hand)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if DEVICE_PLANE.match(plane.name) or line.name in ("python",):
                for e in evs[:4]:
                    stats = {k: (v if not isinstance(v, str) else v[:300]) for k, v in e.stats}
                    print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} stats={stats}")


if __name__ == "__main__":
    summary(sys.argv[1])
