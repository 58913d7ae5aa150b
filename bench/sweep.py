"""Knee sweep: one cell's traffic at several offered rates, in one process.

    python bench/sweep.py --workload <cell> --rates 40,60,80 --seconds 10 --seed <n> \\
        [--out sweep.json] [--keep-trace DIR]

The knee is the highest offered rate at which the backlog does not grow
through the window: for each rate this prints the tasks outstanding (due
and not finished) at each quarter of the window, the completions inside it
per second, and the latency percentiles, and last the knee by ``knee``.  Programs are built once; every
rate after the first reuses them.  ``--keep-trace`` profiles the first rate
and keeps its ``.xplane.pb`` for reading by hand
(``python bench/trace_reduce.py <file>``).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run, spec  # noqa: E402


def outstanding(rec, at: float) -> int:
    return sum(t.due <= at < t.done for t in rec.tasks)


def knee(rows: list[dict]) -> float | None:
    """The highest rate that completes 0.95 of its offer inside the window
    without the backlog growing from mid-window to the close (by more than
    9 tasks and half again)."""
    ok = []
    for r in rows:
        q = r["outstanding_at_quarters"]
        growing = q[3] > q[1] + 9 and q[3] > 1.5 * q[1]
        if r["done_in_window_per_s"] >= 0.95 * r["rate_per_s"] and not growing:
            ok.append(r["rate_per_s"])
    return max(ok, default=None)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    ap.add_argument("--keep-trace")
    args = ap.parse_args()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"])
    devices = run.accelerator(cell["chips"])
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(spec.traffic(cell["traffic"]), rate_per_s=rate)
        trace = bool(args.keep_trace) and i == 0
        out, rec = run.run_cell(bench, cell, config, traffic, args.seed + i, args.seconds, trace, devices,
                                keep_trace=args.keep_trace if trace else None,
                                log=lambda s: print(s, file=sys.stderr, flush=True))
        end = rec.window_start + rec.seconds
        lat = {q: spec.plugin("metrics", f"task_p{q}_ms").read(rec) for q in (50, 95)}
        row = {
            "rate_per_s": rate,
            "attempted": out["attempted"],
            "correct": out["correct"],
            "done_in_window_per_s": sum(t.ok and t.done <= end for t in rec.tasks) / rec.seconds,
            "outstanding_at_quarters": [outstanding(rec, rec.window_start + f * rec.seconds) for f in (0.25, 0.5, 0.75, 1.0)],
            "p50_ms": lat[50], "p95_ms": lat[95],
            "setup_s": rec.setup_s,
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
        }
        if trace:
            row["per_layer"] = out["metrics"]
            row["device"] = out["device"]
            row["breakdown"] = out.get("breakdown")
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"knee_per_s": knee(rows)}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
