"""Finds where a run stalls: one run of a cell, as ``run.py`` makes it, with
a watcher thread beside it.

    python bench/stalls.py --workload <cell> --seed <n> --seconds <s> [--out FILE]

The watcher wakes every 20 ms.  It reports, for the measured window: each
time it woke 200 ms or more late itself (the whole process held up: the
interpreter lock held, or the process not scheduled), with every thread's
innermost frames as it woke; each stretch of 250 ms or more in which the
generator's thread stood in one place other than its sleep (blocked inside
the broker), with that place; and each garbage collection of 50 ms or more.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run, spec  # noqa: E402

PERIOD_S = 0.02


def frames(frame, n: int = 8) -> list[str]:
    return [f"{Path(f.filename).name}:{f.lineno} {f.name}" for f in traceback.extract_stack(frame)[-n:]]


class Watcher(threading.Thread):
    def __init__(self, target: int):
        super().__init__(name="bench-watcher", daemon=True)
        self.target, self.stop = target, threading.Event()
        self.late, self.blocked, self.gcs = [], [], []
        self._gc_start = None

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            took = time.perf_counter() - self._gc_start
            if took >= 0.05:
                self.gcs.append({"at": self._gc_start, "s": took, "generation": info["generation"],
                                 "collected": info["collected"]})

    def run(self):
        place, since = None, None
        while not self.stop.is_set():
            t = time.perf_counter()
            time.sleep(PERIOD_S)
            now = time.perf_counter()
            current = sys._current_frames()
            if now - t - PERIOD_S >= 0.2:
                self.late.append({"at": t, "s": now - t - PERIOD_S, "threads": {
                    th.name: frames(current[th.ident], 6) for th in threading.enumerate() if th.ident in current}})
            f = current.get(self.target)
            if f is None:
                continue
            here = frames(f)
            sleeping = f.f_code.co_name == "send"
            if sleeping or here != place:
                if place is not None and since is not None and now - since >= 0.25:
                    self.blocked.append({"at": since, "s": now - since, "where": place})
                place, since = (None, None) if sleeping else (here, now)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    devices = run.accelerator(cell["chips"])
    w = Watcher(threading.main_thread().ident)
    gc.callbacks.append(w.on_gc)
    w.start()
    out, rec = run.run_cell(bench, cell, config, traffic, args.seed, args.seconds, False, devices,
                            log=lambda s: print(s, file=sys.stderr, flush=True))
    w.stop.set()
    w.join()
    lo, hi = rec.window_start, rec.window_start + rec.seconds

    def inside(xs):
        return [dict(x, at=x["at"] - lo) for x in xs if lo <= x["at"] < hi]

    report = {"metrics": {k: v["value"] for k, v in out["metrics"].items()}, "correct": out["correct"],
              "late": inside(w.late), "blocked": inside(w.blocked), "gc": inside(w.gcs)}
    print(json.dumps(report), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
