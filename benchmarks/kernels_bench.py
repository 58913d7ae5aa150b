"""Kernel micro-bench + exp14 autotuner arm (registry-driven, CI-gated).

Two row families feed ``BENCH_smoke.json`` (benchmarks/run.py --smoke) and
the check_bench.py gates:

  kernel_<name>   one row per registered kernel at its smoke (or --full)
                  shape: the us_per_call column is the XLA reference path
                  (the number that moves with real perf on this CPU host),
                  ``derived`` carries ``allclose_err`` (interpret-mode
                  Pallas vs reference, HARD-gated at 1e-3) and ``xla_us``
                  (relative 30% regression gate).
  exp14_kernels   the tuned-vs-default demonstration: the roofline
                  autotuner sweeps each demo shape (wall timer, warm-up +
                  min-of-3), the committed default config is timed the same
                  way, and the row reports the best tuned/default speedup
                  plus the pruner's sweep cut — both HARD-gated
                  (speedup >= 1.15x, cut >= 2x).

Demo shapes are deliberately small-batch/large-feature: on the interpret
path (and on the roofline model) those shapes make the grid-cell count the
dominant config-sensitive term, so the committed default block (512) is
measurably beaten by the full-width block the tuner picks.  See
docs/EXPERIMENTS.md §exp14 for measured numbers + noise discussion.
"""
from __future__ import annotations

import sys
import time

import jax

from repro.kernels import registry as kreg
from repro.kernels.autotune import Autotuner


def _time_us(fn, reps: int = 3) -> float:
    """Warm-up call (compile) + mean-of-reps, microseconds."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def _min_s(fn, reps: int = 3) -> float:
    """Warm-up + min-of-reps, seconds (the autotuner's timing discipline)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


# exp14 demo problems: small batch x full-width feature dim, where the
# default block (512) launches 2x the grid cells of the admissible maximum
# and the interpret path measures that directly (1.5-1.9x on this host)
DEMO_SHAPES = [
    ("rglru_scan", {"B": 1, "L": 64, "dr": 1024}),
    ("selective_scan", {"B": 1, "chunk": 32, "di": 1024, "N": 8}),
]


def kernel_rows(full: bool = False) -> list[tuple]:
    """One ``kernel_<name>`` row per registered kernel."""
    rows = []
    interpret = kreg.interpret_default()
    for name, kdef in kreg.KERNELS.items():
        shape = dict(kdef.full_shape if full else kdef.smoke_shape)
        args = kdef.make_args(shape, "float32", 0)
        t_ref = _time_us(lambda: kdef.ref(shape, args))
        err = kreg.max_abs_err(
            kdef.call(shape, args, kdef.defaults(shape, "float32"), interpret),
            kdef.ref(shape, args),
        )
        rows.append(
            (f"kernel_{name}", t_ref, f"allclose_err={err:.2e}_xla_us={t_ref:.1f}")
        )
    return rows


def exp14_row(reps: int = 3) -> tuple:
    """Tuned-vs-default on the demo shapes; best speedup + worst sweep cut."""
    tuner = Autotuner(timer="wall", reps=reps)
    interpret = kreg.interpret_default()
    best = None  # (speedup, kernel, tuned_s, default_s, result)
    min_cut = float("inf")
    for name, shape in DEMO_SHAPES:
        kdef = kreg.get_kernel(name)
        result = tuner.tune(name, shape, "float32")
        min_cut = min(min_cut, result.sweep_cut)
        args = kdef.make_args(shape, "float32", 0)
        default = kdef.defaults(shape, "float32")
        default_s = _min_s(lambda: kdef.call(shape, args, default, interpret), reps)
        tuned_s = _min_s(
            lambda: kdef.call(shape, args, result.config, interpret), reps
        )
        speedup = default_s / tuned_s if tuned_s > 0 else float("inf")
        print(
            f"  exp14 {name}: tuned {kreg.config_sig(result.config)} "
            f"{tuned_s:.4f}s vs default {kreg.config_sig(default)} "
            f"{default_s:.4f}s -> {speedup:.2f}x (cut {result.sweep_cut:.1f})"
        )
        if best is None or speedup > best[0]:
            best = (speedup, name, tuned_s, default_s)
    speedup, name, tuned_s, default_s = best
    derived = (
        f"tuned_speedup={speedup:.3f}_sweep_cut={min_cut:.1f}"
        f"_best_kernel={name}_tuned_s={tuned_s:.4f}_default_s={default_s:.4f}"
    )
    return ("exp14_kernels", tuned_s * 1e6, derived)


def main(full: bool = False) -> list[tuple]:
    rows = kernel_rows(full)
    rows.append(exp14_row())
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    main("--full" in sys.argv)
