"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary rows (plus per-experiment
CSV files under artifacts/bench/).  ``--full`` uses the paper's task counts;
``--smoke`` runs a CI-sized subset (tiny task counts, virtual-clock elastic
run) and writes the summary to ``artifacts/bench/BENCH_smoke.json`` so every
PR captures its perf trajectory as a workflow artifact.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _summary(name: str, rows: list[dict], key: str = "th_tasks_per_s") -> str:
    if not rows:
        return f"{name},0,empty"
    vals = [r[key] for r in rows if key in r]
    n_tasks = sum(r.get("n_tasks", 0) for r in rows)
    ovh = [r["ovh_s"] for r in rows if "ovh_s" in r]
    us_per_task = (sum(ovh) / max(n_tasks, 1)) * 1e6 if ovh else 0.0
    derived = f"mean_{key}={sum(vals)/len(vals):.1f}" if vals else "n/a"
    return f"{name},{us_per_task:.2f},{derived}"


def _exp6_summary(rows: list[dict]) -> str:
    streaming_rows = [r for r in rows if r["mode"] == "streaming"]
    mean_pod_ratio = sum(r["pod_ratio"] for r in streaming_rows) / max(len(streaming_rows), 1)
    return (
        f"exp6_streaming,{sum(r['n_submits'] for r in streaming_rows)},"
        f"mean_pod_ratio={mean_pod_ratio:.2f}"
    )


def _exp8_summary(rows: list[dict]) -> str:
    aware = next(r for r in rows if r["mode"] == "aware")
    return (
        f"exp8_staging,{aware['mb_moved']},"
        f"bytes_reduction={aware['bytes_reduction']:.3f}"
        f"_makespan_speedup={aware['makespan_speedup']:.3f}"
    )


def _exp9_summary(rows: list[dict]) -> str:
    scaling = [r for r in rows if r["mode"] == "scaling"]
    data = next(r for r in rows if r["mode"] == "data")
    flat = scaling[-1]["us_per_task"] / max(scaling[0]["us_per_task"], 1e-9)
    return (
        f"exp9_sched,{scaling[-1]['us_per_task']},"
        f"dispatch_tasks_per_s={data['dispatch_tasks_per_s']:.0f}"
        f"_cost_flat_ratio={flat:.2f}"
    )


def _exp10_summary(rows: list[dict]) -> str:
    r = rows[0]
    return (
        f"exp10_scenario,{r['n_tasks']},"
        f"makespan_inflation={r['makespan_inflation']:.4f}"
        f"_recovery_s={r['recovery_s']:.1f}"
        f"_failed={r['failed']}"
        f"_violations={r['violations']}"
    )


def _exp11_summary(rows: list[dict]) -> str:
    flooded = next(r for r in rows if r["mode"] == "flooded")
    return (
        f"exp11_tenants,{flooded['n_flood']},"
        f"interactive_p99_ratio={flooded['interactive_p99_ratio']:.3f}"
        f"_flooded_p99_s={flooded['p99_s']:.3f}"
        f"_rejections={flooded['rejections']}"
    )


def _exp12_summary(rows: list[dict]) -> str:
    emit = next(r for r in rows if r["mode"] == "emit")
    replay = next(r for r in rows if r["mode"] == "replay")
    disp = next(r for r in rows if r["mode"] == "dispatch")
    delta = disp.get("delta_vs_baseline")
    delta_s = f"{delta:+.3f}" if delta is not None else "n/a"
    return (
        f"exp12_events,{emit['us_per_event']},"
        f"emit_events_per_s={emit['events_per_s']:.0f}"
        f"_replay_events_per_s={replay['events_per_s']:.0f}"
        f"_dispatch_delta={delta_s}"
    )


def _exp13_summary(rows: list[dict]) -> str:
    spot = next(r for r in rows if r["mode"] == "spot_mix")
    storm = next(r for r in rows if r["mode"] == "storm")
    return (
        f"exp13_market,{spot['n_tasks']},"
        f"cost_ratio={spot['cost_ratio']:.4f}"
        f"_failed={storm['failed']}"
        f"_reexec_frac={storm['reexec_frac']:.4f}"
        f"_slo_violations={spot['slo_violations'] + storm['slo_violations']}"
    )


def _exp7_summary(rows: list[dict]) -> str:
    weak = [r for r in rows if r["mode"] == "weak"]
    elastic = [r for r in rows if r["mode"] == "elastic"]
    scaled = all(r["scaled_to_demand"] for r in weak) if weak else False
    cost = elastic[0]["cost_vs_max_static"] if elastic else 1.0
    return f"exp7_elastic,{len(weak)},scaled_to_demand={scaled}_cost_vs_static={cost:.3f}"


def _write_bench_json(tag: str, out: list[str]) -> str:
    """BENCH_<tag>.json: the per-PR perf-trajectory artifact CI uploads."""
    from benchmarks.common import RESULT_DIR

    os.makedirs(RESULT_DIR, exist_ok=True)
    path = os.path.join(RESULT_DIR, f"BENCH_{tag}.json")
    rows = []
    for line in out:
        name, us, derived = line.split(",", 2)
        rows.append({"name": name, "us_per_call": float(us), "derived": derived})
    with open(path, "w") as f:
        json.dump(
            {"tag": tag, "unix_time": time.time(), "rows": rows},
            f,
            indent=2,
        )
    return path


def run_smoke() -> list[str]:
    """CI-sized: broker-core experiments at tiny counts (elastic run on a
    virtual clock) plus the kernel lane — per-kernel XLA parity rows and
    the exp14 autotuner arm at smoke shapes."""
    out = []

    from benchmarks import (
        exp1_per_provider,
        exp4_facts,
        exp6_streaming,
        exp7_elastic,
        exp8_staging,
        exp9_sched,
        exp10_scenario,
        exp11_tenants,
        exp12_events,
        exp13_market,
        kernels_bench,
    )

    print("== Exp 1 (smoke): per-provider scaling ==")
    out.append(_summary("exp1_per_provider", exp1_per_provider.main(False)))

    print("== Exp 4 (smoke): FACTS workflows ==")
    r4 = exp4_facts.main(smoke=True)
    ovh_fracs = [r["ovh_frac"] for r in r4]
    out.append(
        f"exp4_facts,{sum(r['ttx_s'] for r in r4)/len(r4)*1e6:.0f},"
        f"mean_ovh_frac={sum(ovh_fracs)/len(ovh_fracs):.4f}"
    )

    print("== Exp 6 (smoke): streaming vs frontier ==")
    out.append(_exp6_summary(exp6_streaming.main(False)))

    print("== Exp 7 (smoke): elastic acquisition ==")
    out.append(_exp7_summary(exp7_elastic.main(smoke=True)))

    print("== Exp 8 (smoke): data-aware staging ==")
    out.append(_exp8_summary(exp8_staging.main(smoke=True)))

    print("== Exp 9 (smoke): scheduler-core dispatch throughput ==")
    out.append(_exp9_summary(exp9_sched.main(smoke=True)))

    print("== Exp 10 (smoke): chaos scenario (searise-smoke, chaos + twin) ==")
    out.append(_exp10_summary(exp10_scenario.main(smoke=True)))

    print("== Exp 11 (smoke): multi-tenant front door (10k flood) ==")
    out.append(_exp11_summary(exp11_tenants.main(smoke=True)))

    print("== Exp 12 (smoke): event-bus overhead (emit/replay/dispatch tax) ==")
    out.append(_exp12_summary(exp12_events.main(smoke=True)))

    print("== Exp 13 (smoke): market scheduler (spot mix + preemption storm) ==")
    out.append(_exp13_summary(exp13_market.main(smoke=True)))

    print("== Exp 14 (smoke): Pallas kernels (XLA parity + autotuner demo) ==")
    for name, us, derived in kernels_bench.main(False):
        out.append(f"{name},{us:.1f},{derived}")

    path = _write_bench_json("smoke", out)
    print(f"\nwrote {path}")
    return out


def run_all(full: bool) -> list[str]:
    out = []

    from benchmarks import exp1_per_provider, exp2_cross_provider, exp3a_cross_platform
    from benchmarks import exp3b_heterogeneous, exp4_facts, exp5_groups, exp6_streaming
    from benchmarks import exp7_elastic, exp8_staging, exp9_sched, exp10_scenario
    from benchmarks import exp11_tenants, exp12_events, exp13_market
    from benchmarks import kernels_bench, roofline_report

    print("== Exp 1: per-provider scaling (OVH/TH/TPT, MCPP vs SCPP) ==")
    r1 = exp1_per_provider.main(full)
    out.append(_summary("exp1_per_provider", r1))

    print("== Exp 2: cross-provider aggregation ==")
    r2 = exp2_cross_provider.main(full)
    out.append(_summary("exp2_cross_provider", r2))

    print("== Exp 3A: cloud + HPC homogeneous ==")
    r3a = exp3a_cross_platform.main(full)
    out.append(_summary("exp3a_cross_platform", r3a))

    print("== Exp 3B: heterogeneous tasks/nodes ==")
    r3b = exp3b_heterogeneous.main(full)
    out.append(_summary("exp3b_heterogeneous", r3b))

    print("== Exp 4: FACTS workflows ==")
    r4 = exp4_facts.main(full)
    ovh_fracs = [r["ovh_frac"] for r in r4]
    out.append(
        f"exp4_facts,{sum(r['ttx_s'] for r in r4)/len(r4)*1e6:.0f},"
        f"mean_ovh_frac={sum(ovh_fracs)/len(ovh_fracs):.4f}"
    )

    print("== Exp 5: provider groups (balanced TPT + failover OVH) ==")
    r5 = exp5_groups.main(full)
    out.append(_summary("exp5_groups", r5))

    print("== Exp 6: streaming vs frontier DAG dispatch ==")
    out.append(_exp6_summary(exp6_streaming.main(full)))

    print("== Exp 7: elastic acquisition (weak scaling + cost curve) ==")
    out.append(_exp7_summary(exp7_elastic.main(full)))

    print("== Exp 8: data-aware staging (locality-aware vs blind placement) ==")
    out.append(_exp8_summary(exp8_staging.main(full)))

    print("== Exp 9: scheduler-core dispatch throughput (ledger + heaps) ==")
    out.append(_exp9_summary(exp9_sched.main(full)))

    print("== Exp 10: chaos scenario (searise, chaos + no-chaos twin) ==")
    out.append(_exp10_summary(exp10_scenario.main(full)))

    print("== Exp 11: multi-tenant front door (interactive p99 under flood) ==")
    out.append(_exp11_summary(exp11_tenants.main(full)))

    print("== Exp 12: event-bus overhead (emit/replay/dispatch tax) ==")
    out.append(_exp12_summary(exp12_events.main(full)))

    print("== Exp 13: market scheduler (spot mix + preemption storm) ==")
    out.append(_exp13_summary(exp13_market.main(full)))

    print("== Kernel micro-benchmarks ==")
    for name, us, derived in kernels_bench.main(full):
        out.append(f"{name},{us:.1f},{derived}")

    print("== Roofline table (from dry-run artifacts) ==")
    rl = roofline_report.main(full)
    if rl:
        mean_mfu = sum(r["mfu_est"] for r in rl) / len(rl)
        out.append(f"roofline_cells,{len(rl)},mean_mfu_est={mean_mfu:.4f}")

    _write_bench_json("full" if full else "default", out)
    return out


def main() -> None:
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    if "--smoke" in sys.argv:
        out = run_smoke()
    else:
        out = run_all("--full" in sys.argv)
    print("\nname,us_per_call,derived")
    for line in out:
        print(line)


if __name__ == "__main__":
    main()
