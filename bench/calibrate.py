"""Readings that the limits of ``correct`` are set from (``oracle.py``).

    python bench/calibrate.py --workload <cell> --seeds 1,2,...,12 --seconds 6 \\
        [--control-seeds 21,22,23] [--out calibrate.json]

In one process, so that set-up is paid once: the program's readings, one
short window at the cell's own rate for each seed (the same path and sizes
as a run), then the control's: the reference with every floating operand
rounded to the next precision below the payload's, on the operands a run
with that seed uses, read by the same gap, and beside it each of the
kernel's planted ``FAULTS``: the reference with the fault, in the program's
place.  A limit lies above the largest program reading and below the
smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import generator, oracle, run, spec  # noqa: E402

# faults a kernel can have, planted in the reference put in the program's
# place: (shape, operands) -> (shape, operands) the faulty reference runs on
FAULTS = {
    "flash_attention": {
        "no_causal_mask": lambda shape, args: (dict(shape, causal=False), args),
        "softmax_scale_1": lambda shape, args: (shape, (args[0] * shape["hd"] ** 0.5, *args[1:])),
        "softmax_scale_1_over_hd": lambda shape, args: (shape, (args[0] / shape["hd"] ** 0.5, *args[1:])),
    },
}


def faulty(kernel: str, fault: str, shape: dict, args: tuple):
    """The reference's output in blocks, with ``fault`` planted."""
    shape, args = FAULTS[kernel][fault](shape, tuple(args))
    return spec.plugin("reference", kernel).outputs(shape, args)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    devices = run.accelerator(cell["chips"])
    result = {"program": {}, "control": {}}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        out, _ = run.run_cell(bench, cell, config, traffic, seed, args.seconds, False, devices,
                              log=lambda s: print(s, file=sys.stderr, flush=True))
        result["program"][seed] = {k: c["value"] for k, c in out["checks"].items()}
        print(json.dumps({"seed": seed, "correct": out["correct"], "checks": result["program"][seed]}), flush=True)
    device = devices[config["providers"][0]["device_offset"]]
    used = sorted(k for k, w in traffic["weights"].items() if w > 0)
    cat = {p["name"]: p for p in config["payloads"]}
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        pairs = [(p, s) for p in used for s in generator.operand_seeds(seed, traffic["operand_seeds"])]
        refs = oracle.references(config, pairs, device)
        low = oracle.references(config, pairs, device, lower=True)
        gaps: dict = {}
        for pair in pairs:
            g = oracle.gap(low[pair][0], refs[pair])
            gaps[f"gap.{pair[0]}"] = max(gaps.get(f"gap.{pair[0]}", 0.0), g)
            p = cat[pair[0]]
            for fault in FAULTS.get(p["kernel"], {}):
                args_ = oracle._operands(p["kernel"], p["shape"], p["dtype"], pair[1], device)
                g = oracle.gap(oracle.summary(faulty(p["kernel"], fault, p["shape"], args_))[0], refs[pair])
                # the least a fault reads: the seed on which it is hardest to see
                name = f"fault.{fault}.{pair[0]}"
                gaps[name] = min(gaps.get(name, g), g)
        result["control"][seed] = gaps
        print(json.dumps({"control_seed": seed, "checks": gaps}), flush=True)
    summary = {}
    for name in sorted({k for r in result["program"].values() for k in r if k.startswith("gap.")}):
        prog = [r[name] for r in result["program"].values() if name in r]
        ctrl = [r[name] for r in result["control"].values() if name in r]
        summary[name] = {"program_max": max(prog), "control_min": min(ctrl) if ctrl else None,
                         "separation": (min(ctrl) / max(prog)) if ctrl and max(prog) > 0 else None}
    for name in sorted({k for r in result["control"].values() for k in r if k.startswith("fault.")}):
        summary[name] = {"fault_min": min(r[name] for r in result["control"].values() if name in r)}
    result["summary"] = summary
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))


if __name__ == "__main__":
    main()
