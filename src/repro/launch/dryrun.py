import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without real hardware:
``jax.jit(step).lower(**abstract_inputs).compile()`` must succeed on the
single-pod (16x16) and multi-pod (2x16x16) production meshes for every
assigned architecture and input shape.  Outputs per cell:

  * compiled.memory_analysis()  - proves the state fits per device,
  * compiled.cost_analysis()    - HLO FLOPs / bytes for §Roofline,
  * parsed collective bytes     - §Roofline collective term,
  * a JSON artifact under artifacts/dryrun/ consumed by the roofline report.

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
      PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch, get_shape, token_batch_spec, ARCHS, SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim import adamw
from repro.parallel.sharding import STRATEGIES, default_strategy, mesh_axis_sizes, resolve_axes
from repro.roofline.hlo import parse_collectives, parse_hbm_traffic
from repro.roofline.model import Roofline, model_flops
from repro.train import step as step_lib

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def build_cell(arch, shape_name: str, mesh, strategy_name: Optional[str] = None):
    """Returns (jitted_fn, abstract_args: tuple, meta) ready to .lower().

    ``arch`` is an ArchConfig (possibly a reduced-depth cost variant).
    """
    shape = get_shape(shape_name)
    if not arch.supports(shape):
        raise ValueError(f"{arch.name} skips {shape_name} (sub-quadratic only)")
    model = Model(arch)
    strategy = STRATEGIES[strategy_name] if strategy_name else default_strategy(arch)
    if arch.family == "moe" and arch.n_experts < 16:
        strategy = strategy.with_overrides(experts=None)

    batch_specs = token_batch_spec(arch, shape)
    named = lambda tree: jax.tree.map(lambda ps: NamedSharding(mesh, ps), tree)

    if shape.kind == "train":
        shardings = step_lib.make_shardings(model, strategy, mesh, batch_specs)
        opt_cfg = adamw.AdamWConfig()
        fn = step_lib.make_train_step(model, strategy, mesh, opt_cfg)
        params, opt = step_lib.abstract_train_state(model)
        metrics_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), step_lib.metrics_struct(model)
        )
        metrics_sh["grad_norm"] = NamedSharding(mesh, P())
        metrics_sh["lr"] = NamedSharding(mesh, P())
        jfn = jax.jit(
            fn,
            in_shardings=(named(shardings.params), named(shardings.opt), named(shardings.batch)),
            out_shardings=(named(shardings.params), named(shardings.opt), metrics_sh),
            donate_argnums=(0, 1),
        )
        args = (params, opt, batch_specs)
    elif shape.kind == "prefill":
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        shardings = step_lib.make_shardings(model, strategy, mesh, batch_specs, cache_specs)
        fn = step_lib.make_prefill_step(model, strategy, mesh, cache_len=shape.seq_len)
        params = model.abstract_params()
        logits_ps = resolve_axes(
            ("batch", None, "vocab_act"), strategy.act_rules, mesh.axis_names,
            (shape.global_batch, 1, arch.vocab_size), mesh_axis_sizes(mesh))
        jfn = jax.jit(
            fn,
            in_shardings=(named(shardings.params), named(shardings.batch)),
            out_shardings=(
                NamedSharding(mesh, logits_ps),
                named(shardings.cache),
            ),
        )
        args = (params, batch_specs)
    elif shape.kind == "decode":
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        shardings = step_lib.make_shardings(model, strategy, mesh, batch_specs, cache_specs)
        fn = step_lib.make_decode_step(model, strategy, mesh)
        params = model.abstract_params()
        cache = model.abstract_cache(shape.global_batch, shape.seq_len)
        logits_ps = resolve_axes(
            ("batch", None, "vocab_act"), strategy.act_rules, mesh.axis_names,
            (shape.global_batch, 1, arch.vocab_size), mesh_axis_sizes(mesh))
        jfn = jax.jit(
            fn,
            in_shardings=(named(shardings.params), named(shardings.cache), named(shardings.batch)),
            out_shardings=(NamedSharding(mesh, logits_ps), named(shardings.cache)),
            donate_argnums=(1,),
        )
        args = (params, cache, batch_specs)
    else:
        raise ValueError(shape.kind)
    meta = {
        "arch": arch.name,
        "shape": shape_name,
        "strategy": strategy.name,
        "kind": shape.kind,
        "n_chips": mesh.size,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
    }
    return jfn, args, meta


def depth_unit(arch) -> tuple[int, float]:
    """(layers per depth-unit, number of depth-units in the full model)."""
    if arch.family == "hybrid":
        p = len(arch.block_pattern or ("rec", "rec", "attn"))
        return p, arch.n_layers / p
    if arch.family == "vlm":
        p = arch.cross_attn_period
        return p, arch.n_layers / p
    return 1, float(arch.n_layers)


def depth_variant(arch, units: int):
    p, _ = depth_unit(arch)
    kw = {"n_layers": units * p}
    if arch.family == "audio":
        kw["n_enc_layers"] = units  # enc and dec depths extrapolate together
    return arch.replace(**kw)


def measure_costs(arch, shape_name: str, mesh, strategy_name, units: int) -> dict:
    """Lower a reduced-depth, fully-unrolled variant and read exact costs
    (no while loops -> cost_analysis and HLO collectives are exact)."""
    from repro.models.layers import unroll_all_scans

    variant = depth_variant(arch, units)
    with unroll_all_scans():
        jfn, args, _ = build_cell(variant, shape_name, mesh, strategy_name)
        lowered = jfn.lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll = parse_collectives(text)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "hbm": float(parse_hbm_traffic(text)),
        "coll": float(coll.total_bytes),
    }


def extrapolate_costs(arch, shape_name: str, mesh, strategy_name) -> dict:
    """True per-step cost = alpha + units_full * beta, solved from exact
    unrolled measurements at depth-units 1 and 2 (see layers.unroll_all_scans)."""
    m1 = measure_costs(arch, shape_name, mesh, strategy_name, 1)
    m2 = measure_costs(arch, shape_name, mesh, strategy_name, 2)
    _, units_full = depth_unit(arch)
    out = {}
    for k in ("flops", "bytes", "hbm", "coll"):
        beta = m2[k] - m1[k]
        alpha = max(m1[k] - beta, 0.0)
        out[k] = alpha + units_full * beta
        out[f"{k}_per_layer_unit"] = beta
        out[f"{k}_outside_layers"] = alpha
    return out


def _mem_fields(mem) -> dict:
    out = {}
    for f in (
        "temp_size_in_bytes",
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(mem, f, None)
        if v is not None:
            out[f] = int(v)
    return out


def run_cell(
    arch_name: str,
    shape_name: str,
    multi_pod: bool = False,
    strategy_name: Optional[str] = None,
    save: bool = True,
    verbose: bool = True,
    extrapolate: bool = True,
    arch_overrides: Optional[dict] = None,
    label: Optional[str] = None,
) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = get_arch(arch_name)
    if arch_overrides:
        arch = arch.replace(**arch_overrides)
    jfn, args, meta = build_cell(arch, shape_name, mesh, strategy_name)
    t0 = time.perf_counter()
    lowered = jfn.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())

    shape = get_shape(shape_name)
    if extrapolate:
        ext = extrapolate_costs(arch, shape_name, mesh, strategy_name)
        flops, byts, collb = ext["flops"], ext["bytes"], ext["coll"]
        hbm = ext["hbm"]
    else:
        ext = None
        flops = float(cost.get("flops", 0.0))
        byts = float(cost.get("bytes accessed", 0.0))
        collb = float(coll.total_bytes)
        hbm = float(parse_hbm_traffic(compiled.as_text()))
    rl = Roofline(
        arch=arch_name,
        shape=shape_name,
        mesh=meta["mesh"],
        n_chips=meta["n_chips"],
        flops_per_chip=flops,
        bytes_per_chip=byts,
        collective_bytes_per_chip=collb,
        model_flops_total=model_flops(arch, shape),
        hbm_bytes_est_per_chip=hbm,
    )
    record = {
        **meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": _mem_fields(mem),
        "raw_cost_flops_per_chip": float(cost.get("flops", 0.0)),
        "raw_cost_bytes_per_chip": float(cost.get("bytes accessed", 0.0)),
        "raw_collectives": coll.row(),
        "extrapolated": ext,
        "flops_per_chip": flops,
        "bytes_per_chip": byts,
        "collective_bytes_per_chip": collb,
        "roofline": rl.row(),
    }
    if verbose:
        print(f"== {arch_name} x {shape_name} on {meta['mesh']} ({meta['strategy']}) ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  cost (extrapolated over scan trip counts): flops={flops:.3e} bytes={byts:.3e} coll={collb:.3e}")
        print(f"  raw collectives (loop bodies once): {coll.row()}")
        print(f"  roofline: {rl.row()}")
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        sname = label or strategy_name or "default"
        path = os.path.join(
            ARTIFACT_DIR, f"{arch_name}__{shape_name}__{meta['mesh']}__{sname}.json"
        )
        with open(path, "w") as f:
            json.dump(record, f, indent=2)
    return record


def kernel_report(save: bool = True, verbose: bool = True) -> list[dict]:
    """Roofline-predicted Pallas kernel configs (kernels/autotune.py
    ``predict_best``): for every registered kernel at its smoke and full
    bench shapes, the config the pruned model sweep picks, its predicted
    arithmetic intensity, and the sweep accounting.  Pure model — no
    execution, no compilation — so the rows sit next to the HLO-derived
    roofline cells and predicted-vs-measured drift is visible in one place
    (benchmarks/roofline_report.py reads the saved artifact)."""
    from repro.kernels import registry as kreg
    from repro.kernels.autotune import predict_best

    rows = []
    for name, kdef in kreg.KERNELS.items():
        for tier in ("smoke", "full"):
            shape = dict(getattr(kdef, f"{tier}_shape"))
            rows.append({"tier": tier, **predict_best(name, shape)})
            if verbose:
                r = rows[-1]
                print(
                    f"  {name:18s} {tier:5s} config={r['config']:28s} "
                    f"intensity={r['intensity_flops_per_byte']:9.3f} "
                    f"swept {r['swept']}/{r['exhaustive']}"
                )
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(ARTIFACT_DIR, "kernels__predicted.json")
        with open(path, "w") as f:
            json.dump({"kind": "kernel_predictions", "rows": rows}, f, indent=2)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--all", action="store_true", help="every supported (arch x shape) cell")
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            if get_arch(a).supports(get_shape(s)):
                cells.append((a, s))
            else:
                print(f"SKIP {a} x {s} (sub-quadratic only; see DESIGN.md)")

    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    failures = []
    for a, s in cells:
        for mp in pods:
            try:
                run_cell(a, s, multi_pod=mp, strategy_name=args.strategy,
                         extrapolate=not mp)
            except Exception as e:
                failures.append((a, s, mp, repr(e)))
                traceback.print_exc()
    print("\n== Pallas kernel predicted configs (roofline model, no execution) ==")
    kernel_report()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print(f"\nall {len(cells) * len(pods)} cells compiled OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
