"""Logical-axis -> mesh-axis sharding rules (MaxText-style), per strategy.

A *strategy* maps logical parameter/activation axis names to mesh axes.  The
same model code serves every strategy; the compute manager picks (or the
hillclimb overrides) the strategy per architecture.

Mesh axes (production): single-pod ("data", "model") = (16, 16);
multi-pod ("pod", "data", "model") = (2, 16, 16).  "pod" is an outer
data-parallel axis crossing the inter-pod DCI links.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, replace
from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Parameter logical axes.
_TP_PARAM: dict[str, AxisVal] = {
    "layers": None,
    "embed": None,
    "embed_table": None,  # input embedding table's d_model dim
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "vocab": "model",
    "experts": "model",  # EP: experts over model axis (arctic)
    "expert_mlp": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "dt_rank": None,
    "conv": None,
    "rnn": "model",
    "norm": None,
    # when a param dim cannot shard (e.g. 56 heads or 8 KV heads on a 16-way
    # model axis), the dropped mesh axis spills onto the embed/mlp dim: the
    # matmul becomes row/column-parallel instead of replicating the weight
    "__spill__": ("embed", "mlp"),
}

# FSDP(+TP): additionally shard the replicated matrix dim over "data".
_FSDP_TP_PARAM = dict(_TP_PARAM, embed="data", embed_table="data")

# Pure FSDP (no tensor parallelism): everything big over ("data","model")
# treated as one flat fsdp axis - used as a hillclimb variant.
_FSDP_PARAM = dict(
    _TP_PARAM,
    mlp=("data", "model"),
    heads=("data", "model"),
    kv_heads=None,
    vocab=("data", "model"),
    experts=("data", "model"),
    ssm_inner=("data", "model"),
    rnn=("data", "model"),
    embed=None,
)

# Activation logical axes ("batch" resolves to the dp axes of the live mesh).
_ACT_BASE: dict[str, AxisVal] = {
    "batch": "__dp__",  # placeholder -> ("pod","data") or ("data",)
    "seq": None,
    "embed_act": None,
    "heads_act": "model",
    "kv_heads_act": "model",
    "mlp_act": "model",
    "vocab_act": "model",
    "experts_act": "model",
    "ssm_inner_act": "model",
    "rnn_act": "model",
    "group_act": "__dp__",
    "cache_batch": "__dp__",  # cache batch dim (decouples from token batch)
    "cache_seq": None,
    # when a dim cannot shard (e.g. 8 KV heads on a 16-way model axis), the
    # dropped mesh axis spills onto these dims instead: a KV cache becomes
    # sequence-sharded (distributed flash-decode layout)
    "__spill__": ("cache_seq",),
}

# Sequence-parallel variant: shard seq over "model" in norm/elementwise regions.
_ACT_SP = dict(_ACT_BASE, seq="model")


@dataclass(frozen=True)
class Strategy:
    """A named sharding strategy = param rules + activation rules + options."""

    name: str
    param_rules: dict[str, AxisVal]
    act_rules: dict[str, AxisVal]
    zero1: bool = True  # shard optimizer state over "data" (ZeRO-1)
    fsdp_pod: bool = False  # extend FSDP sharding over the "pod" axis too
    flash_decode: bool = False  # distributed flash-decode over seq-sharded caches

    def with_overrides(self, **param_overrides: AxisVal) -> "Strategy":
        return replace(self, param_rules={**self.param_rules, **param_overrides})


STRATEGIES: dict[str, Strategy] = {
    "tp": Strategy("tp", _TP_PARAM, _ACT_BASE),
    "fsdp_tp": Strategy("fsdp_tp", _FSDP_TP_PARAM, _ACT_BASE),
    "fsdp": Strategy("fsdp", _FSDP_PARAM, _ACT_BASE),
    "tp_sp": Strategy("tp_sp", _TP_PARAM, _ACT_SP),
    "fsdp_tp_sp": Strategy("fsdp_tp_sp", _FSDP_TP_PARAM, _ACT_SP),
    # §Perf serving strategy: params 2D-sharded (data x model) like fsdp_tp,
    # but token activations REPLICATED over the data axis, so GSPMD computes
    # partial matmuls + activation all-reduces (2D tensor parallelism) instead
    # of all-gathering the weights every layer (FSDP) - the right trade for
    # decode, where weights >> activations.  Caches stay batch-sharded via
    # the separate cache_batch axis.
    "serve_2dtp": Strategy(
        "serve_2dtp",
        # embed table stays 1D (vocab-only) sharded: a 2D-sharded table makes
        # GSPMD all-gather it for every lookup (measured: +4.2GB/step)
        dict(_FSDP_TP_PARAM, embed_table=None),
        dict(_ACT_BASE, batch=None),
        zero1=False,
    ),
}


def default_strategy(arch) -> Strategy:
    """Per-arch default strategy (baseline; §Perf hillclimbs override)."""
    big = arch.param_count() > 100e9
    strat = STRATEGIES["fsdp_tp" if big else "tp"]
    if arch.family == "moe" and arch.n_experts and arch.n_experts < 16:
        # grok: 8 experts cannot shard over 16-way model axis -> expert-internal TP
        strat = strat.with_overrides(experts=None, expert_mlp="model")
    return strat


# ---------------------------------------------------------------------------
# Resolution: logical axes -> PartitionSpec
# ---------------------------------------------------------------------------


def dp_axes(mesh_axis_names) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh_axis_names)


def resolve_axes(
    logical_axes: tuple[Optional[str], ...],
    rules: dict[str, AxisVal],
    mesh_axis_names,
    shape: Optional[tuple[int, ...]] = None,
    axis_sizes: Optional[dict[str, int]] = None,
) -> P:
    """Map logical axis names to a PartitionSpec for the live mesh.

    When ``shape``/``axis_sizes`` are given, a mesh axis that does not divide
    its dim is dropped (dim replicated) and, if the rules declare
    ``__spill__`` targets, re-assigned to the first eligible spill dim.
    """
    used: set[str] = set()
    dropped: list[str] = []
    out: list[Optional[tuple[str, ...]]] = []

    def divides(dim: int, axes: tuple[str, ...]) -> bool:
        if axis_sizes is None:
            return True
        n = 1
        for a in axes:
            n *= axis_sizes.get(a, 1)
        return n > 0 and dim % n == 0

    for i, name in enumerate(logical_axes):
        val: AxisVal = None if name is None else rules.get(name, None)
        if val == "__dp__":
            val = dp_axes(mesh_axis_names)
        if isinstance(val, str):
            val = (val,)
        if val is not None:
            val = tuple(a for a in val if a in mesh_axis_names and a not in used)
            if shape is not None and val:
                keep: list[str] = []
                for a in val:
                    if divides(shape[i], tuple(keep) + (a,)):
                        keep.append(a)
                    else:
                        dropped.append(a)
                val = tuple(keep)
            used.update(val)
            val = val if val else None
        out.append(val)

    # spill dropped mesh axes onto eligible dims (e.g. cache seq dim)
    spill_names = rules.get("__spill__", ()) or ()
    for a in dropped:
        for i, name in enumerate(logical_axes):
            if name not in spill_names:
                continue
            cur = out[i] or ()
            if a in used:
                break
            if shape is not None and not divides(shape[i], cur + (a,)):
                continue
            out[i] = cur + (a,)
            used.add(a)
            break

    final = [v[0] if (v is not None and len(v) == 1) else v for v in out]
    return P(*final)


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def param_pspec_tree(specs, strategy: Strategy, mesh: Mesh):
    """Spec tree -> PartitionSpec tree under the given strategy."""
    from repro.models.spec import ParamSpec, is_spec_leaf

    rules = dict(strategy.param_rules)
    if strategy.fsdp_pod and "pod" in mesh.axis_names:
        # extend the fsdp ("data") shards over ("pod","data")
        rules = {
            k: (("pod", "data") if v == "data" else v) for k, v in rules.items()
        }
    sizes = mesh_axis_sizes(mesh)
    return jax.tree.map(
        lambda s: resolve_axes(s.axes, rules, mesh.axis_names, s.shape, sizes),
        specs,
        is_leaf=is_spec_leaf,
    )


def param_sharding_tree(specs, strategy: Strategy, mesh: Mesh):
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps),
        param_pspec_tree(specs, strategy, mesh),
    )


# ---------------------------------------------------------------------------
# Activation sharding context (used by model code via shard_x)
# ---------------------------------------------------------------------------


class _Ctx(threading.local):
    """Per thread: broker executor threads trace steps for different
    devices' meshes at the same time."""

    rules: Optional[dict[str, AxisVal]] = None
    mesh: Optional[Mesh] = None
    flash_decode: bool = False


_CTX = _Ctx()


class activation_rules:
    """Context manager installing activation rules for model-internal
    ``with_sharding_constraint`` calls.  No-op when not installed."""

    def __init__(self, strategy: Strategy, mesh: Mesh):
        self.rules = strategy.act_rules
        self.mesh = mesh
        self.flash_decode = strategy.flash_decode

    def __enter__(self):
        _CTX.rules, _CTX.mesh = self.rules, self.mesh
        _CTX.flash_decode = self.flash_decode
        return self

    def __exit__(self, *exc):
        _CTX.rules, _CTX.mesh, _CTX.flash_decode = None, None, False
        return False


def flash_decode_enabled() -> bool:
    return (
        _CTX.flash_decode
        and _CTX.mesh is not None
        and "model" in _CTX.mesh.axis_names
    )


def shard_x(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain an activation to the current rules (no-op outside context).

    No divisibility check here: GSPMD pads uneven *intermediate* shardings
    (e.g. 56 heads over 16 shards); only jit-boundary shardings must divide.
    """
    if _CTX.rules is None or _CTX.mesh is None:
        return x
    spec = resolve_axes(tuple(logical_axes), _CTX.rules, _CTX.mesh.axis_names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))
