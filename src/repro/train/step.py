"""Train / serve step builders: model + sharding strategy + optimizer -> jittable steps.

These are what the broker's compute manager compiles ("container images") and
what the multi-pod dry-run lowers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.model import Model
from repro.models.spec import is_spec_leaf, tree_sds
from repro.optim import adamw
from repro.parallel.sharding import (
    Strategy,
    activation_rules,
    dp_axes,
    param_pspec_tree,
    resolve_axes,
)


# ---------------------------------------------------------------------------
# Sharding bundles
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StepShardings:
    params: Any  # PartitionSpec tree
    opt: Any
    batch: Any
    cache: Optional[Any] = None


def batch_pspecs(batch_specs: dict, mesh: Mesh, strategy: Optional[Strategy] = None) -> dict:
    """tokens/labels (B, L) -> P(dp, None); stub embeddings (B, T, D) likewise.
    Respects the strategy's "batch" activation rule (serve_2dtp replicates)."""
    from repro.parallel.sharding import mesh_axis_sizes, resolve_axes as _resolve

    rules = {"batch": strategy.act_rules.get("batch", "__dp__") if strategy else "__dp__"}
    sizes = mesh_axis_sizes(mesh)

    def one(sds):
        axes = ("batch",) + (None,) * (len(sds.shape) - 1)
        return _resolve(axes, rules, mesh.axis_names, tuple(sds.shape), sizes)

    return jax.tree.map(one, batch_specs)


def act_pspec_tree(specs, strategy: Strategy, mesh: Mesh):
    """Cache/state spec tree -> PartitionSpecs via the *activation* rules."""
    from repro.parallel.sharding import mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    return jax.tree.map(
        lambda s: resolve_axes(s.axes, strategy.act_rules, mesh.axis_names, s.shape, sizes),
        specs,
        is_leaf=is_spec_leaf,
    )


def make_shardings(
    model: Model,
    strategy: Strategy,
    mesh: Mesh,
    batch_specs: dict,
    cache_specs=None,
) -> StepShardings:
    pspecs = param_pspec_tree(model.specs(), strategy, mesh)
    from repro.parallel.sharding import mesh_axis_sizes

    opt = adamw.opt_pspec_tree(
        model.specs(), pspecs, strategy.zero1, mesh_axis_sizes(mesh).get("data", 1)
    )
    batch = batch_pspecs(batch_specs, mesh, strategy)
    cache = act_pspec_tree(cache_specs, strategy, mesh) if cache_specs is not None else None
    return StepShardings(pspecs, opt, batch, cache)


def named(tree, mesh: Mesh):
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps), tree)


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def make_train_step(model: Model, strategy: Strategy, mesh: Mesh, opt_cfg: adamw.AdamWConfig):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        with activation_rules(strategy, mesh):
            (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch
            )
        params, opt_state, opt_metrics = adamw.apply_updates(
            opt_cfg, params, grads, opt_state
        )
        return params, opt_state, {**metrics, **opt_metrics}

    return train_step


def make_compressed_train_step(
    model: Model, strategy: Strategy, mesh: Mesh, opt_cfg: adamw.AdamWConfig
):
    """Train step with int8 error-feedback gradient reduction over the DP axes.

    shard_map over the dp axes (model axis left to GSPMD via auto) computes
    LOCAL gradients per DP shard, then the explicit compressed all-reduce
    replaces the implicit bf16/fp32 psum.  comp_state carries the error
    feedback between steps.
    """
    from repro.optim.compression import compressed_mean

    dp = dp_axes(mesh.axis_names)
    auto = frozenset(a for a in mesh.axis_names if a not in dp)
    pspecs = param_pspec_tree(model.specs(), strategy, mesh)

    def local_grads(params, batch):
        with activation_rules(strategy, mesh):
            (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch
            )
        return grads, metrics

    def train_step(params, opt_state, comp_state, batch):
        def shard_body(params, batch, comp_state):
            grads, metrics = local_grads(params, batch)
            out = jax.tree.map(
                lambda g, st: compressed_mean(g, st, dp),
                grads,
                comp_state,
                is_leaf=lambda x: isinstance(x, dict) and "worker_err" in x,
            )
            mean_grads = jax.tree.map(
                lambda x: x[0], out, is_leaf=lambda x: isinstance(x, tuple)
            )
            new_comp = jax.tree.map(
                lambda x: x[1], out, is_leaf=lambda x: isinstance(x, tuple)
            )
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp), metrics)
            return mean_grads, new_comp, metrics

        # params replicated over dp (their model-axis sharding is auto-handled)
        batch_specs = jax.tree.map(lambda _: P(dp if len(dp) > 1 else dp[0]), batch)
        rep = P()
        grads, comp_state, metrics = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, params), batch_specs, jax.tree.map(lambda _: rep, comp_state)),
            out_specs=(
                jax.tree.map(lambda _: rep, params),
                jax.tree.map(lambda _: rep, comp_state),
                jax.tree.map(lambda _: rep, metrics_struct(model)),
            ),
            check_vma=False,
        )(params, batch, comp_state)
        params, opt_state, opt_metrics = adamw.apply_updates(
            opt_cfg, params, grads, opt_state
        )
        return params, opt_state, comp_state, {**metrics, **opt_metrics}

    return train_step


def metrics_struct(model: Model):
    keys = ["ce", "tokens", "loss"]
    if model.cfg.family == "moe":
        keys += ["aux_loss", "z_loss"]
    return {k: 0.0 for k in keys}


def make_prefill_step(model: Model, strategy: Strategy, mesh: Mesh, cache_len: int):
    def prefill_step(params, batch):
        with activation_rules(strategy, mesh):
            return model.prefill(params, batch, cache_len=cache_len)

    return prefill_step


def make_decode_step(model: Model, strategy: Strategy, mesh: Mesh):
    def decode_step(params, cache, batch):
        with activation_rules(strategy, mesh):
            logits, cache = model.decode_step(params, cache, batch["tokens"], batch["pos"])
        return logits, cache

    return decode_step


# ---------------------------------------------------------------------------
# Abstract state (for dry-run and init)
# ---------------------------------------------------------------------------


def abstract_train_state(model: Model):
    params = model.abstract_params()
    opt = tree_sds(adamw.opt_state_specs(model.specs()))
    return params, opt


def init_train_state(model: Model, rng: jax.Array):
    params = model.init(rng)
    return params, adamw.init_state(params)
