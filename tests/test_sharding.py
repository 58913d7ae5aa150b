"""Sharding rule resolution: strategies, divisibility drops, spill targets."""
import jax
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import get_arch
from repro.models.model import Model
from repro.parallel import sharding as sh

SIZES = {"data": 16, "model": 16}
AXES = ("data", "model")


def test_tp_rules_basic():
    rules = sh.STRATEGIES["tp"].param_rules
    assert sh.resolve_axes(("embed", "mlp"), rules, AXES) == P(None, "model")
    assert sh.resolve_axes(("vocab", "embed"), rules, AXES) == P("model", None)


def test_duplicate_mesh_axis_dropped():
    rules = sh.STRATEGIES["tp"].param_rules
    # experts takes 'model'; mlp cannot reuse it
    ps = sh.resolve_axes(("experts", "embed", "mlp"), rules, AXES)
    assert ps == P("model", None, None)


def test_divisibility_drop_and_spill_to_embed():
    rules = sh.STRATEGIES["tp"].param_rules
    # 56 heads cannot shard 16 ways; spills onto embed (7168 divides)
    ps = sh.resolve_axes(("embed", "heads", None), rules, AXES, (7168, 56, 128), SIZES)
    assert ps == P("model", None, None)
    # divisible heads shard normally
    ps = sh.resolve_axes(("embed", "heads", None), rules, AXES, (4096, 32, 128), SIZES)
    assert ps == P(None, "model", None)


def test_cache_seq_spill():
    rules = sh.STRATEGIES["tp"].act_rules
    # 8 KV heads cannot shard 16 ways -> cache becomes sequence-sharded
    ps = sh.resolve_axes(
        ("layers", "batch", "cache_seq", "kv_heads_act", None),
        rules, AXES, (32, 128, 32768, 8, 128), SIZES,
    )
    assert ps == P(None, "data", "model", None, None)


def test_fsdp_tp_shards_embed_over_data():
    rules = sh.STRATEGIES["fsdp_tp"].param_rules
    ps = sh.resolve_axes(("embed", "mlp"), rules, AXES, (16384, 53248), SIZES)
    assert ps == P("data", "model")


def test_default_strategy_by_size():
    assert sh.default_strategy(get_arch("llama3-8b")).name == "tp"
    assert sh.default_strategy(get_arch("llama3-405b")).name == "fsdp_tp"
    grok = sh.default_strategy(get_arch("grok-1-314b"))
    assert grok.param_rules["experts"] is None  # 8 experts can't shard 16-way


def test_param_pspec_tree_covers_every_leaf():
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    for name in ("llama3-8b", "arctic-480b", "falcon-mamba-7b", "recurrentgemma-2b"):
        arch = get_arch(name)
        model = Model(arch)
        specs = model.specs()
        pspecs = sh.param_pspec_tree(specs, sh.default_strategy(arch), mesh)
        n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "axes")))
        n_ps = len(jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_ps


def test_shard_x_noop_outside_context():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert sh.shard_x(x, "batch", None) is x
