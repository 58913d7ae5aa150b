"""Roofline autotuner (kernels/autotune.py): pruning, caching, determinism,
and how a tuned config reaches a kernel (registry.block_config).

The determinism contract is the load-bearing one: under ``timer="model"``
the whole tune is a pure function of (kernel, shape, dtype, seed), the
cached dataset payload is canonical JSON of the *choice* (never timings),
and identically-seeded runs must produce byte-identical payloads — that is
what lets tuned configs replicate through staging like any other dataset."""
from __future__ import annotations

import pytest

from repro.core.events import EventBus
from repro.core.staging import SHARED_SITE, DatasetRegistry
from repro.kernels import registry as kreg
from repro.kernels.autotune import Autotuner

# the exp14 demo problem: small batch x full-width feature dim, where the
# pruner collapses the frontier to the single largest admissible block
DEMO = ("rglru_scan", {"B": 1, "L": 64, "dr": 1024})


def _model_tuner(**kw) -> Autotuner:
    return Autotuner(timer="model", **kw)


# ---------------------------------------------------------------------------
# pruning
# ---------------------------------------------------------------------------


def test_prune_survivors_are_a_real_cut_of_the_space():
    tuner = _model_tuner()
    for name, kdef in kreg.KERNELS.items():
        shape = dict(kdef.smoke_shape)
        survivors, exhaustive = tuner.prune(name, shape, "float32")
        space_sigs = {kreg.config_sig(c) for c in kdef.space(shape)}
        assert exhaustive == len(space_sigs)
        assert 1 <= len(survivors) <= exhaustive
        assert {kreg.config_sig(c) for c in survivors} <= space_sigs


def test_prune_cuts_demo_sweep_at_least_2x_and_tune_picks_full_width():
    """The check_bench HARD floor (sweep_cut >= 2) must hold structurally,
    not just on one lucky run: rglru traffic is config-independent, so the
    Pareto frontier is exactly the largest admissible block."""
    name, shape = DEMO
    tuner = _model_tuner()
    result = tuner.tune(name, shape)
    assert result.sweep_cut >= 2.0
    assert result.exhaustive == result.swept + result.pruned
    assert result.config == {"block_d": 1024}
    assert kreg.config_sig(result.config) in result.timings


def test_vmem_budget_filters_and_degenerate_budget_falls_back_to_defaults():
    name, shape = DEMO
    kdef = kreg.get_kernel(name)
    # a budget no candidate fits: prune must yield the committed defaults
    # rather than an empty sweep, and tune must still return a usable config
    tiny = _model_tuner(vmem_budget=1)
    survivors, exhaustive = tiny.prune(name, shape)
    assert survivors == [kdef.defaults(shape, "float32")]
    assert exhaustive == len(kdef.space(shape))
    assert tiny.tune(name, shape).config == kdef.defaults(shape, "float32")
    # a budget that only admits the smallest block: the winner shrinks
    smallest = kdef.cost(shape, {"block_d": 32}, "float32").vmem_bytes
    capped = _model_tuner(vmem_budget=int(smallest))
    assert capped.tune(name, shape).config == {"block_d": 32}


# ---------------------------------------------------------------------------
# cache + events
# ---------------------------------------------------------------------------


def test_cache_hit_skips_retiming_and_emits_no_second_tune_event():
    name, shape = DEMO
    bus = EventBus(strict=False)
    tuner = _model_tuner(events=bus)
    first = tuner.tune(name, shape)
    second = tuner.tune(name, shape)
    assert not first.cached and second.cached
    assert second.config == first.config
    tune_events = [e for e in bus.events() if e.name == "kernel.tune"]
    assert len(tune_events) == 1  # the hit re-timed nothing, so no event
    assert tuner.stats() == {"tunes": 1, "swept_configs": first.swept}
    assert tune_events[0].attrs["swept"] == first.swept
    # a different shape is a different key: a genuine second sweep
    tuner.tune(name, {"B": 1, "L": 64, "dr": 128})
    assert len([e for e in bus.events() if e.name == "kernel.tune"]) == 2


def test_same_seed_runs_produce_byte_identical_payloads():
    name, shape = DEMO
    results, payloads = [], []
    for _ in range(2):
        tuner = _model_tuner(seed=7)
        r = tuner.tune(name, shape)
        results.append(r)
        payloads.append(tuner.payload(r.key))
    assert results[0].config == results[1].config
    assert isinstance(payloads[0], bytes)
    assert payloads[0] == payloads[1]
    # the payload is the choice, never the timings (timings are wall-noisy
    # under timer="wall"; keeping them out is what makes bytes comparable)
    assert b"timings" not in payloads[0]
    assert b'"seed":7' in payloads[0]


def test_cache_keys_by_device_kind_and_model_prices_named_chip():
    import jax

    from repro.roofline.model import V5E, peaks

    key = _model_tuner().cache_key(*DEMO, "float32")
    assert key.split(":")[2] == jax.devices()[0].device_kind.replace(" ", "-")
    assert peaks(V5E).flops == 197e12 and peaks(V5E).hbm_bw == 819e9
    with pytest.raises(KeyError):  # no default peak for a chip not in the table
        peaks(jax.devices()[0].device_kind + " (not in the table)")


def test_winner_registers_as_pinned_shared_dataset():
    name, shape = DEMO
    registry = DatasetRegistry()
    tuner = _model_tuner(registry=registry, site=SHARED_SITE)
    result = tuner.tune(name, shape)
    assert result.key.startswith(f"tune:{name}:")
    assert result.key.endswith(kreg.shape_sig(shape, "float32"))
    assert registry.known(result.key)
    assert registry.get(result.key).pinned
    assert SHARED_SITE in registry.locate(result.key)


# ---------------------------------------------------------------------------
# how a tuned config reaches a kernel: as an explicit config
# ---------------------------------------------------------------------------


def test_ops_resolution_order_explicit_beats_tuned_beats_default():
    """registry.block_config, the one resolver: a full explicit config (a
    tuned winner is passed as one) wins outright; a partial one is filled
    from the kernel's tile rule; none gives the rule's config."""
    name, shape = DEMO
    kdef = kreg.get_kernel(name)
    tuned = _model_tuner().tune(name, shape).config
    assert tuned != kdef.defaults(shape, "float32")  # a real contrast
    assert kreg.block_config(kdef, shape, "float32", tuned) == tuned
    assert kreg.block_config(kdef, shape, "float32", {"block_d": 64}) == {"block_d": 64}
    assert kreg.block_config(kdef, shape, "float32") == kdef.defaults(shape, "float32")
    assert kreg.block_config(kdef, shape, "float32", {"block_d": None}) == {"block_d": 512}

    gmm = kreg.get_kernel("moe_gmm")
    gshape = {"E": 1, "C": 1024, "D": 6144, "F": 32768}
    rule = gmm.defaults(gshape, "bfloat16")
    assert rule == {"block_c": 1024, "block_f": 1024, "block_d": 512}
    assert kreg.block_config(gmm, gshape, "bfloat16", {"block_f": 256}) == {**rule, "block_f": 256}
    full = {"block_c": 128, "block_f": 256, "block_d": 128}
    assert kreg.block_config(gmm, gshape, "bfloat16", full) == full


@pytest.mark.parametrize("causal,window,live", [(False, None, 16), (True, None, 10), (True, 64, 7)])
def test_attention_cost_fetches_kv_for_live_cells_only(causal, window, live):
    """The K/V index map clamps masked cells onto a live block, so the model
    counts a k and a v tile per live cell, q and the output once each."""
    kdef = kreg.get_kernel("flash_attention")
    B, H, L, hd, blk, isz = 1, 2, 256, 64, 64, 4
    shape = {"B": B, "H": H, "KV": 1, "L": L, "hd": hd, "causal": causal, "window": window}
    cost = kdef.cost(shape, {"block_q": blk, "block_k": blk}, "float32")
    assert cost.grid_cells == B * H * 4 * 4  # every cell is still a grid step
    assert cost.flops == 4.0 * B * H * live * blk * blk * hd
    assert cost.hbm_bytes == isz * B * H * (live * 2 * blk * hd + 2 * L * hd)
