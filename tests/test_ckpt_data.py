"""Checkpointing (sync/async, retention, restart) + data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.data.pipeline import DataConfig, Prefetcher, batch_at


def _state():
    return {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "opt": {"m": jnp.zeros((2, 3)), "step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip(tmp_path):
    state = _state()
    ckpt.save(str(tmp_path), 7, state)
    step, restored = ckpt.restore(str(tmp_path), state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_retention(tmp_path):
    state = _state()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, state, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000004", "step_00000005"]


def test_async_checkpointer(tmp_path):
    state = _state()
    ac = ckpt.AsyncCheckpointer(str(tmp_path))
    ac.save(3, state)
    ac.wait()
    step, restored = ckpt.restore(str(tmp_path), state)
    assert step == 3


def test_restore_validates_shapes(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), {"w": jnp.zeros((3, 3))})


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path), _state())


def test_batches_deterministic_and_step_indexed():
    dc = DataConfig(vocab_size=100, seq_len=8, global_batch=4, seed=1)
    b1, b2 = batch_at(dc, 5), batch_at(dc, 5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_at(dc, 6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted from the same stream
    assert b1["tokens"].shape == b1["labels"].shape == (4, 8)


def test_prefetcher_yields_in_order():
    dc = DataConfig(vocab_size=100, seq_len=8, global_batch=2, seed=0)
    pf = Prefetcher(dc, start_step=3, depth=2)
    try:
        steps = [next(pf)[0] for _ in range(4)]
        assert steps == [3, 4, 5, 6]
        ref = batch_at(dc, 3)
        pf2 = Prefetcher(dc, start_step=3, depth=1)
        np.testing.assert_array_equal(next(pf2)[1]["tokens"], ref["tokens"])
        pf2.close()
    finally:
        pf.close()


def test_train_restart_equivalence(tmp_path):
    """Train 4 steps == train 2, checkpoint, restore, train 2 more."""
    from repro.configs import get_arch
    from repro.models.model import Model
    from repro.optim import adamw
    from repro.train import step as step_lib
    from repro.parallel.sharding import STRATEGIES

    cfg = get_arch("llama3-8b").reduced()
    model = Model(cfg)
    ocfg = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    ts = jax.jit(step_lib.make_train_step(model, STRATEGIES["tp"], mesh, ocfg))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)

    params, opt = step_lib.init_train_state(model, jax.random.key(0))
    for i in range(4):
        params, opt, _ = ts(params, opt, batch_at(dc, i))
    ref = jax.tree.leaves(params)

    params2, opt2 = step_lib.init_train_state(model, jax.random.key(0))
    for i in range(2):
        params2, opt2, _ = ts(params2, opt2, batch_at(dc, i))
    ckpt.save(str(tmp_path), 2, {"params": params2, "opt": opt2})
    _, restored = ckpt.restore(str(tmp_path), {"params": params2, "opt": opt2})
    params3, opt3 = restored["params"], restored["opt"]
    for i in range(2, 4):
        params3, opt3, _ = ts(params3, opt3, batch_at(dc, i))
    for a, b in zip(ref, jax.tree.leaves(params3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
