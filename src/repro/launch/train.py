"""Training driver: data pipeline + train step + checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \\
        --steps 200 --ckpt-dir /tmp/ckpt --ckpt-every 50

Production semantics on a real fleet, CPU-sized defaults here:
  * restart-safe: resumes from the latest checkpoint (data stream is
    step-indexed, so the token stream realigns exactly),
  * async checkpointing overlaps the save with training,
  * optional int8 error-feedback gradient compression over the DP axes,
  * runs standalone or brokered (examples/train_lm.py submits this loop as a
    Hydra compute task).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax

from repro.ckpt import checkpoint as ckpt_lib
from repro.configs import get_arch
from repro.data.pipeline import DataConfig, Prefetcher
from repro.launch.mesh import make_local_mesh
from repro.models.model import Model
from repro.optim import adamw
from repro.parallel.sharding import STRATEGIES, default_strategy
from repro.train import step as step_lib


def train(
    arch_name: str,
    *,
    reduced: bool = True,
    steps: int = 100,
    seq_len: int = 64,
    global_batch: int = 8,
    peak_lr: float = 3e-4,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    strategy_name: Optional[str] = None,
    log_every: int = 10,
    seed: int = 0,
) -> dict:
    arch = get_arch(arch_name)
    if reduced:
        arch = arch.reduced()
    model = Model(arch)
    mesh = make_local_mesh(len(jax.devices()))
    strategy = STRATEGIES[strategy_name] if strategy_name else default_strategy(arch)
    if arch.family == "moe" and arch.n_experts < 16:
        strategy = strategy.with_overrides(experts=None)
    opt_cfg = adamw.AdamWConfig(peak_lr=peak_lr, warmup_steps=max(steps // 10, 1), total_steps=steps)
    train_step = jax.jit(step_lib.make_train_step(model, strategy, mesh, opt_cfg), donate_argnums=(0, 1))

    dc = DataConfig(
        vocab_size=arch.vocab_size, seq_len=seq_len, global_batch=global_batch,
        seed=seed, enc_len=arch.enc_len_train, d_model=arch.d_model,
        n_img_tokens=arch.n_img_tokens, family=arch.family,
    )

    start_step = 0
    params, opt = step_lib.init_train_state(model, jax.random.key(seed))
    checkpointer = None
    if ckpt_dir:
        checkpointer = ckpt_lib.AsyncCheckpointer(ckpt_dir)
        latest = ckpt_lib.latest_step(ckpt_dir)
        if latest is not None:
            start_step, restored = ckpt_lib.restore(ckpt_dir, {"params": params, "opt": opt})
            params, opt = restored["params"], restored["opt"]
            print(f"resumed from step {start_step}")

    prefetch = Prefetcher(dc, start_step=start_step, depth=2)
    losses = []
    t0 = time.perf_counter()
    try:
        for _ in range(start_step, steps):
            step_idx, batch = next(prefetch)
            params, opt, metrics = train_step(params, opt, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if log_every and (step_idx + 1) % log_every == 0:
                dt = (time.perf_counter() - t0) / max(len(losses), 1)
                print(f"step {step_idx + 1:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} ({dt*1e3:.0f} ms/step)")
            if checkpointer and (step_idx + 1) % ckpt_every == 0:
                checkpointer.save(step_idx + 1, {"params": params, "opt": opt})
    finally:
        prefetch.close()
        if checkpointer:
            checkpointer.wait()
    return {
        "arch": arch_name,
        "steps": len(losses),
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "params": params,
        "opt": opt,
    }


def main():
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--strategy", default=None)
    args = ap.parse_args()
    out = train(
        args.arch, reduced=args.reduced, steps=args.steps, seq_len=args.seq_len,
        global_batch=args.global_batch, peak_lr=args.lr, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, strategy_name=args.strategy,
    )
    print(f"done: loss {out['first_loss']:.4f} -> {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
