"""Serving driver: batched prefill + autoregressive decode with KV cache /
recurrent state (per family).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \\
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models.model import Model


def serve(
    arch_name: str,
    *,
    reduced: bool = True,
    batch: int = 4,
    prompt_len: int = 32,
    gen: int = 16,
    temperature: float = 0.0,
    seed: int = 0,
) -> dict:
    arch = get_arch(arch_name)
    if reduced:
        arch = arch.reduced()
    model = Model(arch)
    rng = np.random.default_rng(seed)
    params = model.init(jax.random.key(seed))

    prompts = jnp.asarray(rng.integers(0, arch.vocab_size, (batch, prompt_len)), jnp.int32)
    batch_in = {"tokens": prompts}
    if arch.family == "audio":
        batch_in["enc_frames"] = jnp.asarray(
            rng.normal(size=(batch, arch.enc_len_serve, arch.d_model)), jnp.float32
        )
    if arch.family == "vlm":
        batch_in["img_embeds"] = jnp.asarray(
            rng.normal(size=(batch, arch.n_img_tokens, arch.d_model)), jnp.float32
        )

    cache_len = prompt_len + gen
    t0 = time.perf_counter()
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, cache_len=cache_len))(params, batch_in)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    decode = jax.jit(model.decode_step)
    key = jax.random.key(seed + 1)

    def sample(lg, key):
        if temperature <= 0:
            return jnp.argmax(lg[:, 0, :], axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg[:, 0, :] / temperature).astype(jnp.int32)

    toks = sample(logits, key)[:, None]
    generated = [toks]
    t0 = time.perf_counter()
    for i in range(gen - 1):
        pos = jnp.full((batch,), prompt_len + i, jnp.int32)
        logits, cache = decode(params, cache, toks, pos)
        key, sub = jax.random.split(key)
        toks = sample(logits, sub)[:, None]
        generated.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0
    out_tokens = jnp.concatenate(generated, axis=1)
    return {
        "arch": arch_name,
        "tokens": np.asarray(out_tokens),
        "prefill_s": t_prefill,
        "decode_s_per_token": t_decode / max(gen - 1, 1),
        "tokens_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
    }


def main():
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    out = serve(
        args.arch, batch=args.batch, prompt_len=args.prompt_len,
        gen=args.gen, temperature=args.temperature,
    )
    print(f"{args.arch}: prefill {out['prefill_s']*1e3:.1f} ms, "
          f"decode {out['decode_s_per_token']*1e3:.1f} ms/tok, "
          f"{out['tokens_per_s']:.1f} tok/s")
    print("sample tokens:", out["tokens"][0][:12].tolist())


if __name__ == "__main__":
    main()
