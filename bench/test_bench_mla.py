"""The DeepSeek-V3 latent-attention cell's benchmark files: the work counts
against counts by hand, the HLO types, the reference against a naive loop
over heads and queries, the configuration file's cut, and the cell through
the whole harness at tiny shapes on the CPU (kernels interpreted)."""
from __future__ import annotations

import copy
import inspect
import math
import sys

import numpy as np
import pytest

from bench import oracle, run, spec
from bench.conftest import SEED

B = spec.benchmark()
CELL = "dsv3mla1.mixed.steady"
CONFIG = "dsv3-mla-1chip"
# widths cut for an interpreted kernel on a CPU; only here
TINY = {
    "mla_prefill": {"B": 1, "H": 4, "Lq": 64, "Lk": 256, "dc": 128, "dn": 64, "dr": 32, "dv": 64},
    "mla_decode": {"B": 2, "H": 8, "Lk": 256, "dc": 128, "dn": 64, "dr": 32, "dv": 64},
}


def test_prefill_work_by_hand():
    shape = {"B": 1, "H": 2, "Lq": 2, "Lk": 4, "dc": 8, "dn": 4, "dr": 2, "dv": 3, "scale": 0.5}
    flops, nbytes = spec.plugin("work", "mla_prefill").work(shape, "bfloat16")
    # query 0 sits at position 2 and attends 3 keys, query 1 attends 4: 7 pairs a head
    assert flops == 2 * (4 + 2 + 3) * 2 * 7
    # q_nope, q_rope and the output (2 heads x 2 queries); k_nope and v
    # (2 heads x 4 keys); k_rope once for both heads; two bytes each
    assert nbytes == 2 * (2 * 2 * (4 + 2 + 3) + 2 * 4 * (4 + 3) + 4 * 2)


def test_decode_work_by_hand():
    shape = {"B": 2, "H": 3, "Lk": 5, "dc": 8, "dn": 4, "dr": 2, "dv": 3, "scale": 0.5}
    flops, nbytes = spec.plugin("work", "mla_decode").work(shape, "float32")
    # every head meets every position: scores over 8 + 2, p . c_kv over 8
    assert flops == 2 * 2 * 3 * 5 * (8 + 2 + 8)
    # the latent and rope caches, the two queries and the latent output
    assert nbytes == 4 * 2 * (5 * (8 + 2) + 3 * (8 + 2) + 3 * 8)


def test_work_of_the_published_payloads():
    cat = {p["name"]: p for p in spec.config(B, CONFIG)["payloads"]}
    flops, _ = spec.plugin("work", "mla_prefill").work(cat["dsv3_mla_prefill"]["shape"], "bfloat16")
    assert flops == 2 * 320 * 128 * (2048 * 14336 + 2048 * 2049 / 2)  # 2.58 TFLOP
    flops, nbytes = spec.plugin("work", "mla_decode").work(cat["dsv3_mla_decode"]["shape"], "bfloat16")
    assert flops == 2 * 32 * 128 * 16384 * 1088  # 146 GFLOP
    assert nbytes == 2 * 32 * (16384 * 576 + 128 * 576 + 128 * 512)  # 604 MB of cache and more


def test_hlo_types():
    p = {"B": 1, "H": 128, "Lq": 2048, "Lk": 16384, "dc": 512, "dn": 128, "dr": 64, "dv": 128}
    ins, outs = spec.plugin("work", "mla_prefill").hlo_types(p, "bfloat16")
    assert ins == ["bf16[1,128,2048,128]", "bf16[1,128,2048,64]", "bf16[1,128,16384,128]",
                   "bf16[1,1,16384,64]", "bf16[1,128,16384,128]"]
    assert outs == ["bf16[1,128,2048,128]"]
    d = {"B": 32, "H": 128, "Lk": 16384, "dc": 512, "dn": 128, "dr": 64, "dv": 128}
    ins, outs = spec.plugin("work", "mla_decode").hlo_types(d, "bfloat16")
    assert ins == ["bf16[32,128,512]", "bf16[32,128,64]", "bf16[32,16384,512]", "bf16[32,16384,64]"]
    assert outs == ["bf16[32,128,512]"]


def test_work_ignores_block_config():
    for kernel in ("mla_prefill", "mla_decode"):
        assert list(inspect.signature(spec.plugin("work", kernel).work).parameters) == ["shape", "dtype"]


def _naive(shape: dict, args: tuple) -> np.ndarray:
    """Per sequence, head and query in float64: K and V decompressed, the
    query at key position Lk - Lq + i sees keys 0 ... Lk - Lq + i."""
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = (np.asarray(a, np.float64) for a in args)
    decode = "Lq" not in shape
    if decode:
        q_nope, q_rope, k_rope = q_nope[:, :, None], q_rope[:, :, None], k_rope[:, None]
    b_, h_, lq, _ = q_nope.shape
    lk = c_kv.shape[1]
    out = np.zeros((b_, h_, lq, w_uv.shape[-1]))
    for b in range(b_):
        for h in range(h_):
            k = np.concatenate([c_kv[b] @ w_uk[:, h], k_rope[b, 0]], axis=-1)
            v = c_kv[b] @ w_uv[:, h]
            for i in range(lq):
                n = lk - lq + i + 1
                s = k[:n] @ np.concatenate([q_nope[b, h, i], q_rope[b, h, i]]) * shape["scale"]
                p = np.exp(s - s.max())
                out[b, h, i] = p @ v[:n] / p.sum()
    return out[:, :, 0] if decode else out


@pytest.mark.parametrize("kernel", ["mla_prefill", "mla_decode"])
def test_reference_against_a_naive_loop(kernel):
    """float32 at the highest precision against float64: 1e-4 on outputs of
    size ~1 leaves room for float32 sums over 256 keys, not for a wrong
    mask, scale or head."""
    import jax.numpy as jnp

    shape = dict(TINY[kernel], H=3, scale=0.17)
    ref = spec.plugin("reference", kernel)
    args = ref.operands(shape, "float32", 5)
    axis = 1 if kernel == "mla_prefill" else 0  # blocks of heads; sequences
    got = np.concatenate([np.asarray(b) for b in ref.outputs(shape, args)], axis=axis)
    np.testing.assert_allclose(got, _naive(shape, args), atol=1e-4)
    # the program's own operand recipe: the reference computes on the task's inputs
    from repro.kernels import registry as kreg

    mine = kreg.get_kernel(kernel).make_args(shape, "float32", 5)
    for a, b in zip(args, mine):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(a.dtype == jnp.float32 for a in args)


def test_config_file_states_its_cut():
    from repro.configs.deepseek_v3 import CONFIG as DSV3
    from repro.kernels import registry as kreg

    entry = next(c for c in B["configs"] if c["name"] == CONFIG)
    config = spec.config(B, CONFIG)
    assert entry["source"] == config["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json"
    assert entry["reduced"] == list(config["reduced"]) == ["providers"]
    # the published config's numbers, unchanged
    assert (config["num_attention_heads"], config["kv_lora_rank"], config["q_lora_rank"]) == (128, 512, 1536)
    assert (config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]) == (128, 64, 128)
    assert config["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
                                      "original_max_position_embeddings": 4096, "type": "yarn"}
    assert {"slots", "chunk", "context", "decode_batch", "operands", "outside"} <= set(config["assumed"])
    # xplat-1chip's providers and broker, with this payload's slots: 8 on the chip
    xplat = spec.config(B, "xplat-1chip")
    assert config["broker"] == xplat["broker"]
    assert [(p["name"], p["connector"], p["device_offset"]) for p in config["providers"]] == \
        [(p["name"], p["connector"], p["device_offset"]) for p in xplat["providers"]]
    assert sum(p["concurrency"] for p in config["providers"]) == 8 == int(12e9 // 1_361_051_648)
    cat = {p["name"]: p for p in config["payloads"]}
    for name, kernel in (("dsv3_mla_prefill", "mla_prefill"), ("dsv3_mla_decode", "mla_decode")):
        assert cat[name]["kernel"] == kernel and cat[name]["dtype"] == "bfloat16" and cat[name]["reduced"] == []
        assert cat[name]["shape"] == kreg.published_shapes()[kernel][1]
        assert cat[name]["shape"]["scale"] == DSV3.softmax_scale(16384)
    assert (cat["dsv3_mla_prefill"]["shape"]["Lq"], cat["dsv3_mla_decode"]["shape"]["B"]) == (2048, 32)


def _tiny_config() -> dict:
    config = copy.deepcopy(spec.config(B, CONFIG))
    for p in config["payloads"]:
        p["shape"] = dict(TINY[p["kernel"]], scale=p["shape"]["scale"])
    return config


def _run(config: dict, rate: float = 4.0):
    import jax

    cell = spec.cell(B, CELL)
    traffic = dict(spec.traffic(cell["traffic"]), rate_per_s=rate)
    return run.run_cell(B, cell, config, traffic, SEED, 2.0, False, jax.devices(),
                        log=lambda s: print(s, file=sys.stderr))


def test_tiny_cell_is_correct():
    out, rec = _run(_tiny_config())
    assert out["correct"], out["checks"]
    assert out["attempted"] == len(rec.tasks) == 8 and out["failed"] == 0
    assert {"gap.dsv3_mla_prefill", "gap.dsv3_mla_decode"} <= set(out["checks"])
    assert set(out["metrics"]) == {"task_p50_ms", "task_p95_ms", "setup_s"}


FAULTS = {
    "no_yarn": lambda shape, args: (dict(shape, scale=(shape["dn"] + shape["dr"]) ** -0.5), args),
    "no_rope": lambda shape, args: (shape, (args[0], args[1] * 0, *args[2:])),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["control"])
def test_faults_and_the_control_are_not_correct(monkeypatch, fault):
    """The reference with a fault, or every operand a precision lower, in
    the kernel's place: both payloads' gaps pass their limits."""
    from repro.kernels import registry as kreg

    def planted(kdef, shape, dtype, config, device):
        ref = spec.plugin("reference", kdef.name)
        if fault == "control":
            return lambda *args: list(ref.outputs(shape, tuple(oracle._round_down(a) for a in args)))
        return lambda *args: list(ref.outputs(*FAULTS[fault](shape, args)))

    monkeypatch.setattr(kreg, "compiled", planted)
    out, _ = _run(_tiny_config())
    assert not out["correct"]
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert {"gap.dsv3_mla_prefill", "gap.dsv3_mla_decode"} <= failed, out["checks"]


def test_prefill_without_its_offset_is_not_correct(monkeypatch):
    from repro.kernels import registry as kreg

    def planted(kdef, shape, dtype, config, device):
        ref = spec.plugin("reference", kdef.name)
        if kdef.name == "mla_prefill":
            return lambda *args: list(ref.blocks(shape, args, 0))
        return lambda *args: list(ref.outputs(shape, args))

    monkeypatch.setattr(kreg, "compiled", planted)
    out, _ = _run(_tiny_config())
    assert out["checks"]["gap.dsv3_mla_prefill"]["value"] > out["checks"]["gap.dsv3_mla_prefill"]["limit"]
    assert math.isfinite(out["checks"]["gap.dsv3_mla_decode"]["value"])
