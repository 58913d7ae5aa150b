"""DeepSeek-V3 latent attention (kernels/mla_attention.py) against the plain
non-absorbed reference (kernels/ref.py ``mla_ref``), in interpret mode at a
small size: H 4, Lk 256, Lq 64.  Only here are the widths cut (dc 128, dn
64, dr 32, dv 64); the kernels compile at the published ones in
test_tpu_compile.py.

Tolerances: float32 operands in interpret mode follow the reference to
about 1e-6 (the order of accumulation differs across kv blocks), so
``TOL_F32`` = 2e-5, as in test_kernels_parity.py.  bfloat16 operands round
each decompressed key, absorbed query and p to 8 bits of mantissa (relative
error 2^-9), which moves outputs of size ~1 by up to a few 1e-3; hence
``TOL_BF16`` = 3e-2 on the largest error.  Both are well under what a
float8 operand or a missing YaRN factor does to the output.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.deepseek_v3 import CONFIG as DSV3
from repro.core import Hydra, ProviderSpec, Task, TaskState
from repro.kernels import flash_attention as fa
from repro.kernels import mla_attention as mla
from repro.kernels import registry as kreg
from repro.kernels.ref import mla_ref

TOL_F32 = 2e-5
TOL_BF16 = 3e-2
SMALL = {"B": 2, "H": 4, "Lk": 256, "dc": 128, "dn": 64, "dr": 32, "dv": 64, "scale": DSV3.softmax_scale(16384)}


def _args(shape: dict, dtype: str = "float32", seed: int = 0) -> tuple:
    return kreg.get_kernel("mla_prefill" if "Lq" in shape else "mla_decode").make_args(shape, dtype, seed)


def _err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _prefill_ref(shape, args):
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    return mla_ref(q_nope, q_rope, c_kv, k_rope[:, 0], w_uk, w_uv, scale=shape["scale"])


def _decode_ref(shape, args):
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    return mla_ref(q_nope[:, :, None], q_rope[:, :, None], c_kv, k_rope, w_uk, w_uv, scale=shape["scale"])[:, :, 0]


@pytest.mark.parametrize("lq", [64, 256], ids=["offset", "no_offset"])
@pytest.mark.parametrize("blocks", [(32, 64), (64, 32)])
def test_prefill_matches_the_reference(lq, blocks):
    """Queries the last ``lq`` of 256 positions (causal offset 192, or none),
    with blocks that leave live, edge and skipped cells in the grid."""
    shape = dict(SMALL, Lq=lq)
    args = _args(shape)
    out = mla.prefill_program(*args, scale=shape["scale"], block_q=blocks[0], block_k=blocks[1], interpret=True)
    assert out.shape == (2, 4, lq, 64)
    assert _err(out, _prefill_ref(shape, args)) <= TOL_F32


@pytest.mark.parametrize("block_k", [32, 256])
def test_absorbed_decode_matches_the_non_absorbed_definition(block_k):
    args = _args(SMALL)
    out = mla.decode_program(*args, scale=SMALL["scale"], block_k=block_k, interpret=True)
    assert out.shape == (2, 4, 64)
    assert _err(out, _decode_ref(SMALL, args)) <= TOL_F32


def test_decode_is_prefill_of_one_query_at_the_cache_end():
    """The two kernels agree where their definitions meet: one query per
    head, at the last position, sees the whole cache."""
    args = _args(SMALL)
    q_nope, q_rope, c_kv, k_rope, w_uk, w_uv = args
    pre = mla.prefill_program(
        jnp.repeat(q_nope[:, :, None], 8, axis=2), jnp.repeat(q_rope[:, :, None], 8, axis=2),
        c_kv, k_rope[:, None], w_uk, w_uv, scale=SMALL["scale"], interpret=True,
    )
    dec = mla.decode_program(*args, scale=SMALL["scale"], interpret=True)
    # the last of 8 equal queries is the decode query at position Lk - 1
    assert _err(pre[:, :, -1], dec) <= TOL_F32


@pytest.mark.parametrize("kernel", ["mla_prefill", "mla_decode"])
def test_bfloat16_payload_within_its_tolerance(kernel):
    shape = dict(SMALL, Lq=64) if kernel == "mla_prefill" else dict(SMALL)
    kdef = kreg.get_kernel(kernel)
    args = kdef.make_args(shape, "bfloat16", 1)
    out = kdef.call(shape, args, kreg.block_config(kdef, shape, "bfloat16"), True)
    assert out.dtype == jnp.bfloat16
    assert _err(out, kdef.ref(shape, args)) <= TOL_BF16


@pytest.mark.parametrize("kernel", ["mla_prefill", "mla_decode"])
def test_float8_operands_and_a_missing_yarn_factor_fail_the_tolerances(kernel):
    shape = dict(SMALL, Lq=64) if kernel == "mla_prefill" else dict(SMALL)
    kdef = kreg.get_kernel(kernel)
    args = kdef.make_args(shape, "float32", 2)
    want = kdef.ref(shape, args)
    f8 = tuple(a.astype(jnp.float8_e4m3fn).astype(jnp.float32) for a in args)
    assert _err(kdef.call(shape, f8, kreg.block_config(kdef, shape, "float32"), True), want) > TOL_BF16
    no_yarn = dict(shape, scale=(shape["dn"] + shape["dr"]) ** -0.5)
    assert _err(kdef.call(no_yarn, args, kreg.block_config(kdef, shape, "float32"), True), want) > TOL_BF16


def test_yarn_scale():
    """DeepSeek-V3 at a 16k context: 192^-0.5 (0.1 ln 40 + 1)^2; within the
    original 4,096 positions, 192^-0.5 alone."""
    m = 0.1 * math.log(40) + 1
    assert DSV3.softmax_scale(16384) == pytest.approx(192**-0.5 * m * m, rel=1e-12)
    assert DSV3.softmax_scale(16384) == pytest.approx(0.135234, abs=5e-7)
    assert DSV3.softmax_scale(4096) == 192**-0.5
    for kernel in ("mla_prefill", "mla_decode"):
        arch, shape, dtypes = kreg.published_shapes()[kernel]
        assert arch == "deepseek-v3" and dtypes == ("bfloat16",)
        assert shape["scale"] == DSV3.softmax_scale(shape["Lk"])
        assert (shape["H"], shape["dc"], shape["dn"], shape["dr"], shape["dv"]) == (128, 512, 128, 64, 128)


def test_masked_cells_copy_nothing_with_the_offset():
    """Walking the prefill grid in order, the index map names only live kv
    blocks: no more K/V tiles are copied than there are live cells, and the
    first query block (at position Lk - Lq) reaches exactly its band."""
    lq, lk, bq, bk = 256, 1024, 64, 128
    band = dict(block_q=bq, block_k=bk, n_kv_blocks=lk // bk, causal=True, window=None, q_offset=lk - lq)
    copies, prev, live = 0, None, 0
    for qi in range(lq // bq):
        first, last = (int(x) for x in fa.band(qi, **band))
        assert (first, last) == (0, (lk - lq + qi * bq + bq - 1) // bk)
        for ki in range(lk // bk):
            blk = int(fa.kv_block(qi, ki, **band))
            live += first <= ki <= last
            copies += blk != prev
            prev = blk
    assert copies <= live
    shape = {"B": 1, "H": 1, "Lq": lq, "Lk": lk, "dc": 64, "dn": 64, "dr": 32, "dv": 64, "scale": 0.1}
    cost = kreg.get_kernel("mla_prefill").cost(shape, {"block_q": bq, "block_k": bk}, "float32")
    assert cost.flops == 2.0 * live * bq * bk * (64 + 32 + 64) + 2.0 * lk * 64 * (64 + 64)


def _pallas_operands(fn, *args) -> list:
    jaxpr = jax.make_jaxpr(fn)(*args)
    todo, found = [jaxpr.jaxpr], []
    while todo:
        for e in todo.pop().eqns:
            if e.primitive.name == "pallas_call":
                found.append([v.aval.shape for v in e.invars])
            for p in e.params.values():
                if hasattr(p, "jaxpr"):
                    todo.append(p.jaxpr)
    assert len(found) == 1
    return found[0]


def test_shared_operands_are_not_widened():
    """Prefill reads k_rope as one head (B, 1, Lk, dr) for every query head;
    decode takes the latent cache once, as the key's first part and the
    value."""
    shape = dict(SMALL, Lq=64)
    args = _args(shape)
    ins = _pallas_operands(lambda *a: mla.prefill_program(*a, scale=0.1, interpret=True), *args)
    assert ins == [(2, 4, 64, 64), (2, 4, 64, 32), (2, 4, 256, 64), (2, 1, 256, 32), (2, 4, 256, 64)]
    ins = _pallas_operands(lambda *a: mla.decode_program(*a, scale=0.1, interpret=True), *_args(SMALL))
    assert ins == [(2, 4, 128), (2, 4, 32), (2, 256, 128), (2, 256, 32)]


def test_named_scopes_mark_the_xla_stages():
    shape = dict(SMALL, Lq=64)
    text = jax.jit(lambda *a: mla.prefill_program(*a, scale=0.1, interpret=True)).lower(*_args(shape)).as_text(debug_info=True)
    assert "mla.decompress" in text
    text = jax.jit(lambda *a: mla.decode_program(*a, scale=0.1, interpret=True)).lower(*_args(SMALL)).as_text(debug_info=True)
    assert "mla.absorb" in text and "mla.unabsorb" in text


@pytest.mark.parametrize("kernel", ["mla_prefill", "mla_decode"])
def test_task_through_dispatch_checksums_like_the_reference(tmp_path, kernel):
    """One ``kind="kernel"`` task through Hydra.dispatch, the executor and
    KernelRuntime: its checksum is the reference's on the same seeded
    operands (float32 sums of ~1e3 outputs of size ~1: rel 1e-5)."""
    shape = dict(SMALL, Lq=64) if kernel == "mla_prefill" else dict(SMALL)
    h = Hydra(pod_store="memory", streaming=True, batch_window=0.0, workdir=str(tmp_path))
    handle = h.register_provider(ProviderSpec(name="p", concurrency=1))
    task = Task(kind="kernel", payload={"kernel": kernel, "shape": shape, "dtype": "float32", "seed": 7})
    h.dispatch([task])
    assert task.result(timeout=120) is not None
    h.shutdown(wait=True)
    assert task.tstate == TaskState.DONE
    result = task.result()
    assert result["kernel"] == kernel and result["device"] in [d.id for d in handle.devices]
    kdef = kreg.get_kernel(kernel)
    want = np.asarray(kdef.ref(shape, kdef.make_args(shape, "float32", 7)), np.float64).sum()
    assert result["checksum"] == pytest.approx(want, rel=1e-5, abs=1e-3)
