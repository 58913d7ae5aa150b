"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode on CPU; the same calls lower to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,L,hd,block",
    [
        (1, 4, 4, 128, 64, 64),   # MHA
        (2, 8, 2, 256, 64, 128),  # GQA 4:1
        (1, 4, 1, 128, 32, 32),   # MQA
        (1, 2, 2, 192, 64, 64),   # non-pow2 seq (divisible blocks)
    ],
)
def test_flash_attention_sweep(dtype, B, H, KV, L, hd, block):
    q = jnp.asarray(RNG.normal(size=(B, H, L, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_windowed(window):
    B, H, KV, L, hd = 1, 2, 1, 256, 64
    q = jnp.asarray(RNG.normal(size=(B, H, L, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_non_causal():
    B, H, KV, L, hd = 1, 2, 2, 128, 64
    q = jnp.asarray(RNG.normal(size=(B, H, L, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# the tiles ops.flash_attention takes from the one rule when no block is
# given: 384 -> 128 x 128 (no larger tile divides it), 2048 -> 512 x 1024
_MASKS = {
    "causal": {"causal": True, "window": None},
    "windowed": {"causal": True, "window": 100},
    "full": {"causal": False, "window": None},
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mask", sorted(_MASKS))
@pytest.mark.parametrize("L", [384, 2048])
def test_flash_attention_default_tiles(dtype, mask, L):
    """Parity at the shape-derived default tiles, with a 6:1 GQA group."""
    B, H, KV, hd = 1, 12, 2, 128
    q = jnp.asarray(RNG.normal(size=(B, H, L, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, KV, L, hd)), dtype)
    got = ops.flash_attention(q, k, v, **_MASKS[mask])
    want = ref.attention_ref(q, k, v, **_MASKS[mask])
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_flash_attention_one_tile_rule(monkeypatch):
    """ops.flash_attention and the registry's defaults resolve the same
    tiles, both from flash_attention.default_blocks."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import registry as kreg

    monkeypatch.delenv("HYDRA_AUTOTUNE", raising=False)
    seen = {}
    monkeypatch.setattr(ops, "_flash_attention_jit", lambda q, k, v, **kw: seen.update(kw))
    want = {
        64: (64, 64), 128: (128, 128), 192: (192, 192), 384: (128, 128),
        768: (256, 256), 1024: (512, 1024), 1536: (512, 512), 8192: (512, 1024),
    }
    for L, (bq, bk) in want.items():
        q = jax.ShapeDtypeStruct((1, 4, L, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 2, L, 128), jnp.bfloat16)
        ops.flash_attention(q, kv, kv)
        shape = {"B": 1, "H": 4, "KV": 2, "L": L, "hd": 128, "causal": True, "window": None}
        registry = kreg.get_kernel("flash_attention").defaults(shape, "bfloat16")
        assert (seen["block_q"], seen["block_k"]) == fa.default_blocks(L, L) == (bq, bk)
        assert registry == {"block_q": bq, "block_k": bk}


@pytest.mark.parametrize("causal,window", [(True, None), (True, 100), (False, None), (False, 100)])
def test_flash_attention_masked_cells_copy_nothing(causal, window):
    """Walking the grid in order, the K/V index map names only live blocks,
    a live cell its own block, and a masked cell the block before it: the
    pipeline copies no more K/V tiles than there are live cells."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import registry as kreg

    L, bq, bk = 1024, 128, 256
    band = dict(block_q=bq, block_k=bk, n_kv_blocks=L // bk, causal=causal, window=window)
    copies, prev = 0, None
    for qi in range(L // bq):
        first, last = (int(x) for x in fa.band(qi, **band))
        for ki in range(L // bk):
            blk = int(fa.kv_block(qi, ki, **band))
            assert first <= blk <= last
            assert blk == ki or not first <= ki <= last
            copies += blk != prev
            prev = blk
    shape = {"L": L, "causal": causal, "window": window}
    assert copies <= kreg._fa_live_cells(shape, {"block_q": bq, "block_k": bk})


def _kernel_dots(dtype) -> list:
    """Operand dtypes of each matmul inside the attention kernel's body."""
    from repro.kernels import flash_attention as fa

    q = jax.ShapeDtypeStruct((1, 2, 256, 128), dtype)
    kv = jax.ShapeDtypeStruct((1, 1, 256, 128), dtype)
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(q, k, v))(q, kv, kv)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    dots, todo = [], [call.params["jaxpr"]]
    while todo:
        for e in todo.pop().eqns:
            if e.primitive.name == "dot_general":
                dots.append((tuple(str(x.aval.dtype) for x in e.invars), str(e.outvars[0].aval.dtype)))
            todo += [j.jaxpr if hasattr(j, "jaxpr") else j for j in jax.core.jaxprs_in_params(e.params)]
    return dots


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_matmuls_take_the_payload_dtype(dtype):
    """Both matmuls take the operands' own dtype and accumulate in float32:
    bf16 on the MXU's native path, float32 payloads stay float32."""
    dots = _kernel_dots(jnp.dtype(dtype))
    assert dots and all(ins == (dtype, dtype) and out == "float32" for ins, out in dots), dots


@pytest.mark.parametrize("B,ck,di,N,block_d", [(1, 16, 64, 4, 32), (2, 32, 128, 16, 64), (2, 64, 256, 16, 256)])
def test_selective_scan_sweep(B, ck, di, N, block_d):
    x = jnp.asarray(RNG.normal(size=(B, ck, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, ck, di)), jnp.float32)
    bm = jnp.asarray(RNG.normal(size=(B, ck, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(B, ck, N)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, N)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, di, N)), jnp.float32)
    y1, h1 = ops.selective_scan_chunk(x, dt, bm, cm, a, h0, block_d=block_d)
    y2, h2 = ref.selective_scan_chunk_ref(x, dt, bm, cm, a, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)


def test_selective_scan_bf16_input():
    """bf16 x (the dtype models feed) is read in whole packed slabs."""
    B, ck, di, N = 1, 48, 256, 16
    x = jnp.asarray(RNG.normal(size=(B, ck, di)), jnp.bfloat16)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, ck, di)), jnp.float32)
    bm = jnp.asarray(RNG.normal(size=(B, ck, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(B, ck, N)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, N)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, di, N)), jnp.float32)
    y1, h1 = ops.selective_scan_chunk(x, dt, bm, cm, a, h0, block_d=128)
    y2, h2 = ref.selective_scan_chunk_ref(x, dt, bm, cm, a, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)


def test_selective_scan_chains_chunks():
    """Two chunks chained via h0 == one double-length chunk."""
    B, ck, di, N = 1, 16, 64, 8
    x = jnp.asarray(RNG.normal(size=(B, 2 * ck, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, 2 * ck, di)), jnp.float32)
    bm = jnp.asarray(RNG.normal(size=(B, 2 * ck, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(B, 2 * ck, N)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, N)), jnp.float32)
    h0 = jnp.zeros((B, di, N), jnp.float32)
    y_full, h_full = ops.selective_scan_chunk(x, dt, bm, cm, a, h0, block_d=32)
    y1, h1 = ops.selective_scan_chunk(x[:, :ck], dt[:, :ck], bm[:, :ck], cm[:, :ck], a, h0, block_d=32)
    y2, h2 = ops.selective_scan_chunk(x[:, ck:], dt[:, ck:], bm[:, ck:], cm[:, ck:], a, h1, block_d=32)
    np.testing.assert_allclose(np.asarray(y_full[:, ck:]), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "B,L,dr,block_d",
    [
        (1, 32, 128, 64),
        (2, 64, 256, 128),
        (2, 128, 512, 512),
        (1, 512, 256, 128),  # two 256-step sequence blocks carry the state
        (2, 520, 128, 128),  # 104-step blocks: L is not a multiple of 256
    ],
)
def test_rglru_sweep(B, L, dr, block_d):
    la = -jnp.asarray(RNG.uniform(0.01, 1.0, (B, L, dr)), jnp.float32)
    gx = jnp.asarray(RNG.normal(size=(B, L, dr)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, dr)), jnp.float32)
    y1, h1 = ops.rglru_scan(la, gx, h0, block_d=block_d)
    y2, h2 = ref.rglru_ref(la, gx, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [(2, 32, 64, 128), (4, 64, 128, 256), (8, 128, 256, 128)])
def test_moe_gmm_sweep(dtype, E, C, D, F):
    x = jnp.asarray(RNG.normal(size=(E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(size=(E, D, F)) * 0.1, dtype)
    got = ops.moe_gmm(x, w, block_c=32, block_f=64, block_d=64)
    want = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_moe_gmm_default_blocks(monkeypatch):
    """One tile rule: at Grok-1's expert every tile divides its dimension,
    C is one block and the tiles fit the VMEM budget; small shapes clamp to
    their dimensions; ops.moe_gmm and the registry's defaults agree."""
    from repro.kernels import moe_gmm as gmm
    from repro.kernels import registry as kreg

    C, D, F = 1024, 6144, 32768
    for dtype in ("bfloat16", "float32"):
        isz = jnp.dtype(dtype).itemsize
        bc, bf, bd = gmm.default_blocks(C, D, F, isz)
        assert C % bc == 0 and F % bf == 0 and D % bd == 0
        assert bc == C
        assert gmm.vmem_bytes(bc, bd, bf, isz) <= gmm.VMEM_BUDGET
    assert gmm.default_blocks(C, D, F, 2) == (1024, 1024, 512)
    assert gmm.default_blocks(64, 128, 128, 4) == (64, 128, 128)
    assert gmm.default_blocks(32, 64, 96, 2) == (32, 96, 64)
    assert gmm.default_blocks(384, 768, 1536, 4) == (128, 512, 256)

    monkeypatch.delenv("HYDRA_AUTOTUNE", raising=False)
    seen = {}
    monkeypatch.setattr(ops, "_moe_gmm_jit", lambda x, w, **kw: seen.update(kw))
    for (E, C, D, F), dtype in [((1, 1024, 6144, 32768), jnp.bfloat16), ((2, 64, 128, 256), jnp.float32)]:
        ops.moe_gmm(jax.ShapeDtypeStruct((E, C, D), dtype), jax.ShapeDtypeStruct((E, D, F), dtype))
        shape = {"E": E, "C": C, "D": D, "F": F}
        registry = kreg.get_kernel("moe_gmm").defaults(shape, jnp.dtype(dtype).name)
        rule = gmm.default_blocks(C, D, F, jnp.dtype(dtype).itemsize)
        assert (seen["block_c"], seen["block_f"], seen["block_d"]) == rule
        assert registry == dict(zip(("block_c", "block_f", "block_d"), rule))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_rule_tiles_match_reference(dtype):
    """The rule's tiles with two blocks on F and on D: the float32
    accumulator carries across D and every F block lands in place."""
    from repro.kernels import moe_gmm as gmm

    E, C, D, F = 2, 256, 1024, 2048
    bc, bf, bd = gmm.default_blocks(C, D, F, jnp.dtype(dtype).itemsize)
    assert F // bf > 1 and D // bd > 1
    x = jnp.asarray(RNG.normal(size=(E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(size=(E, D, F)) / D**0.5, dtype)
    got = ops.moe_gmm(x, w)
    want = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_model_path_with_pallas_matches_xla():
    """mamba block computed via the Pallas kernel == the XLA path."""
    from repro.configs import get_arch
    from repro.models import ssm
    from repro.models.model import Model

    cfg = get_arch("falcon-mamba-7b").reduced().replace(ssm_chunk=8)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    x = jnp.asarray(RNG.normal(size=(2, 16, cfg.d_model)), jnp.float32)
    block = jax.tree.map(lambda p: p[0], params["blocks"])
    y_xla = ssm.mamba_block(cfg, x, block, use_pallas=False)
    y_pallas = ssm.mamba_block(cfg, x, block, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_pallas), rtol=2e-4, atol=2e-4)


def test_rglru_seq_with_pallas_matches_xla():
    """RecurrentGemma's recurrence via the Pallas kernel == the XLA scan,
    over a sequence long enough for several sequence blocks."""
    from repro.models import rglru

    B, L, nb, bd = 2, 512, 2, 128
    dr = nb * bd
    p = {
        "w_rec_gate": jnp.asarray(RNG.normal(size=(nb, bd, bd)) / bd**0.5, jnp.float32),
        "b_rec_gate": jnp.zeros((dr,), jnp.float32),
        "w_in_gate": jnp.asarray(RNG.normal(size=(nb, bd, bd)) / bd**0.5, jnp.float32),
        "b_in_gate": jnp.zeros((dr,), jnp.float32),
        "lam": jnp.asarray(RNG.normal(size=(dr,)), jnp.float32),
    }
    u = jnp.asarray(RNG.normal(size=(B, L, dr)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, dr)), jnp.float32)
    y_xla, h_xla = rglru.rglru_seq(p, u, h0, use_pallas=False)
    y_pallas, h_pallas = rglru.rglru_seq(p, u, h0, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_pallas), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_xla), np.asarray(h_pallas), rtol=2e-4, atol=2e-4)
