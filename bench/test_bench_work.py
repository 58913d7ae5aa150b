"""``work/<kernel>.py`` against counts made by hand at small shapes."""
from __future__ import annotations

from bench import spec


def test_flash_attention_causal():
    shape = {"B": 1, "H": 4, "KV": 2, "L": 4, "hd": 8, "causal": True, "window": None}
    flops, nbytes = spec.plugin("work", "flash_attention").work(shape, "bfloat16")
    # 4 heads x 10 attended pairs of 16 (1 + 2 + 3 + 4) x two products of 2 * hd
    assert flops == 4 * 10 * 2 * (2 * 8)
    # q and out: 4 heads x 4 x 8; k and v: 2 heads x 4 x 8; two bytes each
    assert nbytes == 2 * (2 * 4 * 4 * 8 + 2 * 2 * 4 * 8)


def test_flash_attention_full_and_window():
    full = {"B": 2, "H": 2, "KV": 1, "L": 4, "hd": 8, "causal": False, "window": None}
    assert spec.plugin("work", "flash_attention").work(full, "float32")[0] == 2 * 2 * 16 * 4 * 8
    win = {"B": 1, "H": 1, "KV": 1, "L": 4, "hd": 8, "causal": True, "window": 2}
    assert spec.plugin("work", "flash_attention").work(win, "float32")[0] == 7 * 4 * 8  # 1 + 2 + 2 + 2


def test_moe_gmm():
    shape = {"E": 2, "C": 3, "D": 5, "F": 7}
    flops, nbytes = spec.plugin("work", "moe_gmm").work(shape, "bfloat16")
    assert flops == 2 * 2 * 3 * 5 * 7
    assert nbytes == 2 * 2 * (3 * 5 + 5 * 7 + 3 * 7)


def test_selective_scan():
    shape = {"B": 1, "chunk": 2, "di": 3, "N": 4}
    flops, nbytes = spec.plugin("work", "selective_scan").work(shape, "bfloat16")
    assert flops == 6 * 2 * 3 * 4
    # x bf16 (6 values); dt, y float32 (6 each); b, c (8 each); a, h0, h_last (12 each)
    assert nbytes == 2 * 6 + 4 * (6 + 6 + 8 + 8 + 12 + 12 + 12)
    assert spec.plugin("work", "selective_scan").work(shape, "float32")[1] == nbytes + 2 * 6


def test_work_ignores_block_config():
    """The yardstick takes shapes and a dtype only: no block sizes."""
    import inspect

    for kernel in ("flash_attention", "moe_gmm", "selective_scan"):
        assert list(inspect.signature(spec.plugin("work", kernel).work).parameters) == ["shape", "dtype"]
