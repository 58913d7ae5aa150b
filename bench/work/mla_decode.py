"""Algorithmic work of one MLA decode kernel call (absorbed), from its shapes.

Every head's absorbed query (``dc`` wide) and rope query (``dr``) meet all
``Lk`` cached positions.  FLOPs: per head and position, the score against
``[c_kv, k_rope]`` (2 * (dc + dr)) and p . c_kv (2 * dc).  Bytes: the
latent cache c_kv and the rope cache read once, the queries read once and
the latent output written once, in the payload's dtype.  The absorption
and un-absorption beside the kernel are not counted.  Neither number
depends on the block configuration.
"""

ISZ = {"bfloat16": 2, "float32": 4}
HLO = {"bfloat16": "bf16", "float32": "f32"}


def work(shape: dict, dtype: str) -> tuple[float, float]:
    B, H, Lk, dc, dr = shape["B"], shape["H"], shape["Lk"], shape["dc"], shape["dr"]
    flops = 2.0 * B * H * Lk * (dc + dr + dc)
    nbytes = ISZ[dtype] * B * (Lk * (dc + dr) + H * (dc + dr) + H * dc)
    return flops, float(nbytes)


def hlo_types(shape: dict, dtype: str) -> tuple[list[str], list[str]]:
    """HLO types the call's text names: its operands, then its results."""
    B, H, Lk, dc, dr = shape["B"], shape["H"], shape["Lk"], shape["dc"], shape["dr"]
    t = HLO[dtype]
    return (
        [f"{t}[{B},{H},{dc}]", f"{t}[{B},{H},{dr}]", f"{t}[{B},{Lk},{dc}]", f"{t}[{B},{Lk},{dr}]"],
        [f"{t}[{B},{H},{dc}]"],
    )
