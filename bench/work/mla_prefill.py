"""Algorithmic work of one MLA prefill kernel call, from its shapes.

The call is causal attention of the last ``Lq`` queries of an ``Lk``-token
sequence: query i (at key position Lk - Lq + i) attends to Lk - Lq + i + 1
keys.  FLOPs: per attended pair and head, the score in two parts (2 * dn
and 2 * dr) and p . v (2 * dv).  Bytes: the kernel's operands read once
(q_nope, q_rope; the decompressed k_nope and v; k_rope once, shared by the
heads) and its output written once, in the payload's dtype.  The XLA
decompression beside the kernel is not counted.  Neither number depends
on the block configuration.
"""

ISZ = {"bfloat16": 2, "float32": 4}
HLO = {"bfloat16": "bf16", "float32": "f32"}


def pairs(lq: int, lk: int) -> float:
    """(query, key) pairs one head attends: sum of Lk - Lq + i + 1 over i."""
    return lq * (lk - lq) + lq * (lq + 1) / 2


def work(shape: dict, dtype: str) -> tuple[float, float]:
    B, H, Lq, Lk = shape["B"], shape["H"], shape["Lq"], shape["Lk"]
    dn, dr, dv = shape["dn"], shape["dr"], shape["dv"]
    flops = 2.0 * (dn + dr + dv) * B * H * pairs(Lq, Lk)
    nbytes = ISZ[dtype] * B * (H * Lq * (dn + dr + dv) + H * Lk * (dn + dv) + Lk * dr)
    return flops, float(nbytes)


def hlo_types(shape: dict, dtype: str) -> tuple[list[str], list[str]]:
    """HLO types the call's text names: its operands, then its results."""
    B, H, Lq, Lk = shape["B"], shape["H"], shape["Lq"], shape["Lk"]
    dn, dr, dv = shape["dn"], shape["dr"], shape["dv"]
    t = HLO[dtype]
    return (
        [f"{t}[{B},{H},{Lq},{dn}]", f"{t}[{B},{H},{Lq},{dr}]", f"{t}[{B},{H},{Lk},{dn}]",
         f"{t}[{B},1,{Lk},{dr}]", f"{t}[{B},{H},{Lk},{dv}]"],
        [f"{t}[{B},{H},{Lq},{dv}]"],
    )
