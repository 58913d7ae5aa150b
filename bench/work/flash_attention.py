"""Algorithmic work of one causal GQA flash-attention call, from its shapes.

FLOPs: the two matrix products (q k^T and p v), 2 * hd each per attended
(query, key) pair, over the pairs the mask keeps: L (L + 1) / 2 per head
when causal.  Bytes: q, k, v read once and the output written once, in the
payload's dtype.  Neither depends on the block configuration.
"""

ISZ = {"bfloat16": 2, "float32": 4}
HLO = {"bfloat16": "bf16", "float32": "f32"}


def work(shape: dict, dtype: str) -> tuple[float, float]:
    B, H, KV, L, hd = shape["B"], shape["H"], shape["KV"], shape["L"], shape["hd"]
    pairs = L * (L + 1) / 2 if shape.get("causal", True) else L * L
    if shape.get("window") is not None:
        w = shape["window"]
        pairs = sum(min(i + 1, w) for i in range(L))
    flops = 4.0 * B * H * pairs * hd
    nbytes = ISZ[dtype] * B * L * hd * (2 * H + 2 * KV)
    return flops, float(nbytes)


def hlo_types(shape: dict, dtype: str) -> tuple[list[str], list[str]]:
    """HLO types the call's text names: its operands, then its results."""
    B, H, KV, L, hd = shape["B"], shape["H"], shape["KV"], shape["L"], shape["hd"]
    t = HLO[dtype]
    kv = f"{t}[{B},{KV},{L},{hd}]"
    return [f"{t}[{B},{H},{L},{hd}]", kv, kv], [f"{t}[{B},{H},{L},{hd}]"]
