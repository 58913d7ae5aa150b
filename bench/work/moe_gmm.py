"""Algorithmic work of one expert matmul y[e] = x[e] @ w[e], from its shapes.

FLOPs: 2 E C D F.  Bytes: x, w read once and y written once, in the
payload's dtype, whatever the tiling re-fetches.
"""

ISZ = {"bfloat16": 2, "float32": 4}
HLO = {"bfloat16": "bf16", "float32": "f32"}


def work(shape: dict, dtype: str) -> tuple[float, float]:
    E, C, D, F = shape["E"], shape["C"], shape["D"], shape["F"]
    return 2.0 * E * C * D * F, float(ISZ[dtype] * E * (C * D + D * F + C * F))


def hlo_types(shape: dict, dtype: str) -> tuple[list[str], list[str]]:
    """HLO types the call's text names: its operands, then its results."""
    E, C, D, F = shape["E"], shape["C"], shape["D"], shape["F"]
    t = HLO[dtype]
    return [f"{t}[{E},{C},{D}]", f"{t}[{E},{D},{F}]"], [f"{t}[{E},{C},{F}]"]
