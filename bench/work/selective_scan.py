"""Algorithmic work of one Mamba selective-scan chunk, from its shapes.

Per (step, channel, state) the recurrence does exp(dt a), da h, dt x b
(dt x shared over the state), the add, and h c with its sum: 6 operations,
exp counted as one.  Bytes: x (payload dtype), dt, b, c, a, h0 read once
and y, h_last written once, all float32 but x.
"""

ISZ = {"bfloat16": 2, "float32": 4}
HLO = {"bfloat16": "bf16", "float32": "f32"}


def work(shape: dict, dtype: str) -> tuple[float, float]:
    B, ck, di, N = shape["B"], shape["chunk"], shape["di"], shape["N"]
    flops = 6.0 * B * ck * di * N
    nbytes = (
        ISZ[dtype] * B * ck * di  # x
        + 4 * B * ck * di  # dt
        + 4 * 2 * B * ck * N  # b, c
        + 4 * di * N  # a
        + 4 * B * di * N  # h0
        + 4 * B * ck * di  # y
        + 4 * B * di * N  # h_last
    )
    return flops, float(nbytes)


def hlo_types(shape: dict, dtype: str) -> tuple[list[str], list[str]]:
    """HLO types the call's text names: x and dt in, y out.  The small
    operands and the state are left out: the kernel lays them out as it
    chooses (a and h as (N, di))."""
    B, ck, di = shape["B"], shape["chunk"], shape["di"]
    return [f"{HLO[dtype]}[{B},{ck},{di}]", f"f32[{B},{ck},{di}]"], [f"f32[{B},{ck},{di}]"]
