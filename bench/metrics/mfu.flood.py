"""Algorithmic FLOPs of the kernel calls in the traced window over the peak
FLOP/s of the cell's chips for that window (%): the whole device's share of
its peak, which bounds every kernel's roofline share."""
from bench import readers


def read(rec):
    return readers.mfu_pct(rec)
