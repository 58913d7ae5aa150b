"""Share of the roofline of the expert-matmul calls in the traced window:
their least time on this chip (work/moe_gmm.py, peaks.json) over their
device time (%)."""
from bench import readers


def read(rec):
    return readers.roofline_pct(rec, "moe_gmm")
