"""Share of the roofline of the flash-attention calls in the traced window:
their least time on this chip (work/flash_attention.py, peaks.json) over
their device time (%)."""
from bench import readers


def read(rec):
    return readers.roofline_pct(rec, "flash_attention")
