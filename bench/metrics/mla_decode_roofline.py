"""Share of the roofline of the MLA decode kernel calls in the traced
window: their least time on this chip (work/mla_decode.py, peaks.json) over
their device time (%)."""
from bench import readers


def read(rec):
    return readers.roofline_pct(rec, "mla_decode")
