"""Share of the roofline of the selective-scan calls in the traced window:
their least time on this chip (work/selective_scan.py, peaks.json) over
their device time (%)."""
from bench import readers


def read(rec):
    return readers.roofline_pct(rec, "selective_scan")
