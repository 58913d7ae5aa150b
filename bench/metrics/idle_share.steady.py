"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (%)."""
from bench import readers


def read(rec):
    return readers.idle_pct(rec)
