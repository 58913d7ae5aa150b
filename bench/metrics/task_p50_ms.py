"""Median due-to-completion latency of the tasks due in the window (ms)."""
from bench import readers


def read(rec):
    return readers.percentile_ms(rec, 50)
