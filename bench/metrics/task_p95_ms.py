"""95th percentile due-to-completion latency of the tasks due in the
window (ms); failed or unfinished tasks count beyond every other."""
from bench import readers


def read(rec):
    return readers.percentile_ms(rec, 95)
