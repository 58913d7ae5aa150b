"""Mean time from the chip's answer to completion: ``synced`` to
``exec_done`` (the checksum's reduction and scalar read-back, then the
task's completion), in ms."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "synced", "exec_done")
