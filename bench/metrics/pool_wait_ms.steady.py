"""Mean time a task waits at its provider for a slot: ``state:SUBMITTED``
to ``exec_start``, in ms."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "state:SUBMITTED", "exec_start")
