"""Mean wait for the chip: ``launched`` to ``synced`` (the operand build and
the kernel on the device, behind other tasks' work, and interpreter-lock
waits), in ms."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "launched", "synced")
