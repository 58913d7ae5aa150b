"""Mean time in the kernel runtime: ``exec_start`` to ``exec_done``
(operand build, launch, sync, checksum), in ms."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "exec_start", "exec_done")
