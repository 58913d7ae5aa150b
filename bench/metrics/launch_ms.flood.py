"""Mean host time to launch a kernel task: ``exec_start`` to ``launched``
(program lookup, issuing the operand build and the kernel), in ms."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "exec_start", "launched")
