"""Mean time from a task's due time to its hand-over to a provider
(the task's ``state:SUBMITTED`` stamp), in ms: generator, ready queue,
micro-batch window, bind, partition, submit."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, None, "state:SUBMITTED")
