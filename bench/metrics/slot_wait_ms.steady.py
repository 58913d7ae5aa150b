"""Mean time a task waits at its provider for an executor thread or pilot
worker: ``state:SUBMITTED`` to ``slot``, in ms.  ``pool_wait_ms`` less this
is the wait behind earlier tasks of the same pod."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "state:SUBMITTED", "slot")
