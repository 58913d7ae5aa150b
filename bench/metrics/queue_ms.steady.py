"""Mean time a task waits in the dispatcher's ready queue: ``queued``
(``Dispatcher.enqueue``) to ``batched`` (its micro-batch taken, the 2-ms
window included), in ms."""
from bench import readers


def read(rec):
    return readers.mean_ms(rec, "queued", "batched")
