"""Kernel tasks that completed inside the window with a correct answer,
per second of the window."""


def read(rec):
    end = rec.window_start + rec.seconds
    return sum(t.ok and t.done <= end for t in rec.tasks) / rec.seconds
