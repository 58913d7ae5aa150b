"""Broker phase seconds in the window (bind, partition, serialize, submit:
``Hydra.phase_totals``) per task due in the window, in us."""


def read(rec):
    if not rec.tasks:
        return None
    return 1e6 * sum(rec.phase_s.values()) / len(rec.tasks)
