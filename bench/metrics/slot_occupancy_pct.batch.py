"""Busy slot-steps over all slot-steps in the window (%), from the
session's counters."""
from _window import delta


def read(run):
    busy, total = delta(run, "busy"), delta(run, "total")
    if busy is None or not total:
        return None
    return 100.0 * busy / (run.slots * total)
