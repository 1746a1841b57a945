"""Busy slot-steps that consumed prompt tokens over busy slot-steps in the
window (%), from the session's counters: one-token prefill's share."""
from _window import delta, prompt_steps


def read(run):
    busy, fed = delta(run, "busy"), prompt_steps(run)
    if not busy or fed is None:
        return None
    return 100.0 * fed / busy
