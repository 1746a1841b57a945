"""Prompt tokens served from shared prefix pages over prompt tokens
admitted in the window (%), from the KV pool's counter of skipped
prefill tokens and the session's step counters."""
from _window import delta, prompt_steps


def read(run):
    skipped, fed = delta(run, "skipped"), prompt_steps(run)
    if skipped is None or fed is None or skipped + fed <= 0:
        return None
    return 100.0 * skipped / (skipped + fed)
