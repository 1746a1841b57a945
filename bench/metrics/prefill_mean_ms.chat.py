"""Mean time from the first grant of a slot to the first token (ms), over
the requests submitted in the window: one-token prefill of the prompt's
unshared part, chunk-granular. A request with no first token by the end
of the run counts at its time in the slot then; one never admitted counts
0. From the program's request records."""
from _telemetry import stages_ms


def read(run):
    stages = stages_ms(run)
    return None if stages is None else stages[1]
