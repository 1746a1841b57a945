"""Mean time from submission to the first grant of a slot (ms), over the
requests submitted in the window; one not admitted by the end of the run
counts at its age then. From the program's request records."""
from _telemetry import stages_ms


def read(run):
    stages = stages_ms(run)
    return None if stages is None else stages[0]
