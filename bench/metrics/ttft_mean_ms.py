"""Mean time to first token over the measured requests (ms), each timed
from when it was due, not from when it was submitted; one with no first
token by the end of the run counts at its age then."""
from _window import mean_ms, ttft_s


def read(run):
    return mean_ms(ttft_s(run))
