"""90th percentile over the measured requests of the time per output
token (ms): per request, (last token - first token) / (tokens - 1)."""
from _window import percentile_ms, tpot_s


def read(run):
    return percentile_ms(tpot_s(run), 90)
