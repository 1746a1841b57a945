"""90th percentile of the time to first token over the measured requests
(ms), timed as `ttft_mean_ms` is."""
from _window import percentile_ms, ttft_s


def read(run):
    return percentile_ms(ttft_s(run), 90)
