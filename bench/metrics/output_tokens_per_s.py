"""Tokens delivered in the window over the window's length (tokens/s)."""


def read(run):
    w = run.window
    return w.tokens_in_window / (w.close_t - w.open_t)
