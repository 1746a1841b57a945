"""Process start to the window's opening (s): imports, weights, warm-up of
every program the traffic drives, and the traffic's lead-in."""


def read(run):
    return run.setup_s
