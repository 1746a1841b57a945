"""Share of the decode steps' device time that the recurrent state alone
must take (%): the bytes of the SSM states and conv tails that the traced
steps' busy slot-steps read and write once each
(`counts.state_bytes_needed`, from the model's shapes), over the chip's
HBM bandwidth, over the device time of the chunk programs in the trace.
None for an architecture without recurrent state."""
from harness import trace

from _window import CHUNK_PROGRAM, traced_probe


def read(run):
    state_bytes = getattr(run.counts, "state_bytes_needed", None)
    tot = traced_probe(run)
    if state_bytes is None or tot is None or run.peak is None \
            or run.trace is None or not tot["steps"]:
        return None
    t, runs = trace.module_time(run.trace, CHUNK_PROGRAM)
    rows = run.window.traced[3] - run.window.traced[2]
    if runs != rows * run.trace.n_devices:
        raise ValueError(f"the trace holds {runs} runs of {CHUNK_PROGRAM} "
                         f"where the probe dispatched {rows} chunks")
    return 100.0 * state_bytes(run.cfg, tot["slot_steps"]) \
        / run.peak.hbm_bytes_per_s / t
