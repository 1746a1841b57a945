"""Share of the roofline of the decode steps in the trace (%): bytes the
steps need (weights once per step, the attended K/V rows and the written
ones, from the model's shapes) over 819 GB/s, or their FLOPs over the
bf16 peak where larger, over the device time of the chunk programs."""
from _window import step_roofline as read  # noqa: F401
