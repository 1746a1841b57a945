"""Model FLOPs of the window's busy slot-steps (2 per matmul parameter,
unembedding included, plus attention over the attended positions) over
the window and the chip's bf16 peak (%)."""
from _window import step_mfu as read  # noqa: F401
