"""Share of the traced window in which no operation ran on the device
(%), from the profiler trace."""
from _window import idle_pct as read  # noqa: F401
