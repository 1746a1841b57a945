"""Host time from dispatching a chunk to its results, summed over the
window's chunks, over the steps they ran (ms): the session cell's step
as the host sees it (the program's chunk records)."""
from _window import step_ms as read  # noqa: F401
