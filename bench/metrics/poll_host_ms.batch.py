"""Host time per chunk outside the chunk's dispatch-to-sync span (ms):
over the polls that start in the window, the sum of each `serve.poll`
span less its `serve.chunk` span, over the chunks run. The program's own
spans, on the host clock."""
from _telemetry import poll_host_ms as read  # noqa: F401
