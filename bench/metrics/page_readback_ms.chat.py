"""Time per chunk spent reading KV pages back to the host and digesting
them (ms): the `serve.page_read` spans inside the window's polls (admit-
time verify of shared prefix pages, the digests of published prompts, the
scrub), over the chunks run."""
from _telemetry import page_readback_ms as read  # noqa: F401
