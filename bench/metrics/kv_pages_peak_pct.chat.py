"""The share of the reserved KV pool that the traffic uses: the most pages
that running requests' tables held at any chunk of the window (shared
pages once), over the pool's usable pages (%). Higher is better: the
session cell copies the whole pool inside every step, so a pool sized
closer to what traffic holds copies fewer idle pages. From the
`pages_held` gauge on the program's `serve.chunk` spans."""
from _telemetry import kv_pages_peak_pct as read  # noqa: F401
