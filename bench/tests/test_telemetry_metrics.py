"""The readers of the program's own spans and request records, on whole
`--trace 1` runs on the CPU at smoke widths. The smoke checkout lists the
smoke cells in every metric's `workloads`, so these readers run there as
they run on the chip; on the CPU they are host-clock rehearsals, not chip
numbers."""

from __future__ import annotations

import pytest

from harness import serve
from test_cells import BATCH, CHAT, run_cell

NAMES = {"chat": ("poll_host_ms.chat", "page_readback_ms.chat",
                  "queue_wait_mean_ms.chat", "prefill_mean_ms.chat",
                  "kv_pages_peak_pct.chat"),
         "batch": ("poll_host_ms.batch", "kv_pages_peak_pct.batch")}


@pytest.mark.parametrize("cell,kind", [(CHAT, "chat"), (BATCH, "batch")])
def test_telemetry_metrics_read_on_a_traced_run(smoke_root, capsys,
                                                monkeypatch, cell, kind):
    windows = []
    drive = serve.drive

    def keep(*args, **kw):
        windows.append(drive(*args, **kw))
        return windows[-1]
    monkeypatch.setattr(serve, "drive", keep)
    res = run_cell(smoke_root, capsys, cell, trace=1)
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in NAMES[kind]:
        assert m.get(name) is not None, name
    assert 0.0 < m[f"kv_pages_peak_pct.{kind}"] <= 100.0
    assert m[f"poll_host_ms.{kind}"] > 0.0
    if kind == "chat":
        assert 0.0 <= m["page_readback_ms.chat"] <= m["poll_host_ms.chat"]
        assert m["queue_wait_mean_ms.chat"] >= 0.0
        assert m["prefill_mean_ms.chat"] > 0.0
        # the two stages add up to submission-to-first-token of the
        # window's submissions as the harness saw them (a first token when
        # its poll returned, so at most one poll later; none by the end of
        # the run, at the end)
        from repro.runtime import telemetry
        w = windows[-1]
        seen = [(w.end_t if r.first is None else r.first) - r.submitted
                for r in w.records if w.open_t <= r.submitted <= w.close_t]
        polls = [s.end - s.start for s in telemetry.recorder().spans(
            w.open_t, w.end_t) if s.name == "serve.poll"]
        lag = 1e3 * sum(seen) / len(seen) - (m["queue_wait_mean_ms.chat"]
                                             + m["prefill_mean_ms.chat"])
        assert 0.0 <= lag <= 1e3 * max(polls)
