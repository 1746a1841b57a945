"""Arithmetic the readers of the program's own spans and request records
share. The program keeps them in one recorder per process
(`repro.runtime.telemetry.recorder()`), on the host clock the window is
measured by, and they outlive the server, so a reader slices them by the
window. A program without that recorder gives every reader None."""

POLL, CHUNK, PAGE_READ = "serve.poll", "serve.chunk", "serve.page_read"


def recorder():
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    return telemetry.recorder()


def window_polls(run):
    """[(poll span, its chunk span or None)] for the polls that started in
    the window, or None without a recorder. A poll that ran no chunk (an
    empty queue) has None."""
    rec = recorder()
    if rec is None:
        return None
    w = run.window
    if rec.spans_dropped and rec.spans()[0].start > w.open_t:
        raise ValueError("the recorder's span ring no longer holds the "
                         "start of the window")
    spans = rec.spans(w.open_t, w.end_t)
    chunks = {(s.session, s.chunk): s for s in spans if s.name == CHUNK}
    out = []
    for p in spans:
        if p.name != POLL or p.start > w.close_t:
            continue
        c = chunks.get((p.session, p.chunk))
        inside = c is not None and p.start <= c.start and c.end <= p.end
        out.append((p, c if inside else None))
    return out


def per_chunk_ms(run, seconds):
    """`seconds(poll, chunk)` summed over the window's polls, in ms per
    chunk run; None where no chunk ran."""
    polls = window_polls(run)
    if not polls:
        return None
    n = sum(c is not None for _, c in polls)
    if not n:
        return None
    return 1e3 * sum(seconds(p, c) for p, c in polls) / n


def poll_host_ms(run):
    """Host time of a poll outside its chunk (dispatch to sync), per
    chunk: the time the device waits for the host between chunks."""
    return per_chunk_ms(run, lambda p, c: (p.end - p.start)
                        - (0.0 if c is None else c.end - c.start))


def page_readback_ms(run):
    """Time of the page readbacks and their digests inside the window's
    polls, per chunk."""
    rec = recorder()
    if rec is None:
        return None
    w = run.window
    reads = [s for s in rec.spans(w.open_t, w.end_t) if s.name == PAGE_READ]

    def seconds(p, c):
        return sum(s.end - s.start for s in reads
                   if s.session == p.session and p.start <= s.start
                   and s.end <= p.end)
    return per_chunk_ms(run, seconds)


def window_requests(run):
    """Request records of the requests submitted in the window, or None
    without a recorder."""
    rec = recorder()
    if rec is None:
        return None
    w = run.window
    return [r for r in rec.requests()
            if w.open_t <= r.submitted_at <= w.close_t]


def stages_ms(run):
    """Mean queue wait (submitted to first slot) and mean prefill (first
    slot to first token) in ms over the requests submitted in the window.
    A stage not over by the end of the run counts at its length then; a
    request never admitted counts its whole age as queue, none as
    prefill. So the two add up to the mean time from submission to first
    token."""
    reqs = window_requests(run)
    if not reqs:
        return None
    end = run.window.end_t

    def upto(t):
        return end if t is None or t > end else t
    queue = prefill = 0.0
    for r in reqs:
        admitted = upto(r.admitted_at)
        queue += admitted - r.submitted_at
        if r.admitted_at is not None and r.admitted_at <= end:
            prefill += upto(r.first_token_at) - admitted
    n = len(reqs)
    return 1e3 * queue / n, 1e3 * prefill / n


def kv_pages_peak_pct(run):
    """The most KV pages the slots' tables held at a chunk of the window,
    over the pool's usable pages (%)."""
    polls = window_polls(run)
    if not polls:
        return None
    rec = recorder()
    peak = None
    for _, c in polls:
        if c is None or not c.attrs or "pages_held" not in c.attrs:
            continue
        info = rec.session_info(c.session)
        if not info or not info.get("kv_pages"):
            continue
        pct = 100.0 * c.attrs["pages_held"] / info["kv_pages"]
        peak = pct if peak is None else max(peak, pct)
    return peak
