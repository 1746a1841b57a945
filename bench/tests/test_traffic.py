"""The general traffic generator: same seed, same schedule; lengths within
their clips; chat-prefix turns extend the turns before them; chat-short
prompts share no full page; every seed sends the same set of sizes before
the close, and what falls due in the window is measured."""

from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

from conftest import BENCH
from harness import traffic as tl

MIXES = {n: json.loads((BENCH / "traffic" / f"{n}.json").read_text())
         for n in ("chat-prefix", "chat-short", "batch-decode")}
CELL = {"rate_per_s": 4.0}
SEED = 2 ** 31 + 977
LEAD_IN, SECONDS = 10.0, 20.0


def make(mix: str, seed: int = SEED) -> tl.Traffic:
    t = tl.load(MIXES[mix], CELL, vocab=151936, seed=seed, max_context=2048,
                slots=32, lead_in=LEAD_IN, seconds=SECONDS)
    t.start()
    return t


def schedule(mix: str, seed: int = SEED, until: float = 60.0):
    """Requests due in `until` seconds, each finished at once with a
    served answer of its own length drawn from the seed."""
    t = make(mix, seed)
    rng = np.random.default_rng(seed)
    out, now = [], 0.0
    while now < until:
        for r in t.due(now):
            out.append(r)
            t.finished(r, rng.integers(0, 151936, r.max_new), now)
        now += 0.05
    return out


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_schedule(mix):
    a, b = schedule(mix), schedule(mix)
    assert len(a) == len(b) > 50
    for x, y in zip(a, b):
        assert (x.due, x.max_new, x.session, x.turn, x.measured) == \
            (y.due, y.max_new, y.session, y.turn, y.measured)
        assert np.array_equal(x.prompt, y.prompt)
    c = schedule(mix, seed=SEED + 1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_length_clips_hold(mix):
    p = MIXES[mix]
    reqs = schedule(mix)
    ans = p["answer_tokens"]
    for r in reqs:
        assert r.max_new <= ans["max"]
        assert r.prompt.size + r.max_new <= 2048
        if mix == "batch-decode" and r.rid >= 32:
            assert r.max_new >= ans["min"]
        if "prompt_tokens" in p:
            assert p["prompt_tokens"]["min"] <= r.prompt.size \
                <= p["prompt_tokens"]["max"]


def test_chat_prefix_turns_extend_earlier_turns():
    reqs = schedule("chat-prefix", until=120.0)
    t = make("chat-prefix")
    systems = t.system_prompts
    by_session: dict[int, list] = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    multi = 0
    for turns in by_session.values():
        assert [r.turn for r in turns] == list(range(1, len(turns) + 1))
        assert len({r.measured for r in turns}) == 1
        assert turns[0].measured == (LEAD_IN <= turns[0].due
                                     < LEAD_IN + SECONDS)
        first = turns[0].prompt
        assert any(np.array_equal(first[:s.size], s) for s in systems)
        for prev, nxt in zip(turns, turns[1:]):
            multi += 1
            n = prev.prompt.size + prev.max_new
            assert nxt.prompt.size > n
            assert np.array_equal(nxt.prompt[:prev.prompt.size], prev.prompt)
            assert nxt.due > prev.due
    assert multi > 20


def test_chat_short_shares_no_full_page():
    reqs = schedule("chat-short", until=120.0)
    firsts = {r.prompt[:16].tobytes() for r in reqs}
    assert len(firsts) == len(reqs) > 200


@pytest.mark.parametrize("mix", ["chat-prefix", "chat-short"])
def test_window_measures_the_same_sizes_for_every_seed(mix):
    """The lead-in and the window each hold rate x span arrivals; the
    sessions that start before the close send one set of sizes for every
    seed, at other times; every request of an arrival in the window is
    measured, and none other."""
    close = LEAD_IN + SECONDS
    seen = []
    for seed in (SEED, SEED + 1, 7):
        reqs = schedule(mix, seed, until=200.0)
        starts = [r for r in reqs if r.turn <= 1]
        assert sum(r.due < LEAD_IN for r in starts) \
            == CELL["rate_per_s"] * LEAD_IN
        assert sum(LEAD_IN <= r.due < close for r in starts) \
            == CELL["rate_per_s"] * SECONDS
        start = {r.session: r.due for r in starts}
        assert all(r.measured == (LEAD_IN <= start[r.session] < close)
                   for r in reqs if mix == "chat-prefix")
        assert all(r.measured == (LEAD_IN <= r.due < close)
                   for r in reqs if mix == "chat-short")
        early = {r.session for r in starts if r.due < close}
        # every turn of a session that starts before the close
        sent = [(r.prompt.size, r.max_new) for r in reqs
                if (r.session in early if mix == "chat-prefix"
                    else r.due < close)]
        seen.append((Counter(sent), [r.due for r in starts]))
    for other in seen[1:]:
        assert other[0] == seen[0][0]
    assert seen[0][1] != seen[1][1]


def test_open_loop_measures_until_its_window_is_served():
    t = make("chat-short")
    assert t.measuring()
    now, last, rng = 0.0, 0.0, np.random.default_rng(1)
    while t.measuring():
        for r in t.due(now):
            last = max(last, r.due) if r.measured else last
            t.finished(r, rng.integers(0, 9, r.max_new), now)
        now += 0.05
    assert LEAD_IN + SECONDS - 2 < last <= now < LEAD_IN + SECONDS + 0.1
    assert t.unissued == 0


def test_stratified_draws_keep_the_work_fixed():
    """Over three seeds the mean drawn answer length, outside the window
    too, differs by far less than independent draws would."""
    means = [np.mean([r.max_new for r in schedule("chat-short", s)])
             for s in (1, 2, 3)]
    assert max(means) / min(means) < 1.03


def test_backlog_keeps_depth_and_starts_with_residuals():
    t = make("batch-decode")
    first = t.due(0.0)
    assert len(first) == MIXES["batch-decode"]["depth"]
    assert all(r.measured for r in first) and not t.measuring()
    assert t.due(0.0) == []
    t.finished(first[0], np.zeros(1, np.int32), 0.1)
    assert len(t.due(0.1)) == 1
    residual = np.mean([r.max_new for r in first[:32]])
    fresh = np.mean([r.max_new for r in first[32:]])
    assert residual != fresh


def test_a_mix_names_a_kind_found_by_name():
    with pytest.raises(FileNotFoundError):
        tl.kind_module("no-such-kind")
    assert tl.prompt_range(MIXES["chat-short"], 2048) == (32, 512)
