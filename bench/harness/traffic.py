"""The general traffic generator. A traffic mix is a data file of parameters
(`bench/traffic/<mix>.json`) whose `kind` names the generator that reads
it, `bench/traffic/kinds/<kind>.py`; a cell's load level, its arrival
rate, comes from the cell's own file (`bench/cells/<cell>.json`). This
module holds what the kinds share: the draws, the request record, and the
open loop.

Every length, gap and choice is drawn by inverse CDF from stratified
uniforms: each block of `STRATUM` draws takes one value from each of
`STRATUM` equal strata, in an order drawn from the seed. An open loop's
lead-in and its measured window are blocks of their own: exactly
`rate x span` arrivals each, whose gaps are one fixed set (the midpoints
of the exponential's strata) in an order drawn from the seed, and whose
sizes are one fixed set of draws (`midpoints`) dealt out in an order
drawn from the seed. So every seed sends the same work before the close,
in another order and at other times. Token ids are uniform over the
vocabulary. The program receives only the requests.
"""

from __future__ import annotations

import dataclasses
import heapq
import importlib.util
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

STRATUM = 64
_NORMAL = NormalDist()
KINDS = Path(__file__).resolve().parents[1] / "traffic" / "kinds"
# the seed's streams, in the order they are spawned
STREAMS = ("tokens", "gap", "prompt", "answer", "turns", "think", "system",
           "residual", "times")


class Strata:
    """Stratified uniforms in (0, 1): a permuted block at a time."""

    def __init__(self, rng: np.random.Generator, block: int = STRATUM):
        self._rng = rng
        self._block = block
        self._buf: list[float] = []

    def __call__(self) -> float:
        if not self._buf:
            k = self._block
            u = (np.arange(k) + self._rng.random(k)) / k
            self._buf = list(self._rng.permutation(u))
        return _clip(self._buf.pop())


def midpoints(n: int, key: int) -> list[float]:
    """`n` uniforms at the midpoints of `n` equal strata, in an order fixed
    by `key` and `n` alone: draws paired from several such lists are the
    same set for every seed."""
    u = (np.arange(n) + 0.5) / n
    return [_clip(x) for x in np.random.default_rng([n, key]).permutation(u)]


def _clip(u: float) -> float:
    return min(max(float(u), 1e-12), 1.0 - 1e-12)


def lognormal_int(spec: dict, u: float) -> int:
    """Log-normal with `median` and `sigma` (of the log), rounded and
    clipped to [`min`, `max`]."""
    x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    return int(min(max(round(x), spec["min"]), spec["max"]))


def exponential(mean: float, u: float) -> float:
    return -mean * math.log1p(-u)


def zipf_index(n: int, s: float, u: float) -> int:
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w) / w.sum()
    return int(min(np.searchsorted(cdf, u, side="right"), n - 1))


def uniform_int(lo: int, hi: int, u: float) -> int:
    return min(lo + int(u * (hi - lo + 1)), hi)


@dataclasses.dataclass
class Req:
    rid: int
    due: float                  # seconds after the traffic started
    prompt: np.ndarray          # int32 token ids
    max_new: int
    measured: bool = False      # counted in the window's metrics
    session: int = -1
    turn: int = 0


def page_counts(prompt_range: tuple[int, int], page_size: int) -> range:
    """Full-page counts a published or matched prompt can have: the shapes
    of the per-page programs a mix drives."""
    lo, hi = prompt_range
    return range(max(lo // page_size, 1), hi // page_size + 1)


class Traffic:
    """Requests of one mix, from one seed. The driver asks `due(now)` for
    the requests due by `now` and reports each finished request with
    `finished(req, tokens, now)`; times are seconds after `start()`. The
    window opens `lead_in` seconds after the start and lasts `seconds`.

    A kind sets `prompt_range(params, max_context)` (the shortest and the
    longest prompt it sends) and the methods below. `drains`: the run goes
    on after the window until the measured requests have finished."""

    drains = False

    def __init__(self, params: dict, cell: dict, *, vocab: int, seed: int,
                 max_context: int, slots: int, lead_in: float,
                 seconds: float):
        self.params = params
        self.kind = params["kind"]
        self.vocab = vocab
        self.max_context = max_context
        self.slots = slots
        self.lead_in = float(lead_in)
        self.seconds = float(seconds)
        self.rate = float(cell.get("rate_per_s", 0.0))
        streams = np.random.SeedSequence(int(seed)).spawn(len(STREAMS))
        self._tok = np.random.default_rng(streams[0])
        self._times = np.random.default_rng(streams[-1])
        self._u = {n: Strata(np.random.default_rng(s))
                   for n, s in zip(STREAMS[1:-1], streams[1:-1])}
        self._next_rid = 0
        self.unissued = 0               # measured requests not yet due
        self.system_prompts: list[np.ndarray] = []

    def _tokens(self, n: int) -> np.ndarray:
        return self._tok.integers(0, self.vocab, n, dtype=np.int32)

    def _req(self, due: float, prompt, max_new: int, **kw) -> Req:
        self._next_rid += 1
        return Req(self._next_rid - 1, due, prompt, max_new, **kw)

    def _answer(self, u: float | None = None) -> int:
        return lognormal_int(self.params["answer_tokens"],
                             self._u["answer"]() if u is None else u)

    def start(self) -> None:
        pass

    def due(self, now: float) -> list[Req]:
        raise NotImplementedError

    def next_due(self) -> float | None:
        """When the next request falls due (None: it depends on a finish)."""
        return None

    def finished(self, req: Req, served: np.ndarray, now: float) -> None:
        pass

    def measuring(self) -> bool:
        """True while a measured request is due later or unfinished."""
        return False


class OpenLoop(Traffic):
    """Poisson arrivals at the cell's rate. Before the close, two blocks:
    the lead-in's and the window's, each `round(rate x span)` arrivals
    with one fixed set of gaps and of draws, in orders drawn from the seed
    (`arrive(t, block)`). After the close, a Poisson process with
    stratified draws (`block` None), so the load stays while the window's
    requests finish. The requests of the arrivals in the window are
    measured; the run waits for every one of them."""

    drains = True

    def __init__(self, params, cell, **kw):
        super().__init__(params, cell, **kw)
        if self.rate <= 0:
            raise ValueError("an open-loop mix needs the cell's rate_per_s")
        self.close = self.lead_in + self.seconds
        self._heap: list = []           # (due, rid, Req)
        self._t_next = math.inf         # the next arrival after the close
        self._blocks: list = []         # (time, draws) of the arrivals
        #                                 before the close still to come,
        #                                 last first
        self._open = 0                  # measured requests not finished

    def arrive(self, t: float, block: dict | None) -> None:
        """One arrival at `t`: push its request(s) with `_push`. `block`
        holds its draws if it comes before the close (None after it).
        An arrival in the window announces its measured requests with
        `expect`."""
        raise NotImplementedError

    def block_draws(self, n: int) -> list[dict]:
        """The draws of a block of `n` arrivals, one dict each, the same
        for every seed (built from `midpoints`)."""
        raise NotImplementedError

    def in_window(self, t: float) -> bool:
        return self.lead_in <= t < self.close

    def _push(self, req: Req) -> None:
        heapq.heappush(self._heap, (req.due, req.rid, req))

    def expect(self, n: int) -> None:
        """`n` more measured requests will be pushed."""
        self._open += n
        self.unissued += n

    def _poisson_after(self, t: float) -> float:
        return t + exponential(1.0 / self.rate, self._u["gap"]())

    def _block(self, start: float, span: float) -> list:
        """`round(rate x span)` arrivals in [start, start + span): the
        exponential's strata midpoints as gaps, in an order drawn from the
        seed and scaled to the span, and the block's draws dealt out in an
        order drawn from the seed."""
        n = int(round(self.rate * span))
        if n == 0:
            return []
        gaps = self._times.permutation(
            [exponential(1.0, u) for u in midpoints(n + 1, 255)])
        times = start + span * np.cumsum(gaps)[:n] / gaps.sum()
        draws = self.block_draws(n)
        order = self._times.permutation(n)
        return [(float(t), draws[i]) for t, i in zip(times, order)]

    def start(self) -> None:
        self._blocks = (self._block(0.0, self.lead_in)
                        + self._block(self.lead_in, self.seconds))
        self._blocks.reverse()
        self._t_next = self._poisson_after(self.close)

    def _fill(self, now: float) -> None:
        while self._blocks and self._blocks[-1][0] <= now:
            self.arrive(*self._blocks.pop())
        while self._t_next <= now:
            self.arrive(self._t_next, None)
            self._t_next = self._poisson_after(self._t_next)

    def due(self, now: float) -> list[Req]:
        self._fill(now)
        out = []
        while self._heap and self._heap[0][0] <= now:
            req = heapq.heappop(self._heap)[2]
            self.unissued -= req.measured
            out.append(req)
        return out

    def next_due(self) -> float | None:
        t = self._blocks[-1][0] if self._blocks else self._t_next
        return min(t, self._heap[0][0]) if self._heap else t

    def finished(self, req: Req, served: np.ndarray, now: float) -> None:
        self._open -= req.measured

    def measuring(self) -> bool:
        return self._open > 0 or bool(self._blocks)


def load(params: dict, cell: dict, **kw) -> Traffic:
    """The generator of the mix's `kind`, `bench/traffic/kinds/<kind>.py`,
    over the mix's parameters."""
    return kind_module(params["kind"]).Mix(params, cell, **kw)


def kind_module(kind: str):
    path = KINDS / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic kind {kind!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_" + kind.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prompt_range(params: dict, max_context: int) -> tuple[int, int]:
    return kind_module(params["kind"]).Mix.prompt_range(params, max_context)
