"""A closed backlog that keeps `depth` requests outstanding, so the slot
pool is always full and the queue never empties. The first `slots`
requests get the residual lengths of requests already under way, so that
the pool starts in its steady mix. The window's metric is the rate of
tokens delivered in it, so every request counts as measured.

Parameters: `depth`, `prompt_tokens` and `answer_tokens` (log-normal:
`median`, `sigma`, `min`, `max`).
"""

from __future__ import annotations

import math

import numpy as np

from harness import traffic as tl


class Mix(tl.Traffic):

    def __init__(self, params, cell, **kw):
        super().__init__(params, cell, **kw)
        self._issued = 0
        self.outstanding = 0

    @staticmethod
    def prompt_range(params, max_context):
        p = params["prompt_tokens"]
        return p["min"], p["max"]

    def _residual_answer(self) -> int:
        """A request under way: its length drawn in proportion to length
        (the length-biased law of the request a slot holds at a random
        instant), and what remains of it uniform over that length."""
        spec = self.params["answer_tokens"]
        cands = [tl.lognormal_int(spec, self._u["residual"]())
                 for _ in range(16)]
        w = np.asarray(cands, np.float64)
        pick = cands[int(np.searchsorted(np.cumsum(w) / w.sum(),
                                         self._u["residual"]()))]
        return max(1, math.ceil(self._u["residual"]() * pick))

    def due(self, now):
        out = []
        while self.outstanding + len(out) < int(self.params["depth"]):
            p = tl.lognormal_int(self.params["prompt_tokens"],
                                 self._u["prompt"]())
            n = (self._residual_answer() if self._issued < self.slots
                 else self._answer())
            out.append(self._req(now, self._tokens(p), n, measured=True))
            self._issued += 1
        self.outstanding += len(out)
        return out

    def finished(self, req, served, now):
        self.outstanding -= 1
