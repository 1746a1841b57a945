"""Multi-turn chat sessions under shared system prompts: an open loop of
Poisson session starts. A session picks a system prompt by Zipf
popularity and runs a number of turns; turn k's prompt is everything
before it (turn k-1's prompt and the tokens that were served for it) plus
a new user turn, and is due a think time after turn k-1 finished. A
session ends early where its next turn and answer would not fit in
`max_context`. Every turn of a session that starts in the window is
measured. The sessions that start before the close are one fixed set for
every seed.

Parameters: `system_prompts` {`count`, `tokens`, `popularity_zipf_s`},
`turns` {`min`, `max`}, `user_tokens` and `answer_tokens` (log-normal:
`median`, `sigma`, `min`, `max`), `think_s` {`mean`} (exponential).
"""

from __future__ import annotations

import numpy as np

from harness import traffic as tl


class Mix(tl.OpenLoop):

    def __init__(self, params, cell, **kw):
        super().__init__(params, cell, **kw)
        sp = params["system_prompts"]
        self.system_prompts = [self._tokens(sp["tokens"])
                               for _ in range(sp["count"])]
        self._sessions: dict[int, dict] = {}

    @staticmethod
    def prompt_range(params, max_context):
        return (params["system_prompts"]["tokens"]
                + params["user_tokens"]["min"],
                max_context - params["answer_tokens"]["min"] + 1)

    def block_draws(self, n):
        p = self.params
        turns = [tl.uniform_int(p["turns"]["min"], p["turns"]["max"], u)
                 for u in tl.midpoints(n, 0)]
        system = tl.midpoints(n, 1)
        k = sum(turns)
        user, answer, think = (tl.midpoints(k, key) for key in (2, 3, 4))
        out, at = [], 0
        for i, t in enumerate(turns):
            out.append({"turns": t, "system": system[i],
                        "user": user[at:at + t], "answer": answer[at:at + t],
                        "think": think[at:at + t]})
            at += t
        return out

    def _draw(self, block):
        """The session's system-prompt uniform and per-turn uniforms."""
        if block is not None:
            return block["system"], list(zip(block["user"], block["answer"],
                                             block["think"]))
        p = self.params
        n = tl.uniform_int(p["turns"]["min"], p["turns"]["max"],
                           self._u["turns"]())
        return self._u["system"](), [(self._u["prompt"](),
                                      self._u["answer"](),
                                      self._u["think"]()) for _ in range(n)]

    def arrive(self, t, block):
        """Draw a whole session when it starts: its system prompt, and for
        each turn the user tokens, the answer length and the think time
        after it. Only the served tokens and the finish times are left to
        the run, so the same seed gives the same inputs."""
        p = self.params
        u_system, draws = self._draw(block)
        z = tl.zipf_index(len(self.system_prompts),
                          p["system_prompts"]["popularity_zipf_s"], u_system)
        turns, length = [], self.system_prompts[z].size
        for u_user, u_answer, u_think in draws:
            user = tl.lognormal_int(p["user_tokens"], u_user)
            answer = self._answer(u_answer)
            think = tl.exponential(p["think_s"]["mean"], u_think)
            if length + user + answer > self.max_context:
                break                       # this turn would not fit
            turns.append((self._tokens(user), answer, think))
            length += user + answer
        sid = len(self._sessions)
        measured = self.in_window(t)
        self._sessions[sid] = {"turns": turns, "turn": 0,
                               "context": self.system_prompts[z],
                               "measured": measured}
        if measured:
            self.expect(len(turns))
        self._turn(sid, t)

    def _turn(self, sid, due):
        s = self._sessions[sid]
        if s["turn"] >= len(s["turns"]):
            return
        user, answer, _ = s["turns"][s["turn"]]
        s["turn"] += 1
        self._push(self._req(due, np.concatenate([s["context"], user]),
                             answer, measured=s["measured"], session=sid,
                             turn=s["turn"]))

    def finished(self, req, served, now):
        super().finished(req, served, now)
        s = self._sessions[req.session]
        s["context"] = np.concatenate([req.prompt,
                                       np.asarray(served, np.int32)])
        self._turn(req.session, now + s["turns"][req.turn - 1][2])
