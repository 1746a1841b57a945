"""Single requests with unique random prompts: an open loop of Poisson
arrivals. Every request that arrives in the window is measured.

Parameters: `prompt_tokens` and `answer_tokens` (log-normal: `median`,
`sigma`, `min`, `max`).
"""

from __future__ import annotations

from harness import traffic as tl


class Mix(tl.OpenLoop):

    @staticmethod
    def prompt_range(params, max_context):
        p = params["prompt_tokens"]
        return p["min"], p["max"]

    def block_draws(self, n):
        return [{"prompt": p, "answer": a}
                for p, a in zip(tl.midpoints(n, 0), tl.midpoints(n, 1))]

    def arrive(self, t, block):
        u_prompt = block["prompt"] if block else self._u["prompt"]()
        u_answer = block["answer"] if block else self._u["answer"]()
        n = tl.lognormal_int(self.params["prompt_tokens"], u_prompt)
        measured = self.in_window(t)
        if measured:
            self.expect(1)
        self._push(self._req(t, self._tokens(n), self._answer(u_answer),
                             measured=measured))
