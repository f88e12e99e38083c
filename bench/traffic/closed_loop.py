"""Closed loop: a fixed pool of callers, each waiting for its reply.

Mix parameters: ``clients``, ``ramp_s`` (client starts are staggered evenly
over it; the window opens when it ends), ``per_client`` (requests queued per
client, more than a run can finish), ``prompt`` and ``output`` length specs
(``bench/sizes.py``). A client sends its next request when the last one
completes, so a slower system receives less load.
"""
from __future__ import annotations

import numpy as np

from bench import sizes
from bench.traffic import Request


class ClosedLoop:
    closed = True

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.ramp_s = float(mix["ramp_s"])
        c, k = int(mix["clients"]), int(mix["per_client"])
        rng = np.random.default_rng(seed)
        prompts = sizes.lengths(mix["prompt"], c * k, rng)
        outputs = sizes.lengths(mix["output"], c * k, rng)
        self._queues = [
            [Request(rid=j * c + i, due_s=None, prompt_len=int(prompts[j * c + i]),
                     max_new=int(outputs[j * c + i]), client=i)
             for j in range(k)]
            for i in range(c)]
        self.requests = [r for q in self._queues for r in q]

    def initial(self):
        c = len(self._queues)
        out = []
        for i, q in enumerate(self._queues):
            q[0].due_s = self.ramp_s * i / c
            out.append(q.pop(0))
        return out

    def on_complete(self, req, t_s):
        q = self._queues[req.client]
        if not q:
            return None
        nxt = q.pop(0)
        nxt.due_s = t_s
        return nxt


def make(mix: dict, seed: int, seconds: float, vocab: int) -> ClosedLoop:
    return ClosedLoop(mix, seed, seconds, vocab)
