"""Open loop: independent users arriving as a Poisson process.

Mix parameters: ``rate_per_s``, ``ramp_s`` (untimed traffic before the
window), ``tail_s`` (arrivals scheduled past the window, so the requests due
late in the window meet the same load while they finish), ``prompt`` and
``output`` length specs (``bench/sizes.py``). Requests are timed from when
they were due, so a stall that delays later submits shows in their latency.
"""
from __future__ import annotations

import numpy as np

from bench import sizes
from bench.traffic import Request


class OpenLoop:
    closed = False

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.ramp_s = float(mix["ramp_s"])
        horizon = self.ramp_s + seconds + float(mix["tail_s"])
        rate = float(mix["rate_per_s"])
        n = sizes.count(rate, horizon)
        rng = np.random.default_rng(seed)
        due = np.cumsum(sizes.exponential_gaps(rate, n, rng))
        prompts = sizes.lengths(mix["prompt"], n, rng)
        outputs = sizes.lengths(mix["output"], n, rng)
        self.requests = [Request(rid=i, due_s=float(due[i]),
                                 prompt_len=int(prompts[i]),
                                 max_new=int(outputs[i]))
                         for i in range(n)]

    def initial(self):
        return list(self.requests)

    def on_complete(self, req, t_s):
        return None


def make(mix: dict, seed: int, seconds: float, vocab: int) -> OpenLoop:
    return OpenLoop(mix, seed, seconds, vocab)
