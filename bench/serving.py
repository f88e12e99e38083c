"""Serving cells: set-up, the measured window, and the check of its output.

The window drives the program's own serving path: ``Scheduler.submit`` /
``pump`` over a ``ServeEngine`` (bucketed prefill-insert programs and the
decode program, compiled Pallas kernels, the int8 KV cache). The harness
only submits requests when they are due and stamps what it sees: the due
time, and the arrival of every token at the client side (the scheduler's
detokenize callback); a request completes with its last token. One
sequence serves every
caller: ``setup`` (weights, export, engine, warm-up), ``serve`` (the
traffic through a ``Window``), ``judge`` (the check of what it served).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import queue
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as bmodel
from bench import reference, sizes
from bench.stats import percentile
from bench.traffic import generator


@dataclasses.dataclass
class Served:
    """What the window saw of one request."""
    req: object
    due: float                 # host clock, seconds
    times: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)  # client side


@dataclasses.dataclass
class Setup:
    engine: object
    astates: Optional[dict]
    vocab: int
    phases: dict              # seconds of each set-up phase, compile clock


def reference_astates(cfg: dict, model):
    """The 8-bit activation grid of every site (None for weight-only
    recipes): the benchmark's own calibration on the reference, over the
    weights and tokens of the configuration's fixed calibration seed.

    The grid is the same for every run seed. The program bakes activation
    steps into its compiled programs as constants, so a grid that moved
    with the seed would recompile every program in every run; weights drawn
    from any seed have the same distribution, so one grid fits them all."""
    c = cfg["calibration"]
    if cfg["recipe"]["a_bits"] is None:
        return None
    rng = np.random.default_rng(c["seed"])
    toks = rng.integers(0, cfg["arch"]["vocab"],
                        size=(c["sequences"], c["tokens"])).astype(np.int32)
    params = bmodel.weights(model, c["seed"])
    return jax.jit(lambda p, t: reference.calibrate(cfg, p, t))(
        params, jnp.asarray(toks))


def program_astates(astates) -> Dict[str, dict]:
    """The same grid keyed by the program's site names."""
    out = {}
    for site, (steps, betas) in astates.items():
        steps, betas = np.asarray(steps), np.asarray(betas)
        pre = "" if site in ("wq", "wk", "wv", "wo") else "mlp."
        for i in range(steps.shape[0]):
            out[f"layers.{i}.{pre}{site}"] = {
                "step": jnp.float32(steps[i]), "beta": jnp.float32(betas[i])}
    return out


_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


@contextlib.contextmanager
def compile_clock(out: dict):
    """Add to ``out`` the seconds jax spends tracing, lowering, compiling
    and reading its persistent cache while open, and the cache's hits and
    misses: where set-up goes, and whether it compiled."""
    from jax import monitoring

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            k = _COMPILE_EVENTS[event]
            out[k] = out.get(k, 0.0) + secs

    def on_event(event, **_):
        if event in _COMPILE_EVENTS:
            k = _COMPILE_EVENTS[event]
            out[k] = out.get(k, 0) + 1
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield out
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def setup(cfg: dict, mix: dict, seed: int, backend: str = "auto") -> Setup:
    """Weights from the seed, QTensor export, activation grid, engine with
    every prefill bucket and the decode program compiled, then the mix's
    shapes run once. The float weights are gone when this returns."""
    from repro.core.context import QuantCtx
    from repro.serve import EngineConfig, ServeEngine

    phases = {}
    t = [time.perf_counter()]

    def lap(name, tree=None):
        jax.block_until_ready(tree)
        now = time.perf_counter()
        phases[name] = now - t[0]
        t[0] = now

    with compile_clock(phases):
        model = bmodel.build(cfg)
        ast = reference_astates(cfg, model)
        lap("grid_s", ast)
        params = bmodel.weights(model, seed)
        lap("weights_s", params)
        rcp = bmodel.recipe(cfg)
        qparams = bmodel.export(model, params, rcp)
        del params
        lap("export_s", qparams)
        astates = {} if ast is None else program_astates(ast)
        ctx = QuantCtx(mode="deploy", recipe=rcp, astates=astates,
                       backend=backend)
        engine = ServeEngine(model, qparams, ctx,
                             EngineConfig(**cfg["engine"]))
        lap("engine_s", engine.state)
        phases["engine_programs_s"] = {
            k: round(us / 1e6, 3) for k, us in engine.compile_us.items()}
        warm(engine, mix)
        lap("warm_s")
    return Setup(engine, ast, cfg["arch"]["vocab"], phases)


def warm(engine, mix: dict) -> None:
    """Run every prefill bucket the mix's prompts can reach, and the decode
    program, once: the first execution of each compiled program happens
    here, not in the window."""
    top = engine.bucket_for(int(mix["prompt"]["max"]))
    for i, b in enumerate(engine.buckets):
        if b > top:
            break
        engine.admit([(-1 - i, np.zeros((b,), np.int32), 2)])
        while engine.active:
            engine.step()
    engine.drain_finished()


class Window:
    """One run of a traffic mix through the scheduler."""

    def __init__(self, st: Setup, mix: dict, seed: int, seconds: float,
                 annotate=None):
        from repro.serve import Scheduler
        self.seed, self.seconds, self.vocab = seed, seconds, st.vocab
        self.gen = generator(mix["kind"]).make(mix, seed, seconds, st.vocab)
        self.served: Dict[int, Served] = {}
        self.annotate = annotate
        self.sched = Scheduler(st.engine, detokenize=self._on_token)
        self.done: "queue.SimpleQueue" = queue.SimpleQueue()
        self.pending = []          # (due host time, request), sorted
        self.lost: List[Served] = []

    def _on_token(self, rid, tok):
        """The client side (the scheduler's detokenize thread): stamp each
        token; the last one completes the request."""
        s = self.served[rid]
        s.times.append(time.perf_counter())
        if len(s.times) == s.req.max_new:
            self.done.put(s)

    def _schedule(self, reqs):
        for r in reqs:
            self.pending.append((self.t0 + r.due_s, r))
        self.pending.sort(key=lambda p: p[0])

    def _take_done(self):
        while not self.done.empty():
            s = self.done.get()
            s.finished = s.times[-1]
            nxt = self.gen.on_complete(s.req, s.finished - self.t0)
            if nxt is not None:
                self._schedule([nxt])

    def _submit_due(self, now):
        from repro.serve import Request
        self._take_done()
        while self.pending and self.pending[0][0] <= now:
            due, r = self.pending.pop(0)
            self.served[r.rid] = Served(r, due)
            toks = sizes.token_ids(self.seed, r.rid, r.prompt_len, self.vocab)
            self.sched.submit(Request(rid=r.rid, tokens=toks,
                                      max_new=r.max_new))

    def _pump(self):
        if self.annotate is None:
            return self.sched.pump()
        with self.annotate("bench.pump"):
            return self.sched.pump()

    def run(self, on_window_start=None, on_trace_end=None,
            trace_s: float = None, drain_s: float = 120.0):
        """Ramp, window, then (open loop) drain the requests due in the
        window. ``on_window_start`` is called with the window when it opens,
        ``on_trace_end`` ``trace_s`` into it, or at its end. ``backlog``
        keeps the requests waiting for a slot when the window opens and
        when it closes. Returns the window's bounds on the host clock."""
        self.t0 = time.perf_counter()
        self._schedule(self.gen.initial())
        w0 = self.t0 + self.gen.ramp_s
        w1 = w0 + self.seconds
        t_end = w0 + min(trace_s or self.seconds, self.seconds)
        started = ended = False
        while True:
            now = time.perf_counter()
            if not started and now >= w0:
                started = True
                backlog = [self.sched.backlog.qsize()]
                if on_window_start:
                    on_window_start(self)
            if not ended and now >= t_end:
                ended = True
                self.traced = (w0, time.perf_counter())
                if on_trace_end:
                    on_trace_end(self)
            if now >= w1:
                backlog.append(self.sched.backlog.qsize())
                break
            self._submit_due(now)
            busy = self._pump()
            if not busy and self.pending:
                time.sleep(max(0.0, min(self.pending[0][0], w1)
                               - time.perf_counter()))
        if not self.gen.closed:
            end = time.perf_counter() + drain_s
            while time.perf_counter() < end and not all(
                    s.finished for s in self.counted(w0, w1)):
                self._submit_due(time.perf_counter())
                if not self._pump() and not self.pending:
                    break          # nothing left that could finish one
        sched, eng = self.sched, self.sched.engine
        sched.close()              # the detokenize thread has every token
        self._take_done()
        for rid, toks in sched.outputs.items():
            self.served[rid].tokens = list(toks)
        # a request the engine and the scheduler have let go of, and whose
        # last token never reached the client, is lost
        held = {v.rid for v in eng.slots} | {r.rid for r in
                                             sched.backlog.queue}
        self.lost = [s for s in self.served.values()
                     if s.finished is None and s.req.rid not in held]
        self.sched = None          # the engine is the setup's alone again
        self.window, self.backlog = (w0, w1), tuple(backlog)
        return w0, w1

    def counted(self, w0, w1) -> List[Served]:
        """The requests the window answers for: open loop, those due in it;
        closed loop, those that completed in it and those lost."""
        if self.gen.closed:
            return [s for s in self.served.values()
                    if s.finished is not None and w0 <= s.finished < w1
                    ] + self.lost
        return [s for s in self.served.values() if w0 <= s.due < w1]


def serve(st: Setup, mix: dict, seed: int, seconds: float,
          annotate=None, **run_kw):
    """The mix's traffic through the set-up engine for ``seconds``; returns
    the window and its end-to-end numbers."""
    win = Window(st, mix, seed, seconds, annotate=annotate)
    win.run(**run_kw)
    return win, end_to_end(win)


def in_full(s: Served) -> bool:
    return (s.finished is not None and len(s.tokens) == s.req.max_new
            and len(s.times) == s.req.max_new)


def end_to_end(win: Window) -> dict:
    """Every end-to-end number a serving window can give, from all its
    samples; the cell keeps the ones it names."""
    w0, w1 = win.window
    counted = win.counted(w0, w1)
    ok = [s for s in counted if in_full(s)]
    out = {"attempted": len(counted), "failed": len(counted) - len(ok)}
    if not win.gen.closed and counted:
        # a failed request misses every limit: its TTFT is infinite
        ttft = [(s.times[0] - s.due) * 1e3 if in_full(s) else float("inf")
                for s in counted]
        out["ttft_p95_ms"] = percentile(ttft, 95)
        itl = [(b - a) * 1e3 for s in ok for a, b in zip(s.times, s.times[1:])]
    else:
        itl = [(b - a) * 1e3 for s in win.served.values()
               for a, b in zip(s.times, s.times[1:]) if w0 <= b < w1]
    if itl:
        out["itl_p95_ms"] = percentile(itl, 95)
    n_tok = sum(1 for s in win.served.values() for t in s.times
                if w0 <= t < w1)
    out["output_tokens_per_s"] = n_tok / (w1 - w0)
    return out


def sample(win: Window, seed: int, n: int) -> List[Served]:
    """The requests the check compares: the one with the most served
    tokens, and ``n - 1`` more drawn from the seed. Candidates are the
    requests answered in full, and in a closed loop also those still in
    flight when the window closed, with the tokens they had by then."""
    done = sorted((s for s in win.served.values()
                   if in_full(s) or (win.gen.closed and s.finished is None
                                     and s.tokens)),
                  key=lambda s: s.req.rid)
    if not done:
        return []
    longest = max(done, key=lambda s: (s.req.prompt_len + len(s.tokens),
                                       -s.req.rid))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 0x5A3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(cfg: dict, seed: int, picked: List[Served]):
    """(tokens, n_prompt, n_total) per picked request, right-padded to the
    engine's max_len so the reference compiles once."""
    S = cfg["engine"]["max_len"]
    out = []
    for s in picked:
        prompt = sizes.token_ids(seed, s.req.rid, s.req.prompt_len,
                                 cfg["arch"]["vocab"])
        toks = np.zeros((S,), np.int32)
        seq = np.concatenate([prompt, np.asarray(s.tokens, np.int32)])
        toks[:len(seq)] = seq
        out.append((toks, len(prompt), len(seq)))
    return out


def check(cfg: dict, seed: int, astates, seqs, control: bool = False):
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every sequence; with ``control``, the same
    for the token the float8 reference puts first. Needs the program's
    state freed: the float weights are drawn again here."""
    model = bmodel.build(cfg)
    params = bmodel.weights(model, seed)
    fn = reference.control_gaps if control else reference.gaps
    run = jax.jit(lambda p, a, t, n, m: jnp.max(fn(cfg, p, a, t, n, m)))
    worst = 0.0
    for toks, n, m in seqs:
        worst = max(worst, float(run(params, astates, toks, n, m)))
    del params
    return worst


def judge(cfg: dict, seed: int, win: Window, st: Setup, e2e: dict,
          controls=(False,)) -> List[dict]:
    """Whether what the window served is correct: the sample drawn from the
    seed, the program's state freed, then for each entry of ``controls``
    (False: the program's served tokens; True: the float8 control's at the
    same positions) the compared numbers beside the configuration's limits
    and the verdict, ``{"correct": bool, "checks": {...}}``."""
    c = cfg["check"]
    seqs = sequences(cfg, seed, sample(win, seed, c["requests"]))
    ast = st.astates
    free(st)
    served = sum(m - n for _, n, m in seqs)
    out = []
    for control in controls:
        gap = check(cfg, seed, ast, seqs, control) if seqs else math.inf
        checks = {"logit_gap": {"value": gap, "limit": c["max_logit_gap"]},
                  "served_tokens_compared": {"value": served,
                                             "limit": c["min_tokens"]},
                  "failed_requests": {"value": e2e["failed"], "limit": 0}}
        ok = (gap <= c["max_logit_gap"] and served >= c["min_tokens"]
              and e2e["failed"] == 0 and e2e["attempted"] > 0)
        out.append({"correct": bool(ok), "checks": checks})
    return out


def free(st: Setup):
    """Drop the program's state so the reference has the chip's memory."""
    st.engine = None
    gc.collect()
