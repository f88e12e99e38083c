"""Serving-engine tier-1 suite (repro.serve): bucketed-prefill bit parity,
slot recycling, retrace flatness under mixed occupancy, the int8 KV HBM
win, the machine-readable capability-degradation contract, and the serve
path's telemetry spans."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.context import QuantCtx
from repro.core.quant_config import QuantRecipe
from repro.core.reconstruct import quantize_blocks
from repro.data import CalibrationSet, SyntheticTokens
from repro.models import build_model
from repro.obs import ListSink
from repro.obs.telemetry import TELEMETRY, now
from repro.serve import (EngineConfig, KVQuantUnsupported, Request,
                         Scheduler, ServeEngine, serve_capability)
from repro.serve import engine as seng
from repro.serve import kv as skv

MAX_LEN = 32  # engine buckets: [8, 16, 32]


@pytest.fixture(scope="module")
def deploy_lm():
    """Export-only quantized smoke LM + deploy ctx (shared, read-only)."""
    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    recipe = QuantRecipe(method="flexround", w_bits=4, a_bits=8, iters=0,
                         batch_size=4)
    cal = CalibrationSet.build(SyntheticTokens(vocab=cfg.vocab, seq_len=16,
                                               seed=0), 4)
    x0, blocks, assemble = model.quant_blocks(params, cal.tokens)
    finalized, astates, _ = quantize_blocks(blocks, recipe, x0)
    qparams = assemble(finalized)
    ctx = QuantCtx(mode="deploy", recipe=recipe, astates=astates,
                   backend="xla")
    return cfg, model, qparams, ctx


@pytest.fixture(scope="module")
def engine(deploy_lm):
    """Shared 3-slot engine; every test that runs requests drains them, so
    the engine is idle (all slots free) between tests."""
    _, model, qparams, ctx = deploy_lm
    return ServeEngine(model, qparams, ctx,
                       EngineConfig(slots=3, max_len=MAX_LEN,
                                    prefill_group=2, kv_quant=True))


@pytest.fixture(scope="module")
def ref_engine(deploy_lm):
    """Single-slot engine: the isolated-request oracle."""
    _, model, qparams, ctx = deploy_lm
    return ServeEngine(model, qparams, ctx,
                       EngineConfig(slots=1, max_len=MAX_LEN,
                                    prefill_group=1, kv_quant=True))


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def _ref_greedy(ref_engine, toks, max_new):
    """Greedy tokens for one request run alone through the 1-slot engine."""
    out = [ref_engine.admit([(0, toks, max_new)])[0][1]]
    while ref_engine.active:
        out.extend(t for _, t in ref_engine.step())
    ref_engine.drain_finished()
    return out


# ------------------------------------------------------------ bucket parity
def test_bucketed_prefill_parity_per_bucket(deploy_lm):
    """Right-padding a prompt to its bucket must not change the last real
    position's result: padded keys are strictly future to every real query
    under the causal mask, so they contribute exactly zero. XLA may still
    tile the softmax reduction differently for the padded key length, so
    the pin is a reduction-order rounding envelope on the hidden state
    plus *identical* greedy tokens (the serving-visible contract)."""
    cfg, model, qparams, ctx = deploy_lm
    for bucket in (8, 16, 32):
        n = bucket - 3
        toks = jax.random.randint(jax.random.key(bucket), (2, n), 0,
                                  cfg.vocab, dtype=jnp.int32)
        cache = model.init_cache(2, n, kv_quant=True)
        last, _ = model.prefill(qparams, toks, cache, ctx)
        padded = jnp.zeros((2, bucket), jnp.int32).at[:, :n].set(toks)
        cache_p = model.init_cache(2, bucket, kv_quant=True)
        last_p, _ = model.prefill(qparams, padded, cache_p, ctx,
                                  true_len=jnp.full((2,), n, jnp.int32))
        a, b = np.asarray(last_p[:, 0]), np.asarray(last[:, -1])
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=2e-6,
            err_msg=f"bucket {bucket}: padded prefill left the rounding "
                    "envelope — padding is leaking into real positions")
        head = np.asarray(model.lm_head(qparams), np.float32)
        np.testing.assert_array_equal(
            (a.astype(np.float32) @ head).argmax(-1),
            (b.astype(np.float32) @ head).argmax(-1),
            err_msg=f"bucket {bucket}: greedy token changed under padding")


# ------------------------------------------------------- continuous batching
def test_continuous_batching_matches_isolated_decode(engine, ref_engine,
                                                     deploy_lm):
    """Five requests over three slots (mixed lengths, two buckets, slot
    reuse mid-flight) emit exactly the tokens each request gets alone."""
    cfg = deploy_lm[0]
    lens = [5, 9, 12, 7, 3]
    prompts = _prompts(lens, cfg.vocab, seed=1)
    reqs = [Request(i, p, max_new=6) for i, p in enumerate(prompts)]
    with Scheduler(engine) as sched:
        outs = sched.run(reqs)
    assert engine.active == 0 and not engine._finished
    for i, p in enumerate(prompts):
        assert outs[i] == _ref_greedy(ref_engine, p, 6), f"request {i}"


def test_slot_recycling(engine, ref_engine, deploy_lm):
    """A slot freed by a finished request serves the next request with the
    same tokens as a fresh engine would — stale KV from the previous
    occupant is never visible (the mask reads only positions the current
    occupant has written)."""
    cfg = deploy_lm[0]
    long, short = _prompts([20, 4], cfg.vocab, seed=2)
    first = engine.admit([(100, long, 5)])
    while engine.active:
        first.extend(engine.step())
    engine.drain_finished()
    recycled = engine.admit([(101, short, 5)])
    got = [recycled[0][1]]
    while engine.active:
        got.extend(t for _, t in engine.step())
    engine.drain_finished()
    assert got == _ref_greedy(ref_engine, short, 5)


# ---------------------------------------------------------- retrace flatness
def test_compile_count_flat_across_occupancy(engine, deploy_lm, no_retrace):
    """After __init__ the engine never compiles again: occupancy, group
    fill, request count, and bucket mix all reuse the AOT executables
    (the acceptance gate for continuous batching)."""
    cfg = deploy_lm[0]
    before = engine.compile_count
    assert before == len(engine.buckets) + 1
    lens = [3, 6, 14, 25, 9, 5, 28, 2]  # all three buckets, odd group fills
    reqs = [Request(200 + i, p, max_new=4)
            for i, p in enumerate(_prompts(lens, cfg.vocab, seed=3))]
    with no_retrace(0, xla_budget=0):
        with Scheduler(engine) as sched:
            outs = sched.run(reqs)
    assert engine.compile_count == before
    assert sorted(outs) == [200 + i for i in range(len(lens))]
    assert all(len(v) == 4 for v in outs.values())


# ------------------------------------------------------------------ int8 KV
def test_int8_kv_halves_hbm_per_slot(deploy_lm):
    """The int8 cache must be strictly smaller per slot than the bf16
    cache (scales cost (1/head_dim) extra, codes save half)."""
    _, model, _, _ = deploy_lm
    slots = 4
    c8 = model.init_cache(slots, 64, kv_quant=True)
    cb = model.init_cache(slots, 64, dtype=jnp.bfloat16, kv_quant=False)
    mib8 = skv.hbm_per_slot_mib(c8, slots)
    mibb = skv.hbm_per_slot_mib(cb, slots)
    assert mib8 < mibb, f"int8 {mib8} MiB/slot not below bf16 {mibb}"
    # the bytes accessor is the single source the bench row and memcheck's
    # QL403 both read — it must tile back to the whole cache
    assert skv.hbm_per_slot_bytes(c8, slots) * slots == skv.cache_bytes(c8)


def test_stats_report_the_kv_append_form(engine):
    """``stats()`` names the form the compiled decode appends K/V in: the
    smoke LM's head dim of 16 takes the positional select."""
    assert engine.stats()["kv_append"] == "select"


def test_kv_scales_floored_above_subnormal(deploy_lm):
    """Stored KV scales obey the QL303 contract: >= KV_SCALE_MIN even for
    an all-zero append (the absmax floor), far above float32 tiny."""
    _, model, qparams, ctx = deploy_lm
    toks = jnp.zeros((1, 8), jnp.int32)  # degenerate prompt
    cache = model.init_cache(1, 8, kv_quant=True)
    _, cache = model.prefill(qparams, toks, cache, ctx)
    for nm, buf in cache.items():
        if nm.endswith("_scale"):
            lo = float(jnp.min(buf))
            assert lo >= skv.KV_SCALE_MIN, f"{nm} scale {lo} below floor"
    codes, scale = skv.kv_quantize(jnp.zeros((1, 2, 4), jnp.float32))
    assert float(jnp.min(scale)) >= skv.KV_SCALE_MIN
    assert not np.any(np.asarray(codes))


# --------------------------------------------------- capability degradation
def test_kv_quant_named_error_ssm_hybrid():
    """Families without a KV cache raise the machine-readable
    ``KVQuantUnsupported`` (a ValueError), never a bare TypeError."""
    for arch, family in (("mamba2-130m", "ssm"),
                         ("recurrentgemma-2b", "hybrid")):
        model = build_model(get_smoke_config(arch))
        with pytest.raises(KVQuantUnsupported) as ei:
            model.init_cache(2, 16, kv_quant=True)
        assert ei.value.reason == f"kv_quant_unsupported:{family}"
        assert isinstance(ei.value, ValueError)
        # kv_quant=False still works: the unified signature is accepted
        model.init_cache(2, 16, kv_quant=False)


def test_engine_capability_reasons():
    """The engine and the plain serve smoke degrade through stable
    ``key:detail`` reasons shared with benchmarks and launch."""
    ssm = build_model(get_smoke_config("mamba2-130m"))
    assert serve_capability(ssm, engine=True) == (False,
                                                  "unsupported_family:ssm")
    mla = build_model(get_smoke_config("deepseek-v3-671b"))
    assert serve_capability(mla, engine=True) == (False,
                                                  "unsupported_layout:mla")
    assert serve_capability(mla, kv_quant=True) == (
        False, "kv_quant_unsupported:mla")
    ok, reason = serve_capability(mla)  # uniform fp serve smoke still fine
    assert ok and reason == "ok"
    with pytest.raises(KVQuantUnsupported) as ei:
        ServeEngine(ssm, None, None)
    assert ei.value.reason == "unsupported_family:ssm"


def test_admit_rejects_oversubscription(engine, deploy_lm):
    """More requests than free slots (or than the group size) is a host
    bug, reported eagerly instead of silently dropping a request."""
    cfg = deploy_lm[0]
    prompts = _prompts([4, 4, 4], cfg.vocab, seed=4)
    with pytest.raises(ValueError, match="free slots"):
        engine.admit([(300 + i, p, 2) for i, p in enumerate(prompts)])
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        engine.bucket_for(MAX_LEN + 1)


# ------------------------------------------------------------- observability
def test_detok_error_counted_and_reraised(engine, deploy_lm):
    """A raising detokenize callback must not kill the drain thread: the
    loop keeps consuming (so ``queue.join()`` never hangs), the error is
    counted on the scheduler's metrics, and the first exception is
    re-raised on the caller's thread — once; ``close()`` after the raise
    is clean."""
    cfg = deploy_lm[0]
    prompts = _prompts([4, 6], cfg.vocab, seed=5)
    poisoned = []

    def bad_detok(rid, tok):
        if rid == 400 and not poisoned:
            poisoned.append(tok)
            raise RuntimeError("tokenizer exploded")

    reqs = [Request(400 + i, p, max_new=3) for i, p in enumerate(prompts)]
    sched = Scheduler(engine, detokenize=bad_detok)
    try:
        with pytest.raises(RuntimeError, match="tokenizer exploded"):
            sched.run(reqs)
        sched.close()  # joins the drain thread; cleared error, no re-raise
        assert sched.metrics.detok_errors >= 1
        assert sched.outputs[400], "drain loop died at the poisoned token"
    finally:
        while engine.active:  # leave the shared engine idle for later tests
            engine.step()
        engine.drain_finished()


def test_prefill_latency_histogram_accumulates(engine, deploy_lm):
    """Per-bucket prefill latency is a histogram, not a last-write scalar:
    repeated admits into the same bucket all survive into the summary
    (count grows; p50/p95 exposed through ``engine.stats()``)."""
    cfg = deploy_lm[0]
    before = engine.metrics.prefill_hist(8).count
    for seed in (6, 7):
        toks = _prompts([4], cfg.vocab, seed=seed)[0]
        engine.admit([(500 + seed, toks, 2)])
        while engine.active:
            engine.step()
        engine.drain_finished()
    s = engine.stats()["prefill_us"][8]
    assert s["count"] == before + 2, "prefill histogram overwrote a sample"
    assert {"count", "mean", "p50", "p95", "max"} <= set(s)
    assert 0 < s["p50"] <= s["p95"] <= s["max"]


# ------------------------------------------------------- spans on the serve path
def test_detok_spans_time_the_queue_lag(engine, deploy_lm):
    """With a slow client callback, the detokenize thread records one
    ``serve.detok`` span per run of queued tokens (not one per token), its
    ``tokens`` count every stamped token, and their mean lag is at least
    half the callback's time: a token waits behind those put with it."""
    cfg = deploy_lm[0]
    slow_s = 0.01
    reqs = [Request(300 + i, p, max_new=6)
            for i, p in enumerate(_prompts([5, 9, 4], cfg.vocab, seed=4))]
    sink = ListSink()
    with TELEMETRY.enabled_scope(sink=sink):
        with Scheduler(engine, detokenize=lambda r, t: time.sleep(slow_s)
                       ) as sched:
            outs = sched.run(reqs)
    n_tok = sum(len(v) for v in outs.values())
    detok = [r for r in sink.records
             if r["kind"] == "span" and r["name"] == "serve.detok"]
    assert sum(r["attrs"]["tokens"] for r in detok) == n_tok == 18
    assert len(detok) < n_tok
    lag_us = sum(r["attrs"]["lag_us_sum"] for r in detok)
    assert lag_us / n_tok >= 0.5 * slow_s * 1e6
    assert all(r["parent_id"] is None for r in detok)  # its own thread
    names = {r["name"] for r in sink.records if r["kind"] == "span"}
    assert {"serve.pump", "serve.admit", "serve.prefill.dispatch",
            "serve.prefill.wait", "serve.decode.dispatch",
            "serve.decode.wait", "serve.decode.bookkeep",
            "serve.emit"} <= names


def test_request_arrival_starts_its_queue_wait(engine, deploy_lm):
    """A request that arrived 50 ms before its submit waits at least 50 ms
    by the scheduler's own accounting."""
    cfg = deploy_lm[0]
    p, = _prompts([6], cfg.vocab, seed=5)
    before = engine.metrics.queue_wait_us.count
    with Scheduler(engine) as sched:
        sched.run([Request(400, p, max_new=2, arrival=now() - 0.05)])
    assert engine.metrics.queue_wait_us.count == before + 1
    assert engine.metrics.queue_wait_us.values[before] >= 50_000


def test_serve_path_flat_and_jaxpr_identical_under_profiler(
        engine, deploy_lm, no_retrace, tmp_path):
    """With telemetry enabled and a profiler trace active, every span of
    the serve path writes its annotation and the engine still neither
    traces nor compiles (``no_retrace(0, xla_budget=0)``), and the decode
    and prefill jaxprs are identical to those traced with both off."""
    cfg, model, qparams, ctx = deploy_lm
    ecfg = engine.cfg
    state = seng.init_state(model, ecfg)
    meta = {k: state[k] for k in ("tokens", "pos", "remaining")}
    G, b = ecfg.prefill_group, ecfg.min_bucket
    pargs = (qparams, state, jnp.zeros((G, b), jnp.int32),
             jnp.ones((G,), jnp.int32), jnp.zeros((G,), jnp.int32),
             jnp.ones((G,), jnp.int32))

    def jaxprs():
        return (str(jax.make_jaxpr(seng.make_decode(model, ctx, ecfg))(
                    qparams, state["cache"], meta)),
                str(jax.make_jaxpr(seng.make_prefill(model, ctx, ecfg, b))(
                    *pargs)))

    plain = jaxprs()
    reqs = [Request(500 + i, p, max_new=3)
            for i, p in enumerate(_prompts([4, 11, 20], cfg.vocab, seed=6))]
    sink = ListSink()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TELEMETRY.enabled_scope(sink=sink):
            assert jaxprs() == plain
            with no_retrace(0, xla_budget=0):
                with Scheduler(engine) as sched:
                    outs = sched.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert sorted(outs) == [500, 501, 502]
    assert any(r["kind"] == "span" and r["name"] == "serve.decode_step"
               for r in sink.records)
