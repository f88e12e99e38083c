"""int8 KV-cache decode path: consistency vs bf16 cache, and the slot
decode's two append forms leaving the same cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.context import QuantCtx
from repro.models import build_model
from repro.serve import kv as skv
from repro.serve.engine import EngineConfig, make_decode

B, S = 2, 32


def test_int8_kv_cache_decode_close_to_bf16():
    cfg = get_smoke_config("qwen2.5-14b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
    ctx = QuantCtx(mode="fp")

    outs = {}
    for quant in (False, True):
        cache = model.init_cache(B, S + 4, kv_quant=quant)
        _, cache = model.prefill(params, tokens[:, :-1], cache, ctx)
        logits, _ = model.decode_step(params, tokens[:, -1:], cache,
                                      jnp.int32(S - 1), ctx)
        outs[quant] = np.asarray(logits, np.float32)

    # int8 cache must match bf16 cache decode closely (per-token scales)
    denom = np.abs(outs[False]).max()
    rel = np.abs(outs[True] - outs[False]).max() / denom
    assert rel < 0.05, f"int8 KV divergence {rel:.3f}"
    # and greedy tokens should agree
    np.testing.assert_array_equal(outs[True].argmax(-1),
                                  outs[False].argmax(-1))


def test_int8_kv_greedy_horizon_64_steps():
    """Long-horizon serving contract: 64 autoregressive greedy steps on
    the int8 cache emit exactly the fp-cache token stream — quantization
    error from quantize-on-append must not compound into a divergent
    trajectory (each step re-reads every cached position)."""
    H, P = 64, 8
    cfg = get_smoke_config("qwen2.5-14b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab)
    ctx = QuantCtx(mode="fp")

    trajs = {}
    for quant in (False, True):
        cache = model.init_cache(B, P + H, kv_quant=quant)
        _, cache = model.prefill(params, prompt[:, :-1], cache, ctx)
        step = jax.jit(
            lambda p, t, c, pos: model.decode_step(p, t, c, pos, ctx))
        tok, toks = prompt[:, -1:], []
        for i in range(H):
            logits, cache = step(params, tok, cache, jnp.int32(P - 1 + i))
            tok = logits[:, -1].argmax(-1)[:, None].astype(jnp.int32)
            toks.append(np.asarray(tok[:, 0]))
        trajs[quant] = np.stack(toks)
    np.testing.assert_array_equal(trajs[True], trajs[False])


SLOTS, MAX_LEN = 4, 16


def _filled_cache(model, kv_quant):
    """A cache with every entry set, so a write to a wrong place shows."""
    cache = model.init_cache(SLOTS, MAX_LEN, kv_quant=kv_quant)
    out = {}
    for i, (nm, a) in enumerate(sorted(cache.items())):
        key = jax.random.key(10 + i)
        if a.dtype == jnp.int8:
            out[nm] = jax.random.randint(key, a.shape, -127, 128, jnp.int8)
        elif nm.endswith("_scale"):
            out[nm] = jax.random.uniform(key, a.shape, a.dtype, 0.01, 0.1)
        else:
            out[nm] = jax.random.normal(key, a.shape, a.dtype)
    return out


def _decode_steps(model, params, cache, steps=3):
    decode = jax.jit(make_decode(model, QuantCtx(mode="fp"),
                                 EngineConfig(slots=SLOTS, max_len=MAX_LEN)))
    # slot 0 starts at 0, slot 2 at the last position (its later steps
    # fall past the end), slot 3 is inactive and keeps its position
    meta = {"tokens": jnp.array([[3], [5], [7], [11]], jnp.int32),
            "pos": jnp.array([0, MAX_LEN // 2, MAX_LEN - 1, 5], jnp.int32),
            "remaining": jnp.array([steps, steps, steps, 0], jnp.int32)}
    emitted = []
    for _ in range(steps):
        cache, meta, out = decode(params, cache, meta)
        emitted.append(np.asarray(out))
    return jax.tree.map(np.asarray, cache), np.stack(emitted)


@pytest.mark.parametrize("kv_quant", [True, False], ids=["int8", "fp"])
@pytest.mark.parametrize("heads", [(9, 3), (4, 4)], ids=["gqa", "mha"])
def test_select_append_matches_scatter(monkeypatch, heads, kv_quant):
    """The positional-select append leaves the cache bit-identical to the
    per-slot scatter, and the greedy tokens identical."""
    n_heads, n_kv = heads
    cfg = dataclasses.replace(get_smoke_config("smollm-135m"),
                              n_heads=n_heads, n_kv_heads=n_kv)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = _filled_cache(model, kv_quant)
    assert skv.append_form(cache) == "select"  # head dim 16
    sel_cache, sel_toks = _decode_steps(model, params, cache)
    monkeypatch.setattr(skv, "append_form", lambda cache: "scatter")
    sc_cache, sc_toks = _decode_steps(model, params, cache)
    np.testing.assert_array_equal(sel_toks, sc_toks)
    assert sel_toks[:, 3].tolist() == [-1, -1, -1]  # inactive slot
    for nm in cache:
        np.testing.assert_array_equal(sel_cache[nm], sc_cache[nm], err_msg=nm)
        # every slot wrote its position once per step: entries changed only
        # at the positions the decode reached
        changed = np.argwhere(np.any(
            sel_cache[nm] != np.asarray(cache[nm]),
            axis=tuple(range(3, cache[nm].ndim))))
        assert {(b, s) for _, b, s in changed.tolist()} <= {
            (0, 0), (0, 1), (0, 2), (1, 8), (1, 9), (1, 10), (2, 15), (3, 5)}


@pytest.mark.parametrize("shape,form", [
    ((30, 64, 2048, 3, 64), "select"),     # smollm-135m
    ((2, 4, 16, 3, 16), "select"),         # the smoke LMs
    ((16, 32, 2048, 16, 128), "scatter"),  # olmo-1b
    ((4, 8, 256, 2, 256), "scatter"),
])
def test_append_form_follows_the_lane_width(shape, form):
    """The form follows K's head dim, and holds for every leaf."""
    cache = {"k": jax.ShapeDtypeStruct(shape, jnp.int8),
             "k_scale": jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32)}
    assert skv.append_form(cache) == form
