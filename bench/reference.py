"""The plain reference: the configuration's quantized model in float32.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, imports nothing of
the program, and takes nothing the program made. It draws the float weights
from the seed itself (``bench/model.weights``, the benchmark's own
generator), quantizes them by the configuration's recipe, and runs the
forward pass the served model is specified to compute:

* weights: asymmetric min-max round-to-nearest (FlexRound's initial state),
  per tensor or per output channel, ``w_hat = s * (clip(round(w/s) + z,
  0, 2^b - 1) - z)``;
* activations (A8 recipes): the static 8-bit grid of each site's step and
  offset, ``x_hat = s * (clip(round(x/s) + z, 0, 255) - z)`` with
  ``z = clip(round(-beta/s), 0, 255)``;
* attention: rotate-half RoPE, causal softmax; the prompt's own positions
  attend over float K/V (prefill), every generated position over K/V
  stored as int8 per (token, head) absmax codes (the int8 KV cache);
* norms: RMSNorm with zero-centred gain, or OLMo's non-parametric
  LayerNorm; SwiGLU MLP; logits from the tied embedding or the head.

``dtype="float8"`` is the control: every matmul operand is rounded to
float8 (e4m3) first, accumulation stays float32.
"""
from __future__ import annotations

import fnmatch

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
KV_FLOOR = 1e-6


def _mm(a, b, control: bool):
    if control:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ein(spec, a, b, control: bool):
    if control:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def layer_bits(cfg: dict) -> np.ndarray:
    """Weight bits of each layer: the recipe's default, or the last rule
    whose glob matches the layer's sites."""
    r, n = cfg["recipe"], cfg["arch"]["n_layers"]
    bits = np.full((n,), r["w_bits"], np.int32)
    for i in range(n):
        for pat, b in r["w_bits_rules"]:
            if fnmatch.fnmatchcase(f"layers.{i}.wq", pat):
                bits[i] = b
    return bits


def fake_quant_weight(w, bits, per_channel: bool):
    """Asymmetric min-max round-to-nearest of a (d_in, d_out) weight."""
    w = w.astype(jnp.float32)
    qmax = (2.0 ** bits) - 1.0
    axis = 0 if per_channel else None
    lo = jnp.minimum(jnp.min(w, axis=axis, keepdims=True), 0.0)
    hi = jnp.maximum(jnp.max(w, axis=axis, keepdims=True), 0.0)
    s = jnp.maximum((hi - lo) / qmax, 1e-8)
    z = jnp.clip(jnp.round(-lo / s), 0.0, qmax)
    return s * (jnp.clip(jnp.round(w / s) + z, 0.0, qmax) - z)


def fake_quant_act(x, step, beta):
    z = jnp.clip(jnp.round(-beta / step), 0.0, 255.0)
    return step * (jnp.clip(jnp.round(x / step) + z, 0.0, 255.0) - z)


def kv_int8(t):
    """Per-(token, head) absmax int8 codes of (S, H, D) entries, back in
    float: the values the int8 KV cache holds."""
    s = jnp.maximum(jnp.max(jnp.abs(t), axis=-1, keepdims=True),
                    KV_FLOOR) / 127.0
    return jnp.clip(jnp.round(t / s), -127.0, 127.0) * s


def _norm(kind, x, gain, eps):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * (1.0 + gain)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1, rotate-half."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _site_weight(p, site):
    return p["attn"][site] if site in ("wq", "wk", "wv", "wo") else \
        p["mlp"][site]


def hidden(cfg: dict, params, astates, tokens, n_prompt, *,
           control: bool = False, record: bool = False):
    """Final hidden states (S, D) of one sequence ``tokens`` (S,) whose
    first ``n_prompt`` positions are the prompt. ``astates``: per site,
    (L,) steps and offsets, or None for weight-only recipes. With
    ``record``, also returns each site's (min, max) input per layer (the
    calibration of the activation grid)."""
    a = cfg["arch"]
    L, D, H, Hkv = a["n_layers"], a["d_model"], a["n_heads"], a["n_kv_heads"]
    Dh = a.get("head_dim") or D // H
    per_channel = cfg["recipe"]["w_granularity"] == "per_channel"
    eps, norm = cfg["norm_eps"], a["norm"]
    S = tokens.shape[0]
    bits = jnp.asarray(layer_bits(cfg), jnp.float32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    qpos = jnp.arange(S)
    causal = qpos[None, :] <= qpos[:, None]
    decode_row = (qpos >= n_prompt)[:, None, None]

    def body(x, inp):
        p, b, ast = inp
        ranges = {}

        def lin(site, h):
            if record:
                ranges[site] = jnp.stack([jnp.min(h), jnp.max(h)])
            if ast is not None:
                h = fake_quant_act(h, ast[site][0], ast[site][1])
            w = fake_quant_weight(_site_weight(p, site), b, per_channel)
            return _mm(h, w, control)

        g1 = p["ln1"]["scale"].astype(jnp.float32) if "ln1" in p else None
        h = _norm(norm, x, g1, eps)
        q = _rope(lin("wq", h).reshape(S, H, Dh), a["rope_theta"])
        k = _rope(lin("wk", h).reshape(S, Hkv, Dh), a["rope_theta"])
        v = lin("wv", h).reshape(S, Hkv, Dh)
        rep = H // Hkv

        def attend(k, v):
            kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            s = _ein("qhd,khd->hqk", q, kk, control) * Dh ** -0.5
            s = jnp.where(causal[None], s, -1e30)
            return _ein("hqk,khd->qhd", jax.nn.softmax(s, -1), vv, control)

        o = jnp.where(decode_row, attend(kv_int8(k), kv_int8(v)),
                      attend(k, v))
        x = x + lin("wo", o.reshape(S, H * Dh))
        g2 = p["ln2"]["scale"].astype(jnp.float32) if "ln2" in p else None
        h = _norm(norm, x, g2, eps)
        m = jax.nn.silu(lin("w_gate", h)) * lin("w_up", h)
        x = x + lin("w_down", m)
        return x, (ranges if record else None)

    x, ranges = jax.lax.scan(body, x, (params["layers"], bits, astates),
                             length=L)
    g = params["final_norm"]["scale"].astype(jnp.float32) \
        if "final_norm" in params else None
    x = _norm(norm, x, g, eps)
    return (x, ranges) if record else x


def head(cfg: dict, params):
    if cfg["arch"]["tie_embeddings"]:
        return params["embed"].astype(jnp.float32).T
    return params["lm_head"].astype(jnp.float32)


def calibrate(cfg: dict, params, tokens):
    """Per site, (L,) step and offset of the 8-bit activation grid from the
    (min, max) of its input over the calibration ``tokens`` (N, S) on the
    weight-quantized float model (LSQ's asymmetric initialisation)."""
    def one(t):
        return hidden(cfg, params, None, t, t.shape[0], record=True)[1]
    ranges = jax.lax.map(one, tokens)          # site -> (N, L, 2)
    out = {}
    for site, r in ranges.items():
        lo = jnp.minimum(jnp.min(r[..., 0], axis=0), 0.0)
        hi = jnp.maximum(jnp.max(r[..., 1], axis=0), 0.0)
        out[site] = (jnp.maximum((hi - lo) / 255.0, 1e-8), lo)
    return out


def gaps(cfg: dict, params, astates, tokens, n_prompt, n_total):
    """For one served sequence (prompt + served tokens, right-padded), at
    each position that produced a served token: how far the reference's
    logit of that token lies below the reference's best. Returns (S,) with
    0 at positions that produced nothing."""
    h = hidden(cfg, params, astates, tokens, n_prompt)
    logits = jnp.matmul(h, head(cfg, params), precision=HIGHEST)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    served = jnp.take_along_axis(logits, nxt[:, None], axis=1)[:, 0]
    pos = jnp.arange(tokens.shape[0])
    produced = (pos >= n_prompt - 1) & (pos < n_total - 1)
    return jnp.where(produced, jnp.max(logits, -1) - served, 0.0)


def control_gaps(cfg: dict, params, astates, tokens, n_prompt, n_total):
    """The control on the same sequence: at each position, the gap of the
    token the float8 reference puts first, read on the float32 one."""
    h32 = hidden(cfg, params, astates, tokens, n_prompt)
    h8 = hidden(cfg, params, astates, tokens, n_prompt, control=True)
    w = head(cfg, params)
    ref = jnp.matmul(h32, w, precision=HIGHEST)
    top8 = jnp.argmax(_mm(h8, w, True), -1)
    picked = jnp.take_along_axis(ref, top8[:, None], axis=1)[:, 0]
    pos = jnp.arange(tokens.shape[0])
    produced = (pos >= n_prompt - 1) & (pos < n_total - 1)
    return jnp.where(produced, jnp.max(ref, -1) - picked, 0.0)
