"""Shared model components: norms, RoPE, MLPs, embeddings, fused loss."""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.context import QuantCtx


# ------------------------------------------------------- layer stacks / scan
def stack_layers(layers):
    """Restack per-layer param trees into (L, ...) arrays for ``lax.scan``.

    Mixed-precision PTQ can finalize different layers to structurally
    different trees (QTensor carries static bits/packing in its treedef) or
    to same-treedef trees with different leaf shapes (e.g. a per-channel
    granularity rule on one layer), so when the layers are heterogeneous in
    either way this falls back to a plain list — consumed by the
    eager-unroll path of ``scan_layers``. QTensor leaves in either form hit
    the kernel-backed deploy matmuls via ``ctx.linear`` (the unrolled layers
    each dispatch their own bit-width to the matching kernel).
    """
    same_tree = len({jax.tree.structure(l) for l in layers}) == 1
    if same_tree and len({tuple(jnp.shape(x) for x in jax.tree.leaves(l))
                          for l in layers}) == 1:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return list(layers)


def scan_layers(body, carry, layers, *aux, names=None):
    """``lax.scan`` body over per-layer params, with a mixed-precision path.

    ``body(carry, p_l) -> (carry, out)`` — or ``body(carry, (p_l, *aux_l))``
    when ``aux`` (stacked (L, ...) arrays sliced per layer) is given. When
    ``layers`` is a stacked pytree this is exactly ``lax.scan``; when it is a
    list of heterogeneous per-layer trees the loop unrolls eagerly (bigger
    HLO, same math). ``names`` (one per layer) reaches the unrolled body as
    ``name=``: each layer's sites then carry their canonical
    ``layers.<i>.<site>`` names, so per-layer recipe rules and activation
    states resolve at deploy time exactly as they did during PTQ.
    """
    if isinstance(layers, (list, tuple)):
        outs = []
        for i, p_l in enumerate(layers):
            aux_l = tuple(jax.tree.map(lambda a: a[i], a_) for a_ in aux)
            kw = {} if names is None else {"name": names[i]}
            carry, out = body(carry, (p_l, *aux_l) if aux else p_l, **kw)
            outs.append(out)
        if not outs or all(o is None for o in outs):
            return carry, None
        return carry, jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return jax.lax.scan(body, carry, (layers, *aux) if aux else layers)


# ------------------------------------------------------------------- norms
def rmsnorm(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))  # gamma stored zero-centered
    return y.astype(x.dtype)


def layernorm(x: jax.Array, scale: Optional[jax.Array], bias: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(kind: str, x: jax.Array, p: Optional[dict]) -> jax.Array:
    """kind: rmsnorm | layernorm | layernorm_nonparam (OLMo)."""
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"] if p else None)
    if kind == "layernorm":
        return layernorm(x, p.get("scale") if p else None,
                         p.get("bias") if p else None)
    if kind == "layernorm_nonparam":
        return layernorm(x, None, None)
    raise ValueError(f"unknown norm {kind!r}")


def norm_params(kind: str, d: int, dtype) -> Optional[dict]:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}  # gamma, applied as (1+gamma)
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if kind == "layernorm_nonparam":
        return None
    raise ValueError(kind)


# -------------------------------------------------------------------- rope
def rope_sin_cos(positions: jax.Array, head_dim: int, theta: float):
    """positions (...,) int -> sin/cos (..., head_dim/2) in float32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, S, H, D); sin/cos: (B or 1, S, D/2). Rotate-half convention."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    s = sin[:, :, None, :]
    c = cos[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- mlp
def mlp(p: dict, x: jax.Array, ctx: QuantCtx, name: str, act: str = "swiglu",
        batch_dims: int = 0) -> jax.Array:
    """SwiGLU or GELU MLP; all matmuls quantizable via ctx."""
    if act in ("swiglu", "geglu"):
        g = ctx.linear(f"{name}.w_gate", x, p["w_gate"], batch_dims=batch_dims)
        u = ctx.linear(f"{name}.w_up", x, p["w_up"], batch_dims=batch_dims)
        nl = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = nl(g.astype(jnp.float32)).astype(x.dtype) * u
    elif act == "gelu":
        h = ctx.linear(f"{name}.w_up", x, p["w_up"], p.get("b_up"),
                       batch_dims=batch_dims)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    else:
        raise ValueError(f"unknown act {act!r}")
    return ctx.linear(f"{name}.w_down", h, p["w_down"], p.get("b_down"),
                      batch_dims=batch_dims)


def mlp_params(key, d_model: int, d_ff: int, act: str, dtype,
               lead: tuple = ()) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    std_in = d_model**-0.5
    std_out = d_ff**-0.5
    p = {
        "w_up": jax.random.normal(k1, lead + (d_model, d_ff), dtype) * std_in,
        "w_down": jax.random.normal(k2, lead + (d_ff, d_model), dtype) * std_out,
    }
    if act in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k3, lead + (d_model, d_ff), dtype) * std_in
    return p


# ------------------------------------------------------------------ loss
def fused_cross_entropy(x: jax.Array, w_out: jax.Array, labels: jax.Array,
                        mask: Optional[jax.Array] = None,
                        chunk: int = 512, logit_scale: float = 1.0) -> jax.Array:
    """Mean next-token CE without materializing (B, S, V) logits.

    Scans over sequence chunks; each chunk is rematerialized in the backward
    pass (jax.checkpoint), so peak memory is O(B * chunk * V) instead of
    O(B * S * V) — required for train_4k at 152k-256k vocabularies.
    """
    B, S, D = x.shape
    chunk = min(chunk, S)
    n_chunks = S // chunk
    rem = S - n_chunks * chunk
    if rem:  # fold remainder into one extra masked chunk via padding
        pad = chunk - rem
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask if mask is not None else
                       jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
        n_chunks += 1
    elif mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    xc = x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    mc = mask.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_loss(xb, lb, mb):
        logits = (xb.astype(jnp.float32) @ w_out.astype(jnp.float32)) * logit_scale
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mb), jnp.sum(mb)

    def body(carry, inp):
        tot, cnt = carry
        l, c = chunk_loss(*inp)
        return (tot + l, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                 (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def embed_tokens(embed: jax.Array, tokens: jax.Array, mult: float = 1.0) -> jax.Array:
    return jnp.take(embed, tokens, axis=0) * mult


_AMBIENT_MESH = [None]


@contextlib.contextmanager
def ambient_mesh(mesh):
    """Make the physical mesh visible to model-internal sharding hints.

    (The Auto-axis mesh context does not populate get_abstract_mesh inside
    jit tracing — verified on jax 0.8 — so hints need the concrete mesh.)
    """
    _AMBIENT_MESH.append(mesh)
    try:
        yield
    finally:
        _AMBIENT_MESH.pop()


def get_ambient_mesh():
    return _AMBIENT_MESH[-1]


def shard_hint(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint that no-ops without an ambient mesh.

    ``axes`` entries: None, a mesh axis name, "dp" (expands to the data(/pod)
    axes present), or a tuple of names. Axes missing from the ambient mesh
    degrade to None, so the same model code runs on CPU tests and under the
    production mesh. Indivisible dims degrade to None per-axis.
    """
    mesh = get_ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def size_of(a):
        n = 1
        for nm in (a if isinstance(a, tuple) else (a,)):
            n *= mesh.shape[nm]
        return n

    spec = []
    for dim, a in zip(x.shape, axes):
        if a == "dp":
            a = tuple(n for n in ("pod", "data") if n in names)
        elif isinstance(a, str):
            a = (a,) if a in names else ()
        elif isinstance(a, tuple):
            a = tuple(n for n in a if n in names)
        elif a is None:
            a = ()
        a = tuple(a)
        if not a or dim % size_of(a) != 0:
            spec.append(None)
        else:
            spec.append(a if len(a) > 1 else a[0])
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec)))
