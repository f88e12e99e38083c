"""First-class int8 KV cache for the serving engine.

Per-(token, head) absmax quantization of K/V entries — promoted out of
``models/transformer.py`` so every attention family and the serving engine
share one quantizer, one layout, and one accounting of HBM-per-slot:

  codes  int8   (..., S, H, D)    the K/V entries on the [-127, 127] grid
  scale  f32    (..., S, H, 1)    absmax/127, floored at KV_EPS/127

The floor is the value contract quantcheck (QL303) proves against: every
stored scale is >= :data:`KV_SCALE_MIN` (~7.9e-9), five orders of magnitude
above the float32 subnormal boundary, so no dequant multiply or
quantize-on-append divide can flush to zero. The serving trace entries
(``analysis/trace.py: serve_decode_entry``) declare these ranges.

:func:`int8_decode_attention` is the dequant-free score path: the cache is
never rematerialized in the KV dtype. Because the scale is constant over the
head dim, ``q . (codes * scale) == (q . codes) * scale`` exactly, so scores
contract q against the int8 codes and fold the scale in afterwards; on the
value side the per-token scale folds into the softmax probabilities before
the probs-x-codes contraction. HBM traffic per decode step is therefore the
int8 codes plus one f32 scalar per (token, head) — 1.125 B/elem at D=32
versus 2 (bf16) or 4 (f32) — which is what turns W4 weights into more
concurrent users per chip. (No int8 attention Pallas kernel exists yet —
the kernel table covers matmuls only — so this path expresses the
order-of-operations in XLA; a future kernel slots in behind the same
signature.)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30
KV_EPS = 1e-6
KV_QMAX = 127.0
# smallest scale the quantizer can store: the contract QL303 proves against
KV_SCALE_MIN = KV_EPS / KV_QMAX
# width of a TPU vector register's lane axis
LANES = 128


class KVQuantUnsupported(ValueError):
    """A model family was asked for an int8 KV cache it cannot have.

    Raised (instead of a bare ``TypeError``) by ``init_cache(kv_quant=True)``
    on families with no attention KV cache (ssm, rglru recurrent state) or a
    latent cache that is already compressed (MLA). ``reason`` is the
    machine-readable tag the serving engine and benchmarks surface.
    """

    def __init__(self, reason: str, detail: str):
        self.reason = reason
        super().__init__(f"{reason}: {detail}")


def kv_quantize(t: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, head) absmax int8 quantization of K/V entries."""
    t32 = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(t32), axis=-1, keepdims=True),
                        KV_EPS) / KV_QMAX
    codes = jnp.clip(jnp.round(t32 / scale), -KV_QMAX, KV_QMAX)
    return codes.astype(jnp.int8), scale


def kv_dequantize(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def quantized_cache(cache) -> bool:
    """Does this cache dict hold int8 codes + scales (vs raw K/V)?"""
    return isinstance(cache, dict) and "k_scale" in cache


def _pos_mask(pos, B: int, Smax: int, window: int) -> jax.Array:
    """(B, Smax) validity mask; ``pos`` is scalar or per-row (B,)."""
    k_pos = jnp.arange(Smax)
    pos = jnp.asarray(pos)
    posb = jnp.broadcast_to(pos.reshape(-1, 1) if pos.ndim else pos, (B, 1))
    valid = k_pos[None, :] <= posb
    if window > 0:
        valid &= k_pos[None, :] > posb - window
    return valid


def int8_decode_attention(q: jax.Array, k_codes: jax.Array,
                          k_scale: jax.Array, v_codes: jax.Array,
                          v_scale: jax.Array, pos, *,
                          window: int = 0) -> jax.Array:
    """Single-token decode attention directly over the int8 cache.

    q (B,1,Hq,D); codes (B,Smax,Hkv,D) int8; scales (B,Smax,Hkv,1) f32.
    ``pos`` is the current token's absolute position — a scalar for a
    uniform batch, or (B,) for the slot-based engine where every slot sits
    at its own depth. The per-(token, head) scales fold in *after* the
    contractions (keys: into the scores; values: into the probabilities),
    so the cache is never dequantized into a (B,Smax,Hkv,D) float tensor.
    """
    B, _, Hq, D = q.shape
    Smax, Hkv = k_codes.shape[1], k_codes.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, 1, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_codes.astype(jnp.float32))
    # scale (B,Smax,Hkv,1) -> (B,Hkv,1,1,Smax): constant over D, so folding
    # it here is exact (not an approximation of dequant-then-dot)
    k_s = k_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    s = s * k_s * (D ** -0.5)
    valid = _pos_mask(pos, B, Smax, window)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    pv = p * v_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", pv, v_codes.astype(jnp.float32))
    return out.reshape(B, 1, Hq, v_codes.shape[-1]).astype(q.dtype)


def append_form(cache) -> str:
    """How the slot decode appends each slot's token to the stacked
    (L, B, Smax, ...) cache leaves: ``"scatter"`` or ``"select"``, one
    form for every leaf, codes and scales, chosen by the head dim of K.

    A head dim that fills whole 128-wide lane tiles (128) keeps the code
    leaves row-major on a TPU, where the per-slot scatter writes in place.
    A narrower one (64) would be padded, so the chip lays the leaf out with
    the sequence axis in the lanes; a scatter into that axis makes XLA copy
    the whole stacked leaf to another layout and back on every step. The
    positional select writes in the layout the leaf already has. The scales
    follow the codes: in a stacked-scan decode the per-layer select of a
    scale costs more than the whole-scale copies it saves.
    """
    return "scatter" if cache["k"].shape[-1] % LANES == 0 else "select"


def append_token(buf: jax.Array, li, pos: jax.Array, val: jax.Array,
                 form: str) -> Tuple[jax.Array, jax.Array]:
    """Write each slot's (B, 1, ...) entry at its own position ``pos`` (B,)
    into layer ``li`` of a (L, B, Smax, ...) cache leaf, in ``form`` (see
    :func:`append_form`). Returns the leaf and its layer ``li`` after the
    write, which attention reads. Both forms leave the same bits; a
    position at or past Smax writes nothing."""
    val = val.astype(buf.dtype)
    if form == "scatter":
        buf = buf.at[li, jnp.arange(val.shape[0]), pos].set(val[:, 0])
        return buf, jax.lax.dynamic_index_in_dim(buf, li, 0, False)
    hit = jnp.arange(buf.shape[2])[None, :] == pos[:, None]
    hit = hit.reshape(hit.shape + (1,) * (buf.ndim - 3))
    layer = jnp.where(hit, val,
                      jax.lax.dynamic_index_in_dim(buf, li, 0, False))
    if buf.shape[-1] == 1:
        # a scale layer: attention wants it in another layout than the
        # stacked scales have. Held apart by the barrier, only this small
        # layer is laid out anew, never the whole stacked leaf.
        layer = jax.lax.optimization_barrier(layer)
        return jax.lax.dynamic_update_index_in_dim(buf, layer, li, 0), layer
    # a code layer is read back from the written cache: one copy of it
    buf = jax.lax.dynamic_update_index_in_dim(buf, layer, li, 0)
    return buf, jax.lax.dynamic_index_in_dim(buf, li, 0, False)


def cache_bytes(cache) -> int:
    """Total bytes held by a cache pytree (codes + scales + fp arrays)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache))


def hbm_per_slot_bytes(cache, slots: int) -> int:
    """Bytes of KV state one decode slot pins in HBM, from the live cache
    pytree (codes + scales, or raw K/V). The single accessor the serve
    bench row and the memcheck weight-traffic check (QL403) both read —
    any accounting drift between them is a bug, not a rounding choice."""
    return cache_bytes(cache) // slots


def hbm_per_slot_mib(cache, slots: int) -> float:
    """MiB of KV state one decode slot pins in HBM."""
    return hbm_per_slot_bytes(cache, slots) / 2**20


def unsupported(family: str, detail: str) -> KVQuantUnsupported:
    """Named error for families with no quantizable KV cache."""
    return KVQuantUnsupported(f"kv_quant_unsupported:{family}", detail)


def check_kv_quant_supported(cfg, kv_quant: bool,
                             family: Optional[str] = None) -> None:
    """Shared guard for ``init_cache(kv_quant=...)`` across model families."""
    if not kv_quant:
        return
    fam = family or getattr(cfg, "family", "?")
    if fam in ("ssm", "hybrid"):
        raise unsupported(
            fam, f"{cfg.name}: the {fam} family keeps recurrent state "
            "(conv tail / SSM state / LRU hidden), not an attention KV "
            "cache — there is nothing to int8-quantize per token; serve "
            "it with kv_quant=False")
    if getattr(cfg, "use_mla", False):
        raise unsupported(
            "mla", f"{cfg.name}: MLA caches the compressed latent "
            "(kv_lora_rank per token), which is already the memory "
            "optimization — int8 per-head scales do not apply to the "
            "latent layout; serve it with kv_quant=False")
