"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick of every roofline and utilization share, so they
count what the work requires, not what a kernel happens to move: no padding,
no re-reads, no recomputation.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

BF16 = 2
F32 = 4


def linear_sites(arch: dict) -> List[Tuple[str, int, int]]:
    """(site, d_in, d_out) of one transformer layer's quantized linears."""
    D, H, Hkv = arch["d_model"], arch["n_heads"], arch["n_kv_heads"]
    Dh = arch.get("head_dim") or D // H
    F = arch["d_ff"]
    return [("wq", D, H * Dh), ("wk", D, Hkv * Dh), ("wv", D, Hkv * Dh),
            ("wo", H * Dh, D), ("w_gate", D, F), ("w_up", D, F),
            ("w_down", F, D)]


def w4_call(m: int, k: int, n: int, per_channel: bool = True
            ) -> Tuple[float, float]:
    """One W4 dequant-matmul ``x (m, k) bf16 @ W (k, n)`` with nibble-packed
    codes: 2mkn operations; bytes of the packed codes, the f32 scale and
    zero (per output channel, or one each), x read and the bf16 result
    written."""
    grid = 2 * (n if per_channel else 1) * F32
    return (2.0 * m * k * n,
            k * n / 2 + grid + m * k * BF16 + m * n * BF16)


def decode_token_flops(arch: dict, context: int) -> float:
    """Model operations of one decode token whose query attends over
    ``context`` positions: every linear of every layer, the vocabulary
    projection, and the attention scores and weighted values."""
    L, D, H = arch["n_layers"], arch["d_model"], arch["n_heads"]
    Dh = arch.get("head_dim") or D // H
    lin = sum(k * n for _, k, n in linear_sites(arch))
    return 2.0 * (L * lin + D * arch["vocab"]) + 4.0 * L * H * Dh * context


def kv_bytes_per_token(arch: dict) -> int:
    """int8 K and V codes plus one f32 scale per (token, head), all layers."""
    L, D, H, Hkv = (arch["n_layers"], arch["d_model"], arch["n_heads"],
                    arch["n_kv_heads"])
    Dh = arch.get("head_dim") or D // H
    return L * 2 * Hkv * (Dh + F32)


def weight_bytes(arch: dict, layer_bits: List[int]) -> Dict[str, float]:
    """Bytes one decode step reads of the weights: the integer codes of
    every layer at its bit width, and the bf16 embedding / vocabulary
    head (read once when tied)."""
    lin = sum(k * n for _, k, n in linear_sites(arch))
    codes = sum(lin * b / 8 for b in layer_bits)
    head = arch["vocab"] * arch["d_model"] * BF16
    if not arch.get("tie_embeddings"):
        head *= 2
    return {"codes": codes, "head": head}


def decode_step(arch: dict, layer_bits: List[int], contexts: List[int]
                ) -> Tuple[float, float]:
    """Operations and bytes of one decode step of the slot engine, as the
    algorithm needs them: the active slots at their ``contexts``, and the
    bytes of the weights plus the KV entries those slots attend over."""
    w = weight_bytes(arch, layer_bits)
    flops = sum(decode_token_flops(arch, c) for c in contexts)
    kv = kv_bytes_per_token(arch) * sum(contexts)
    return flops, w["codes"] + w["head"] + kv


def w4_decode_step(arch: dict, slots: int, layer_bits: List[int],
                   per_channel: bool) -> Tuple[float, float, int]:
    """Operations, bytes and call count of every W4 kernel call in one
    decode step of ``slots`` rows."""
    ops = byt = 0.0
    calls = 0
    for b in layer_bits:
        if b != 4:
            continue
        for _, k, n in linear_sites(arch):
            o, y = w4_call(slots, k, n, per_channel)
            ops, byt, calls = ops + o, byt + y, calls + 1
    return ops, byt, calls
