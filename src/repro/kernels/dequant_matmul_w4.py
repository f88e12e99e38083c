"""Pallas TPU kernels: weight-only dequant-matmul (the int4/int8 serving path).

W4: weights are nibble-packed uint8 (two 4-bit codes per byte along K). Each
K tile is unpacked and dequantized *in VMEM* right before the MXU matmul, so
HBM traffic for the weight is 0.5 bytes/element — the memory-roofline win
that makes int4 decode ~4x lighter than bf16 (see EXPERIMENTS.md §Perf).
W8 is the same kernel without the unpack (1 byte/element, 2x lighter).

    out[M, N] = x[M, K] @ (scale * (unpack(codes)[K, N] - zero))

Grid (M/bm, N/bn, K/bk); float32 VMEM accumulator across K steps. The
batched-expert variant prepends the expert axis to the grid —
(E, M/bm, N/bn, K/bk) with K innermost so the accumulator stays coherent per
(e, i, j) tile — serving stacked MoE expert weights (E, K, N) without ever
materializing the dequantized stack in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.envelope import assert_grid_divisible


def _codes_f32(codes):
    """uint8 codes -> float32, widened through int32: Mosaic has no direct
    uint8 -> float32 cast, and every code value is exact in both."""
    return codes.astype(jnp.int32).astype(jnp.float32)


def _unpack_f32(codes):
    """(bk//2, bn) packed uint8 -> (bk, bn) float32 codes, pairs along K."""
    c = codes.astype(jnp.int32)
    lo = (c & 0xF).astype(jnp.float32)
    hi = ((c >> 4) & 0xF).astype(jnp.float32)
    bk2, bn = codes.shape
    return jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)


def _kernel(x_ref, c_ref, scale_ref, zero_ref, o_ref, acc_ref, *, k_steps,
            packed):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = c_ref[...]  # (bk//2, bn) uint8 if packed else (bk, bn)
    q = _unpack_f32(codes) if packed else _codes_f32(codes)
    w = scale_ref[...] * (q - zero_ref[...])  # dequant in VMEM
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_batched(x_ref, c_ref, scale_ref, zero_ref, o_ref, acc_ref, *,
                    k_steps, packed):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = c_ref[0]  # expert-sliced block: (bk//2, bn) or (bk, bn)
    q = _unpack_f32(codes) if packed else _codes_f32(codes)
    w = scale_ref[0] * (q - zero_ref[0])
    x = x_ref[0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _pad_mkn(x, codes, scale, zero, M, K, N, block_m, block_k, block_n,
             packed, lead=()):
    """Pad every operand to block multiples (zero padding is exact for
    matmul; padded x rows / K columns contribute nothing)."""
    z = ((0, 0),) * len(lead)
    Mp, Kp, Np = (-M % block_m, -K % block_k, -N % block_n)
    x = jnp.pad(x, z + ((0, Mp), (0, Kp)))
    codes = jnp.pad(codes, z + ((0, Kp // 2 if packed else Kp), (0, Np)))
    scale = jnp.pad(jnp.broadcast_to(jnp.asarray(scale, jnp.float32),
                                     lead + (1, N)), z + ((0, 0), (0, Np)))
    zero = jnp.pad(jnp.broadcast_to(jnp.asarray(zero, jnp.float32),
                                    lead + (1, N)), z + ((0, 0), (0, Np)))
    return x, codes, scale, zero, M + Mp, K + Kp, N + Np


@functools.partial(jax.jit, static_argnames=("packed", "block_m", "block_n",
                                             "block_k", "out_dtype",
                                             "interpret"))
def dequant_matmul(x, codes, scale, zero, *, packed: bool,
                   block_m: int = 128, block_n: int = 128, block_k: int = 512,
                   out_dtype=None, interpret: bool = False):
    """x (M, K); codes (K//2, N) packed uint8 or (K, N) uint8;
    scale/zero (1, N) or (1, 1)."""
    M, K = x.shape
    N = codes.shape[1]
    if packed:
        assert codes.shape[0] * 2 == K, "codes must be K/2 nibble-packed rows"
    else:
        assert codes.shape[0] == K
    out_dtype = out_dtype or x.dtype
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert block_k % 2 == 0 or not packed
    x, codes, scale, zero, Mf, Kf, Nf = _pad_mkn(
        x, codes, scale, zero, M, K, N, block_m, block_k, block_n, packed)
    assert_grid_divisible("dequant_matmul", M=(Mf, block_m), K=(Kf, block_k),
                          N=(Nf, block_n))
    k_steps = Kf // block_k
    grid = (Mf // block_m, Nf // block_n, k_steps)
    bkc = block_k // 2 if packed else block_k
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkc, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mf, Nf), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, codes, scale, zero)
    return out[:M, :N]


def dequant_matmul_w4(x, codes, scale, zero, *, block_m: int = 128,
                      block_n: int = 128, block_k: int = 512,
                      out_dtype=None, interpret: bool = False):
    """x (M, K); codes (K//2, N) uint8; scale/zero (1, N) or (1, 1)."""
    return dequant_matmul(x, codes, scale, zero, packed=True, block_m=block_m,
                          block_n=block_n, block_k=block_k,
                          out_dtype=out_dtype, interpret=interpret)


def dequant_matmul_w8(x, codes, scale, zero, *, block_m: int = 128,
                      block_n: int = 128, block_k: int = 512,
                      out_dtype=None, interpret: bool = False):
    """x (M, K); codes (K, N) uint8; scale/zero (1, N) or (1, 1). Weight-only
    int8 serving (no activation states)."""
    return dequant_matmul(x, codes, scale, zero, packed=False,
                          block_m=block_m, block_n=block_n, block_k=block_k,
                          out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("packed", "block_m", "block_n",
                                             "block_k", "out_dtype",
                                             "interpret"))
def dequant_matmul_batched(x, codes, scale, zero, *, packed: bool,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 512, out_dtype=None,
                           interpret: bool = False):
    """Grid-extended per-expert dequant-matmul (MoE serving path).

    x (E, M, K); codes (E, K//2, N) packed uint8 or (E, K, N) uint8;
    scale/zero broadcastable to (E, 1, N). out (E, M, N) = per-expert
    x[e] @ dequant(codes[e]).
    """
    E, M, K = x.shape
    N = codes.shape[-1]
    if packed:
        assert codes.shape[1] * 2 == K
    else:
        assert codes.shape[1] == K
    out_dtype = out_dtype or x.dtype
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert block_k % 2 == 0 or not packed
    x, codes, scale, zero, Mf, Kf, Nf = _pad_mkn(
        x, codes, scale, zero, M, K, N, block_m, block_k, block_n, packed,
        lead=(E,))
    assert_grid_divisible("dequant_matmul_batched", M=(Mf, block_m),
                          K=(Kf, block_k), N=(Nf, block_n))
    k_steps = Kf // block_k
    grid = (E, Mf // block_m, Nf // block_n, k_steps)
    bkc = block_k // 2 if packed else block_k
    out = pl.pallas_call(
        functools.partial(_kernel_batched, k_steps=k_steps, packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bkc, block_n), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, 1, block_n), lambda e, i, j, k: (e, 0, j)),
            pl.BlockSpec((1, 1, block_n), lambda e, i, j, k: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Mf, Nf), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, codes, scale, zero)
    return out[:, :M, :N]
