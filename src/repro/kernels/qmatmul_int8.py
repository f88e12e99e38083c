"""Pallas TPU kernel: W8A8 integer matmul (serving path for the paper's
8-bit recipes).

int8 x int8 -> int32 accumulation on the MXU, with the affine corrections
applied on the final K step. For activations a = a_scale * (A_q - a_zero) and
weights b = b_scale * (B_q - b_zero) (b_zero = 0 recovers the symmetric
weight case):

    out = a_scale * b_scale * (A_q @ B_q - a_zero * colsum(B_q)
                               - rowsum(A_q) * b_zero + K * a_zero * b_zero)

colsum/rowsum are computed once outside the kernel on the *unpadded* codes,
so the rank-1 corrections are exact regardless of tile padding. Blocking:
(block_m, block_k) x (block_k, block_n) tiles resident in VMEM, grid
(M/bm, N/bn, K/bk) with an int32 VMEM scratch accumulator; K is the
innermost (sequential) grid axis so the accumulator persists across K steps.
Tile sizes default to MXU-aligned multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.envelope import assert_grid_divisible


def _kernel(a_ref, b_ref, ascale_ref, azero_ref, bscale_ref, bzero_ref,
            colsum_ref, rowsum_ref, o_ref, acc_ref, *, k_steps, k_real):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU (Mosaic rejects an int32 x int32
    # dot); the int32 accumulation is exact either way
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == k_steps - 1)
    def _finish():
        acc = acc_ref[...].astype(jnp.float32)
        a_z = azero_ref[...]
        b_z = bzero_ref[...]  # (1, bn)
        corr = (a_z * colsum_ref[...].astype(jnp.float32)
                + rowsum_ref[...].astype(jnp.float32) * b_z
                - k_real * a_z * b_z)
        o_ref[...] = (ascale_ref[...] * bscale_ref[...] * (acc - corr)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def qmatmul_int8(a_q, b_q, a_scale, a_zero, b_scale, b_zero=None, *,
                 block_m: int = 128, block_n: int = 128, block_k: int = 512,
                 out_dtype=jnp.float32, interpret: bool = False):
    """a_q (M, K) int8, b_q (K, N) int8, b_scale/b_zero (1, N) or (1, 1).
    ``b_zero=None`` means symmetric weights (b = b_scale * b_q)."""
    M, K = a_q.shape
    N = b_q.shape[1]
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    # rank-1 corrections on the unpadded codes (exact under zero padding)
    colsum = jnp.sum(b_q.astype(jnp.int32), axis=0, keepdims=True)  # (1, N)
    rowsum = jnp.sum(a_q.astype(jnp.int32), axis=1, keepdims=True)  # (M, 1)
    # pad every dim to a block multiple: out-of-bounds Pallas tiles are
    # undefined, and zero padding is exact for matmul
    Mp, Kp, Np = (-M % block_m, -K % block_k, -N % block_n)
    a_q = jnp.pad(a_q, ((0, Mp), (0, Kp)))
    b_q = jnp.pad(b_q, ((0, Kp), (0, Np)))
    b_scale = jnp.pad(jnp.broadcast_to(jnp.asarray(b_scale, jnp.float32),
                                       (1, N)), ((0, 0), (0, Np)))
    if b_zero is None:
        b_zero = jnp.zeros((1, N), jnp.float32)
    b_zero = jnp.pad(jnp.broadcast_to(jnp.asarray(b_zero, jnp.float32),
                                      (1, N)), ((0, 0), (0, Np)))
    colsum = jnp.pad(colsum, ((0, 0), (0, Np)))
    rowsum = jnp.pad(rowsum, ((0, Mp), (0, 0)))
    Mf, Kf, Nf = M + Mp, K + Kp, N + Np
    assert_grid_divisible("qmatmul_int8", M=(Mf, block_m), K=(Kf, block_k),
                          N=(Nf, block_n))
    k_steps = pl.cdiv(Kf, block_k)
    a_scale = jnp.broadcast_to(jnp.asarray(a_scale, jnp.float32), (1, 1))
    a_zero = jnp.broadcast_to(jnp.asarray(a_zero, jnp.float32), (1, 1))
    grid = (Mf // block_m, Nf // block_n, k_steps)
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, k_real=float(K)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mf, Nf), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(a_q, b_q, a_scale, a_zero, b_scale, b_zero, colsum, rowsum)
    return out[:M, :N]
