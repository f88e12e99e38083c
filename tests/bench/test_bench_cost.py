"""The operation and byte counts behind every roofline and utilization
share, against counts made by hand."""
import pytest

from bench import cost
from bench.model import load_config

SMOLLM = load_config("smollm-135m-w4a8")["arch"]
OLMO = load_config("olmo-1b-w4a16")["arch"]


def test_w4_call_by_hand():
    # x (64, 576) bf16 @ W (576, 1536) int4, per-channel f32 scale + zero
    ops, byt = cost.w4_call(64, 576, 1536, per_channel=True)
    assert ops == 2 * 64 * 576 * 1536
    assert byt == 576 * 1536 / 2 + 2 * 1536 * 4 + 64 * 576 * 2 + 64 * 1536 * 2


def test_w4_call_per_tensor_grid_is_two_scalars():
    _, byt = cost.w4_call(1, 2, 2, per_channel=False)
    assert byt == 2 * 2 / 2 + 2 * 4 + 1 * 2 * 2 + 1 * 2 * 2


def test_decode_token_flops_by_hand():
    # olmo-1b: per layer 4 x 2048^2 attention + 3 x 2048 x 8192 MLP
    lin = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    head = 2048 * 50304
    attn = 4 * 16 * 16 * 128 * 1000
    assert cost.decode_token_flops(OLMO, 1000) == 2 * (lin + head) + attn


def test_kv_bytes_per_token_by_hand():
    # smollm: (2 x 3 x 64 codes + 2 x 3 x 4 scale bytes) x 30 layers
    assert cost.kv_bytes_per_token(SMOLLM) == (2 * 3 * 64 + 2 * 3 * 4) * 30
    assert cost.kv_bytes_per_token(OLMO) == (2 * 16 * 128 + 2 * 16 * 4) * 16


def test_decode_step_by_hand():
    bits = [8] + [4] * 29
    lin = 2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536
    ctx = [300, 1200]
    ops, byt = cost.decode_step(SMOLLM, bits, ctx)
    assert ops == sum(cost.decode_token_flops(SMOLLM, c) for c in ctx)
    codes = lin * 1 + 29 * lin / 2
    head = 49152 * 576 * 2  # tied: read once
    kv = (2 * 3 * 64 + 2 * 3 * 4) * 30 * sum(ctx)
    assert byt == pytest.approx(codes + head + kv)


def test_w4_decode_step_counts_only_the_w4_layers():
    ops, byt, calls = cost.w4_decode_step(SMOLLM, 64, [8] + [4] * 29, False)
    assert calls == 29 * 7
    one = sum(cost.w4_call(64, k, n, False)[0]
              for _, k, n in cost.linear_sites(SMOLLM))
    assert ops == 29 * one
