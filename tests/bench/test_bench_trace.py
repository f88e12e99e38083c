"""Trace reduction on a small trace in the layout of a recorded v5e one,
and the per-layer readers that turn it into metrics."""
import pathlib

import pytest

from bench import cost, run, trace_reduce
from bench.metrics import reader
from bench.model import load_config

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_decode.pbtxt"
MS = 1e-3


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_text_proto(FIXTURE.read_text()))


def test_window_busy_and_programs(red):
    # window is the bench.window host span: 1..11 ms; busy 1.5-3.5, 4-8, 9-10
    assert red["window_s"] == pytest.approx(10 * MS)
    assert red["busy_s"] == pytest.approx(7 * MS, rel=1e-6)
    assert red["programs"]["jit_decode"] == pytest.approx(
        {"seconds": 5 * MS, "count": 2})
    assert red["programs"]["jit_prefill_insert"] == pytest.approx(
        {"seconds": 2 * MS, "count": 1})


def test_operations_outside_the_window_and_loop_events_are_left_out(red):
    names = " ".join(red["ops"])
    assert "%fusion.0 " not in names          # before the window
    assert "%while" not in names              # encloses the body's ops
    # the loop's own time past its body (7.0-7.5 ms) is busy, in no leaf
    assert sum(red["ops"].values()) == pytest.approx(6.5 * MS, rel=1e-6)


def test_kernel_calls_are_attributed_to_their_program(red):
    rec = run.RunRecord(load_config("olmo-1b-w4a16"), None, {}, [], red,
                        {})
    seconds, calls = rec.kernel_time("jit_decode", "dequant_matmul")
    assert seconds == pytest.approx(2.8 * MS) and calls == 4
    seconds, calls = rec.kernel_time("jit_prefill_insert", "dequant_matmul")
    assert seconds == pytest.approx(1 * MS) and calls == 1


def test_idle_gaps_named_by_the_host_span_open_in_them(red):
    gaps = red["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.pump", "bench.pump", "bench.pump",
                                    "bench.window"]
    assert [g[1] for g in gaps] == pytest.approx([1 * MS, 1 * MS, 0.5 * MS,
                                                  0.5 * MS], rel=1e-5)


def test_idle_reader(red):
    rec = run.RunRecord(load_config("olmo-1b-w4a16"), None, {}, [], red,
                        {})
    assert reader("device_idle_pct.serve")(rec) == pytest.approx(30.0,
                                                                 rel=1e-5)


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _decode_trace(seconds_w4, calls, steps, seconds_decode):
    return {"programs": {"jit_decode": {"seconds": seconds_decode,
                                        "count": steps}},
            "ops_in_program": {"jit_decode": {
                '%dequant_matmul.3 = bf16[32,8192] custom-call(bf16[32,2048] '
                '%f), custom_call_target="tpu_custom_call"':
                    {"seconds": seconds_w4, "count": calls}}}}


def test_w4_roofline_reader_by_hand():
    cfg = load_config("olmo-1b-w4a16")
    steps = 100
    rec = run.RunRecord(cfg, None, {}, [],
                        _decode_trace(0.9, 7 * 16 * steps, steps, 2.0), PEAKS)
    ops = byt = 0.0
    for _, k, n in cost.linear_sites(cfg["arch"]):
        o, b = cost.w4_call(32, k, n, per_channel=True)
        ops, byt = ops + 16 * o, byt + 16 * b
    least = steps * max(ops / 197e12, byt / 819e9)
    assert reader("dequant_matmul_w4_roofline")(rec) == pytest.approx(
        100 * least / 0.9)
    assert rec.notes["dequant_matmul_w4_roofline_bound"] == "memory"


def test_w4_roofline_reader_refuses_calls_it_cannot_account_for():
    cfg = load_config("olmo-1b-w4a16")
    rec = run.RunRecord(cfg, None, {}, [],
                        _decode_trace(0.9, 5 * 16 * 100, 100, 2.0), PEAKS)
    assert reader("dequant_matmul_w4_roofline")(rec) is None


def test_readers_return_nothing_without_a_trace():
    rec = run.RunRecord(load_config("smollm-135m-w4a8"), None, {}, [],
                        {}, PEAKS)
    for name in ("decode_mfu_pct", "dequant_matmul_w4_roofline",
                 "device_idle_pct.serve", "prefill_ms", "decode_step_ms"):
        assert reader(name)(rec) is None


def test_decode_mfu_reader_by_hand():
    cfg = load_config("smollm-135m-w4a8")
    rec = run.RunRecord(cfg, None, {}, [],
                        _decode_trace(0.1, 1, 1, 0.5), PEAKS)
    rec.decode_contexts = [100, 300]
    want = sum(cost.decode_token_flops(cfg["arch"], c) for c in (100, 300))
    assert reader("decode_mfu_pct")(rec) == pytest.approx(
        100 * want / (0.5 * 197e12))
