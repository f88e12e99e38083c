"""Roofline share of the W4 dequant-matmul kernel inside the decode
program, in percent: the least time the chip could take for the calls
(the larger of operations over the bf16 peak and bytes over HBM bandwidth,
``bench/cost.w4_call`` at the decode's rows) over the measured device time
of those calls. Kernel layer; moves itl_p95_ms."""
from bench import cost

PROGRAM = "jit_decode"


def read(run):
    # dequant_matmul_w4 runs as the jitted ``dequant_matmul`` (packed
    # codes); no site of these configurations is W8A16, so every
    # dequant_matmul call in the decode program is a W4 call, and the call
    # count below must match the W4 sites the cost counts
    hit = run.kernel_time(PROGRAM, "dequant_matmul")
    steps = (run.trace or {}).get("programs", {}).get(PROGRAM, {}).get("count")
    if not hit or not steps:
        return None
    seconds, calls = hit
    ops, byt, per_step = cost.w4_decode_step(
        run.arch, run.cfg["engine"]["slots"], run.layer_bits,
        run.cfg["recipe"]["w_granularity"] == "per_channel")
    if per_step == 0 or abs(calls - per_step * steps) > 0.01 * calls:
        return None  # the trace's calls are not the ones the cost counts
    least = steps * max(ops / run.peaks["bf16_flops"],
                        byt / run.peaks["hbm_bytes_per_s"])
    run.note("dequant_matmul_w4_roofline_bound",
             "memory" if byt / run.peaks["hbm_bytes_per_s"] >
             ops / run.peaks["bf16_flops"] else "compute")
    return 100.0 * least / seconds
