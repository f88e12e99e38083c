"""Model operations of the window's decode tokens (each active slot at its
real position, ``bench/cost.decode_token_flops``) over the device time of
the decode program times the chip's bf16 peak, in percent. Model step
layer; moves itl_p95_ms."""
from bench import cost

PROGRAM = "jit_decode"


def read(run):
    prog = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not prog or prog["seconds"] <= 0 or not run.decode_contexts:
        return None
    flops = sum(cost.decode_token_flops(run.arch, c)
                for c in run.decode_contexts)
    return 100.0 * flops / (prog["seconds"] * run.peaks["bf16_flops"])
