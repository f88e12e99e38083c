"""Mean of the program's ``serve.prefill`` spans in the window (each ends
with the host readback of the first tokens, so it covers the device's
work). Engine prefill layer; moves ttft_p95_ms."""


def read(run):
    return run.span_mean_ms("serve.prefill")
