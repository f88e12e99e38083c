"""Mean of the program's ``serve.decode_step`` spans in the window (each
ends with the host readback of the step's tokens). Engine decode layer;
moves itl_p95_ms."""


def read(run):
    return run.span_mean_ms("serve.decode_step")
