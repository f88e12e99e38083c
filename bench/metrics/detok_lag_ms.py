"""How long a token waits between the engine and the client, in ms: over
the program's ``serve.detok`` spans (the scheduler's detokenize thread),
the sum of ``lag_us_sum`` over the sum of ``tokens``, where a token's lag
is its callback's start less the time the scheduler put it in the queue.
Client delivery layer; moves itl_p95_ms."""


def read(run):
    tokens = lag_us = 0
    for s in run.spans:
        if s.get("kind") == "span" and s.get("name") == "serve.detok":
            attrs = s.get("attrs", {})
            tokens += attrs.get("tokens", 0)
            lag_us += attrs.get("lag_us_sum", 0.0)
    if not tokens:
        return None
    return lag_us / tokens / 1e3
