"""Host work of a decode step, in ms: the mean per step of the program's
``serve.decode.dispatch`` span (the decode program's call until it
returns) plus its ``serve.decode.bookkeep`` span (the slot loop and the
step's metrics after the readback). The device is idle for most of it.
Engine decode host side layer; moves itl_p95_ms."""


def read(run):
    dur = {"serve.decode.dispatch": [], "serve.decode.bookkeep": []}
    for s in run.spans:
        if s.get("kind") == "span" and s.get("name") in dur:
            dur[s["name"]].append(s["dur_us"])
    steps = len(dur["serve.decode.dispatch"])
    if not steps:
        return None
    return sum(map(sum, dur.values())) / steps / 1e3
