"""Share of the traced window in which no operation ran on the device, in
percent (1 - busy / window). Device layer; moves itl_p95_ms."""


def read(run):
    t = run.trace or {}
    if not t.get("window_s") or t.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
