"""The scheduler's own host time per round, in ms: the mean self time of
the program's ``serve.pump`` spans, each span's duration less the part of
it that its child spans (``parent_id`` the pump's ``id``) cover, from their
``t0_ns`` and ``dur_us``. Scheduler layer; moves itl_p95_ms."""


def self_us(parent, children):
    """``parent``'s duration less the union of its children's intervals,
    clipped to its own, in microseconds."""
    lo = parent["t0_ns"]
    hi = lo + parent["dur_us"] * 1e3
    covered, end = 0.0, lo
    for a, b in sorted((c["t0_ns"], c["t0_ns"] + c["dur_us"] * 1e3)
                       for c in children):
        a, b = max(a, end), min(b, hi)
        if b > a:
            covered += b - a
            end = b
    return parent["dur_us"] - covered / 1e3


def read(run):
    # a program whose spans carry no id or start has nothing to read
    spans = [s for s in run.spans if s.get("kind") == "span"
             and "id" in s and "t0_ns" in s]
    pumps = {s["id"]: s for s in spans if s["name"] == "serve.pump"}
    if not pumps:
        return None
    kids = {i: [] for i in pumps}
    for s in spans:
        if s.get("parent_id") in kids:
            kids[s["parent_id"]].append(s)
    total = sum(self_us(p, kids[i]) for i, p in pumps.items())
    return total / len(pumps) / 1e3
