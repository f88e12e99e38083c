"""From a profiler trace (``.xplane.pb``) to device numbers.

What a v5e trace holds, as read by hand from a recorded one (PERF.md,
"Where the time goes"): each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Modules`` has one event per program execution (``jit_decode(...)``,
``jit_prefill_insert(...)``) and whose line ``XLA Ops`` has one event per
operation, Pallas kernels among them; the host is the plane ``/host:CPU``,
one line per thread, holding the ``TraceAnnotation`` spans of the harness
and the program. Both are on one clock, in nanoseconds.

``reduce`` clips everything to the window (the host span ``bench.window``
where there is one) and returns busy and idle time, device time per
operation and per program, operations per program, and the longest idle
gaps with the innermost host span open while the device waited.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _events(line) -> List[Tuple[int, int, str, object]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, e)
            for e in line.events]


def program_name(name: str) -> str:
    """``jit_decode(123)`` and ``jit_decode`` name one program."""
    return name.split("(")[0]


def _union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def leaves(ops):
    """The operations that enclose no other: a loop's event (``%while``)
    spans its body's operations, which carry the time."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][0] >= e[1]]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def host_spans(pd) -> List[Tuple[int, int, str, int]]:
    """Every host event as (start, end, name, depth on its thread)."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            stack: List[int] = []
            for a, b, name, _ in sorted(_events(line), key=lambda e: (e[0], -e[1])):
                while stack and stack[-1] <= a:
                    stack.pop()
                out.append((a, b, name, len(stack)))
                stack.append(b)
    return out


def window_of(pd) -> Optional[Tuple[int, int]]:
    for a, b, name, _ in host_spans(pd):
        if name == WINDOW_SPAN:
            return a, b
    return None


def reduce(pd, window: Optional[Tuple[int, int]] = None,
           host_prefix: str = "bench.", top: int = 10) -> Dict:
    """Device numbers of one trace, averaged over its TPU planes.

    ``window``: (start, end) ns; default the ``bench.window`` host span, or
    the extent of the device's operations."""
    window = window or window_of(pd)
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        return {}
    per_dev = []
    for plane in devices:
        lines = {l.name: _events(l) for l in plane.lines}
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULES_LINE, [])
        if not ops:
            continue
        lo, hi = window or (min(e[0] for e in ops), max(e[1] for e in ops))
        ops = [e for e in ops if e[1] > lo and e[0] < hi]
        mods = [e for e in mods if e[1] > lo and e[0] < hi]
        per_dev.append((lo, hi, ops, mods))
    if not per_dev:
        return {}
    lo, hi = per_dev[0][0], per_dev[0][1]
    busy = 0
    op_time: Dict[str, float] = defaultdict(float)
    prog: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    in_prog: Dict[str, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(lambda: [0.0, 0]))
    busy_iv = []
    for dlo, dhi, ops, mods in per_dev:
        iv = [_clip(a, b, dlo, dhi) for a, b, _, _ in ops]
        u = _union(iv)
        busy += sum(b - a for a, b in u)
        if not busy_iv:
            busy_iv = u
        for a, b, name, _ in leaves(ops):
            a, b = _clip(a, b, dlo, dhi)
            op_time[name] += (b - a) / 1e9
        mods = sorted(mods)
        starts = [m[0] for m in mods]
        for a, b, name, _ in mods:
            p = prog[program_name(name)]
            p[0] += (min(b, dhi) - max(a, dlo)) / 1e9
            p[1] += 1
        for a, b, name, _ in ops:
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and mods[i][1] >= b:
                rec = in_prog[program_name(mods[i][2])][name]
                rec[0] += (b - a) / 1e9
                rec[1] += 1
    n = len(per_dev)
    window_s = (hi - lo) / 1e9
    gaps = idle_gaps(busy_iv, lo, hi, host_spans(pd), host_prefix, top)
    return {
        "window_s": window_s,
        "busy_s": busy / 1e9 / n,
        "ops": {k: v / n for k, v in op_time.items()},  # leaves only
        "programs": {k: {"seconds": v[0] / n, "count": v[1] / n}
                     for k, v in prog.items()},
        "ops_in_program": {p: {k: {"seconds": v[0] / n, "count": v[1] / n}
                               for k, v in d.items()}
                           for p, d in in_prog.items()},
        "idle_gaps": gaps,
    }


def idle_gaps(busy, lo, hi, spans, prefix: str, top: int):
    """The ``top`` longest stretches with no operation on the device, each
    named by the innermost host span that covered its middle."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [s for s in spans if s[2].startswith(prefix) or
             s[2].startswith("serve.")]
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) // 2
        cover = [s for s in named if s[0] <= mid < s[1]]
        name = max(cover, key=lambda s: s[3])[2] if cover else "no host span"
        out.append([name, (b - a) / 1e9])
    return out


def top_ops(red: Dict, top: int = 10):
    return sorted(([k, v] for k, v in red.get("ops", {}).items()),
                  key=lambda kv: -kv[1])[:top]
