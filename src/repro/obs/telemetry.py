"""Process-global telemetry: counters, gauges, timing histograms, spans.

Design contract (the part quantlint enforces, see ROADMAP "Observability"):

* **Host-side only.** Spans and metrics are read on the host, around the
  compiled-call boundaries — never inside a jitted/scanned body. Telemetry
  therefore adds zero traced ops: the recon-chunk and serve-decode jaxprs
  are byte-identical with telemetry on or off (pinned by tier-1's
  ``no_retrace(0, xla_budget=0)`` assertion), and QL103 keeps ``time.*``
  out of traced scopes while QL106 keeps ad-hoc clocks out of host code.

* **Negligible overhead when disabled.** ``span()`` returns a shared no-op
  singleton (no allocation, no clock read); counters/gauges are plain
  attribute bumps. The default state is disabled — enabling requires an
  explicit ``TELEMETRY.enable(...)`` (``launch/quantize --telemetry DIR``).

* **One span system, on the profiler's clock.** While enabled, every span
  also enters a ``jax.profiler.TraceAnnotation`` of its own name with its
  ``span_id`` as a stat, so an active profiler trace holds the program's
  spans on its host plane beside the device's operations, and a sink
  record matches its trace event by id. The annotation closes after the
  span's ``block_on`` sync. Records carry ``id``, ``parent_id`` and
  ``t0_ns`` (:func:`now_ns`), from which a layer's self time follows.

* **Device work is attributed explicitly.** A span measures wall time; jax
  dispatch is async, so a span around a compiled call measures *dispatch*
  unless you opt in: ``sp.block_on(out)`` (or ``span(..., sync=out)``)
  runs ``jax.block_until_ready`` at span exit, folding device completion
  into the span's duration instead of misattributing it to whichever span
  happens to block next.

Span taxonomy (dotted, coarse-to-fine): ``recon.block > recon.chunk``,
``alloc.teacher`` / ``alloc.probe``, ``serve.build``, ``serve.pump >
serve.admit > serve.prefill > serve.prefill.{dispatch,wait}``,
``serve.pump > serve.decode_step > serve.decode.{dispatch,wait}``,
``serve.pump > serve.decode.bookkeep``, ``serve.emit``, ``serve.detok``
(the detokenize thread) and ``obs.gc`` (Python's generation-1 and -2
collections; generation 0 is counted in ``obs.gc0``). XLA compiles are
attributed to the innermost open span by :mod:`repro.obs.compile_events`.
"""
from __future__ import annotations

import gc
import itertools
import math
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs.sink import SCHEMA_VERSION, RunManifest


def now() -> float:
    """Monotonic host timestamp (seconds) — the sanctioned absolute clock
    for lifecycle timing (queue wait, TTFT) outside this module."""
    return time.perf_counter()


def now_ns() -> int:
    """:func:`now` in integer nanoseconds: span starts and queue stamps."""
    return time.perf_counter_ns()


class Stopwatch:
    """The repo's one sanctioned ad-hoc clock (QL106 keeps bare
    ``time.perf_counter`` out of host code outside this module): started on
    construction, read via ``elapsed_s``/``elapsed_us``."""

    __slots__ = ("_t0",)

    def __init__(self):
        self._t0 = time.perf_counter()

    def restart(self) -> None:
        self._t0 = time.perf_counter()

    def elapsed_s(self) -> float:
        return time.perf_counter() - self._t0

    def elapsed_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6


class Counter:
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Exact-sample timing histogram (bounded reservoir; serving runs emit
    thousands of observations, not millions — keeping the samples makes
    the percentiles exact instead of bucket-quantized)."""

    __slots__ = ("name", "values", "max_samples", "count", "total", "max")

    def __init__(self, name: str, max_samples: int = 65536):
        self.name = name
        self.values: List[float] = []
        self.max_samples = max_samples
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v > self.max:
            self.max = v
        if len(self.values) < self.max_samples:
            self.values.append(v)

    def percentile(self, q: float) -> float:
        """Linear-interpolated percentile over the retained samples
        (matches numpy's default method)."""
        if not self.values:
            return 0.0
        vs = sorted(self.values)
        k = (len(vs) - 1) * q / 100.0
        f, c = math.floor(k), math.ceil(k)
        if f == c:
            return vs[int(k)]
        return vs[f] + (vs[c] - vs[f]) * (k - f)

    def summary(self) -> Dict[str, float]:
        return {"count": float(self.count),
                "mean": self.total / self.count if self.count else 0.0,
                "p50": self.percentile(50),
                "p95": self.percentile(95),
                "max": self.max}


class _NullSpan:
    """Shared no-op span handed out while telemetry is disabled: no clock
    read, no allocation beyond the call's own kwargs, every method inert."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def annotate(self, **attrs) -> None:
        pass

    def block_on(self, tree: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Span:
    __slots__ = ("name", "attrs", "parent", "depth", "dur_us", "id",
                 "parent_id", "t0_ns", "_tel", "_sync", "_ann")

    def __init__(self, tel: "Telemetry", name: str, sync: Any,
                 attrs: Dict[str, Any]):
        self._tel = tel
        self.name = name
        self.attrs = attrs
        self._sync = sync
        self.id = next(tel._ids)
        self.parent: Optional[str] = None
        self.parent_id: Optional[int] = None
        self.depth = 0
        self.dur_us = 0.0

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def block_on(self, tree: Any) -> None:
        """Register device values whose completion belongs to this span;
        ``block_until_ready`` runs on them at span exit."""
        self._sync = tree

    def __enter__(self) -> "Span":
        stack = self._tel._stack()
        if stack:
            self.parent, self.parent_id = stack[-1].name, stack[-1].id
        self.depth = len(stack)
        stack.append(self)
        self._ann = TraceAnnotation(self.name, span_id=self.id)
        self._ann.__enter__()
        self.t0_ns = now_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self._sync is not None:
            import jax
            jax.block_until_ready(self._sync)
        self.dur_us = (now_ns() - self.t0_ns) / 1e3
        self._ann.__exit__(None, None, None)
        stack = self._tel._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tel._record_span(self, synced=self._sync is not None)
        return False


class Telemetry:
    """Process-global metric registry + span stack (per-thread) + sink."""

    def __init__(self):
        self.enabled = False
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}
        self.manifest: Optional[RunManifest] = None
        self._sink = None
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)

    # ------------------------------------------------------------- metrics
    def counter(self, name: str) -> Counter:
        c = self.counters.get(name)
        if c is None:
            with self._lock:
                c = self.counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self.gauges.get(name)
        if g is None:
            with self._lock:
                g = self.gauges.setdefault(name, Gauge(name))
        return g

    def histogram(self, name: str) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            with self._lock:
                h = self.histograms.setdefault(name, Histogram(name))
        return h

    # --------------------------------------------------------------- spans
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, sync: Any = None, **attrs):
        """Nested wall-time span. Disabled mode returns a shared no-op
        context manager — callers never branch on ``enabled``."""
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name, sync, attrs)

    def current_span(self) -> Optional[str]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1].name if stack else None

    def _record_span(self, span: Span, synced: bool) -> None:
        self.histogram(f"span.{span.name}").observe(span.dur_us)
        rec = {"kind": "span", "name": span.name, "id": span.id,
               "parent_id": span.parent_id, "t0_ns": span.t0_ns,
               "dur_us": round(span.dur_us, 3), "depth": span.depth,
               "parent": span.parent, "synced": synced}
        if span.attrs:
            rec["attrs"] = span.attrs
        self.emit(rec)

    # ---------------------------------------------------------------- sink
    def emit(self, record: Dict[str, Any]) -> None:
        sink = self._sink  # read once: another thread may disable
        if sink is not None:
            record.setdefault("schema", SCHEMA_VERSION)
            record.setdefault("ts", time.time())
            sink.emit(record)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """``gc.callbacks`` hook: generation 0 is counted, older
        generations become ``obs.gc`` spans on the collecting thread."""
        gen = info["generation"]
        if gen == 0:
            if phase == "start" and self.enabled:
                self.counter("obs.gc0").inc()
            return
        if phase == "start":
            sp = self.span("obs.gc", generation=gen)
            if sp is not _NULL_SPAN:
                self._tls.gc_span = sp.__enter__()
        else:
            sp = getattr(self._tls, "gc_span", None)
            if sp is not None:
                self._tls.gc_span = None
                sp.annotate(collected=info["collected"])
                sp.__exit__(None, None, None)

    def _install_gc(self, on: bool) -> None:
        if on and self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        elif not on and self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def enable(self, sink=None, manifest: Optional[RunManifest] = None
               ) -> None:
        self.enabled = True
        self._sink = sink
        self.manifest = manifest
        if manifest is not None and sink is not None:
            sink.emit(manifest.record())
        from repro.obs import compile_events
        compile_events.install()
        self._install_gc(True)

    def disable(self) -> None:
        self.enabled = False
        self._install_gc(False)
        sink, self._sink = self._sink, None  # detach before closing
        if sink is not None:
            sink.close()

    @contextmanager
    def enabled_scope(self, sink=None,
                      manifest: Optional[RunManifest] = None):
        """Enable telemetry for a region, restoring the prior state after —
        used by tests and by the quantlint trace entries (which trace the
        production functions *under* live telemetry to prove instrumentation
        adds zero traced ops)."""
        prev = (self.enabled, self._sink, self.manifest)
        self.enabled = True
        self._sink = sink
        self.manifest = manifest
        if manifest is not None and sink is not None:
            sink.emit(manifest.record())
        self._install_gc(True)
        try:
            yield self
        finally:
            self.enabled, self._sink, self.manifest = prev
            self._install_gc(self.enabled)

    # ------------------------------------------------------------ snapshot
    def snapshot(self) -> Dict[str, Any]:
        return {
            "counters": {n: c.value for n, c in sorted(self.counters.items())},
            "gauges": {n: g.value for n, g in sorted(self.gauges.items())},
            "histograms": {n: h.summary()
                           for n, h in sorted(self.histograms.items())},
        }

    def reset(self) -> None:
        """Drop accumulated metrics (tests and bench isolation)."""
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()


TELEMETRY = Telemetry()


def span(name: str, sync: Any = None, **attrs):
    return TELEMETRY.span(name, sync=sync, **attrs)


def counter(name: str) -> Counter:
    return TELEMETRY.counter(name)


def gauge(name: str) -> Gauge:
    return TELEMETRY.gauge(name)


def histogram(name: str) -> Histogram:
    return TELEMETRY.histogram(name)
