"""``--profile`` wiring: a perfetto-loadable ``jax.profiler`` trace dir.

One process-wide trace (jax allows a single active profile): ``start(dir)``
/ ``stop()`` bracket the run. The hot loops need no markers of their own:
every enabled telemetry span writes a ``TraceAnnotation`` of its name
(:mod:`repro.obs.telemetry`), so ``recon.chunk``, ``serve.decode_step`` and
the rest land on the trace's host plane. Load the emitted directory in
perfetto (ui.perfetto.dev) or TensorBoard's profile plugin.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

_ACTIVE_DIR: Optional[str] = None


def active() -> Optional[str]:
    return _ACTIVE_DIR


def start(trace_dir: str) -> bool:
    """Begin the process-wide profiler trace into ``trace_dir``; returns
    False (with a warning) if the profiler backend refuses, so --profile
    degrades instead of killing the run."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is not None:
        return True
    import jax
    os.makedirs(trace_dir, exist_ok=True)
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception as e:  # pragma: no cover - backend-dependent
        print(f"profiler: could not start trace ({e}); continuing unprofiled")
        return False
    _ACTIVE_DIR = trace_dir
    return True


def stop() -> Optional[str]:
    """End the trace; returns the trace dir (None if none was active)."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is None:
        return None
    import jax
    d, _ACTIVE_DIR = _ACTIVE_DIR, None
    try:
        jax.profiler.stop_trace()
    except Exception as e:  # pragma: no cover - backend-dependent
        print(f"profiler: stop_trace failed ({e})")
    return d


@contextlib.contextmanager
def trace(trace_dir: str):
    """Bracket a region with start/stop (the --profile entry point)."""
    started = start(trace_dir)
    try:
        yield
    finally:
        if started:
            stop()

