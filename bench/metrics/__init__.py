"""Per-layer metric readers: ``<metric>.py`` holds ``read(run)``, found by
the metric's name in ``BENCHMARK.json``.

``read`` takes the run's record (``bench.run.RunRecord``: the cell's
configuration, the window's host-clock samples, the program's spans and the
reduced device trace) and returns a number, or None where the run holds
nothing to read; the harness then leaves the metric out of the line. A
share of a roofline or a peak is never returned as 0 for want of data.
"""
from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def reader(name: str, directory: pathlib.Path = HERE):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", directory / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
