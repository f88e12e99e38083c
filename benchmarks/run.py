"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only tableN] [--json out.json]

``--json`` additionally writes the rows as a list of records (the ``derived``
key=value pairs parsed into typed fields) — CI jobs upload these to build
perf trajectories.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def rows_to_records(rows):
    """``name,us_per_call,derived`` CSV lines -> JSON-able dicts."""
    records = []
    for line in rows:
        name, us, derived = line.split(",", 2)
        rec = {"name": name, "us_per_call": float(us)}
        for pair in filter(None, derived.split(";")):
            k, eq, v = pair.partition("=")
            if not eq:
                continue
            try:
                rec[k] = float(v)
            except ValueError:
                rec[k] = v
        records.append(rec)
    return records


def stamp_records(records):
    """Stamp every bench record with the run manifest (git sha + schema
    version) so a perf trajectory is attributable to the commit and JSONL
    schema that produced it. Validated by ``repro.obs.sink --check-bench``."""
    from repro.obs.sink import current_manifest
    brief = current_manifest().brief()
    for rec in records:
        rec["manifest"] = dict(brief)
    return records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on table function names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON records to PATH")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.tables import ALL_TABLES

    rows = ["name,us_per_call,derived"]
    failures = 0
    for fn in ALL_TABLES:
        if args.only and args.only not in fn.__name__:
            continue
        t0 = time.perf_counter()
        try:
            fn(rows)
            print(f"# {fn.__name__} done in {time.perf_counter()-t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {fn.__name__} FAILED:", file=sys.stderr)
            traceback.print_exc()
    print("\n".join(rows), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(stamp_records(rows_to_records(rows[1:])), f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
