"""Telemetry tier-1 suite (repro.obs): span nesting/attribution, the
disabled-mode no-op fast path, exact-sample histogram percentiles, manifest
round-trips (bench JSON rows + PTQ checkpoint meta), JSONL sink validation,
compile attribution, the zero-compile contract for instrumented warm
paths (telemetry is host-side only, so a warmed jit under live spans must
never trace or compile again), spans in the profiler trace on its clock,
collections as spans, and span ids across threads."""
import gc
import glob
import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.run import rows_to_records, stamp_records
from jax.profiler import ProfileData

from repro.obs import compile_events
from repro.obs.serve_metrics import ServeMetrics, percentiles_from_events
from repro.obs.sink import (SCHEMA_VERSION, JsonlSink, ListSink, RunManifest,
                            check_bench, current_manifest, validate_events)
from repro.obs.telemetry import (_NULL_SPAN, TELEMETRY, Histogram, Stopwatch,
                                 now)


# ----------------------------------------------------------------- disabled
def test_disabled_span_is_shared_noop():
    """Disabled telemetry hands out one shared inert span — no allocation,
    no clock read, nothing recorded — so instrumented hot loops never
    branch on ``enabled``."""
    assert not TELEMETRY.enabled
    sp = TELEMETRY.span("obs.test.disabled", idx=3)
    assert sp is TELEMETRY.span("obs.test.other") is _NULL_SPAN
    with sp as s:
        s.annotate(x=1)
        s.block_on(jnp.zeros(2))
    assert "span.obs.test.disabled" not in TELEMETRY.histograms
    assert TELEMETRY.current_span() is None


# -------------------------------------------------------------------- spans
def test_span_nesting_and_attribution():
    """Nested spans record parent/depth, merged annotations, and land in
    the sink schema-stamped; the enclosing scope restores disabled state."""
    sink = ListSink()
    with TELEMETRY.enabled_scope(sink=sink):
        with TELEMETRY.span("obs.test.outer", stage="a") as so:
            so.annotate(blocks=2)
            assert TELEMETRY.current_span() == "obs.test.outer"
            with TELEMETRY.span("obs.test.inner"):
                assert TELEMETRY.current_span() == "obs.test.inner"
    assert not TELEMETRY.enabled
    inner, outer = [r for r in sink.records if r["kind"] == "span"]
    assert inner["name"] == "obs.test.inner"
    assert inner["parent"] == "obs.test.outer" and inner["depth"] == 1
    assert outer["name"] == "obs.test.outer"
    assert outer["parent"] is None and outer["depth"] == 0
    assert outer["attrs"] == {"stage": "a", "blocks": 2}
    assert outer["dur_us"] >= inner["dur_us"] >= 0.0
    assert all(r["schema"] == SCHEMA_VERSION and "ts" in r
               for r in (inner, outer))
    # span durations also feed the process-global timing histograms
    assert TELEMETRY.histograms["span.obs.test.outer"].count >= 1


def test_span_sync_folds_device_time():
    """``block_on`` registers device values whose completion belongs to the
    span (block_until_ready at exit), recorded as ``synced``."""
    sink = ListSink()
    x = jnp.arange(4.0)
    with TELEMETRY.enabled_scope(sink=sink):
        with TELEMETRY.span("obs.test.sync") as sp:
            sp.block_on(x * 2.0)
    (rec,) = [r for r in sink.records if r["kind"] == "span"]
    assert rec["synced"] is True and rec["dur_us"] > 0.0


def test_stopwatch_and_now_monotonic():
    sw = Stopwatch()
    t0 = now()
    assert sw.elapsed_s() >= 0.0 and now() >= t0
    sw.restart()
    assert sw.elapsed_us() >= 0.0


# --------------------------------------------------------------- histograms
def test_histogram_percentiles_linear_interp():
    """Percentiles match numpy's default linear interpolation over the
    retained samples; the empty histogram summarizes to zeros."""
    h = Histogram("obs.test.h")
    for v in range(1, 101):
        h.observe(float(v))
    data = np.arange(1, 101, dtype=np.float64)
    assert h.percentile(50) == pytest.approx(np.percentile(data, 50))
    assert h.percentile(95) == pytest.approx(np.percentile(data, 95))
    s = h.summary()
    assert s["count"] == 100 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert Histogram("obs.test.empty").summary() == {
        "count": 0.0, "mean": 0.0, "p50": 0.0, "p95": 0.0, "max": 0.0}


def test_snapshot_collects_all_registries():
    TELEMETRY.counter("obs.test.ctr").inc(3)
    TELEMETRY.gauge("obs.test.g").set(2.5)
    TELEMETRY.histogram("obs.test.snap").observe(10.0)
    snap = TELEMETRY.snapshot()
    assert snap["counters"]["obs.test.ctr"] == 3
    assert snap["gauges"]["obs.test.g"] == 2.5
    assert snap["histograms"]["obs.test.snap"]["count"] == 1


# ----------------------------------------------------------------- manifest
def test_manifest_roundtrip_and_brief():
    m = current_manifest()
    assert m is current_manifest()  # process-cached
    assert m.schema_version == SCHEMA_VERSION and m.git_sha
    # unknown fields from a newer writer are dropped on the way back in
    assert RunManifest.from_dict(dict(m.to_dict(), extra="ignored")) == m
    assert set(m.brief()) == {"git_sha", "schema_version"}


def test_bench_records_manifest_stamped(tmp_path):
    """``benchmarks.run --json`` rows round-trip through the CSV parser and
    come out manifest-stamped; ``check_bench`` flags a missing stamp."""
    rows = ["recon/smoke,12.5,steps_per_s=80.0;compile_count=2",
            "serve/requests/int8-kv,9000.0,requests=10;slots=4"]
    records = stamp_records(rows_to_records(rows))
    assert records[0]["steps_per_s"] == 80.0
    for rec in records:
        assert rec["manifest"]["git_sha"] == current_manifest().git_sha
        assert rec["manifest"]["schema_version"] == SCHEMA_VERSION
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(records))
    assert check_bench(str(p)) == []
    records[1].pop("manifest")
    p.write_text(json.dumps(records))
    assert any("no manifest stamp" in e for e in check_bench(str(p)))


def test_ptq_checkpoint_meta_carries_manifest(tmp_path):
    """PTQ checkpoint meta records which code/runtime produced the partial
    state — readable back as a RunManifest."""
    from repro.checkpoint.checkpoint import PTQCheckpointer, load_pytree
    ck = PTQCheckpointer(str(tmp_path))
    ck.save(next_block=1, finalized=[{"w": jnp.ones((2, 2))}], astates={},
            reports=[], x_fp=jnp.zeros((2,)), x_q=jnp.zeros((2,)))
    _, meta = load_pytree(ck.path)
    m = RunManifest.from_dict(meta["manifest"])
    assert m.git_sha == current_manifest().git_sha
    assert m.schema_version == SCHEMA_VERSION


# --------------------------------------------------------------- JSONL sink
def test_jsonl_sink_schema_valid(tmp_path):
    """A real run's event file opens with the manifest, every record is
    kind-tagged and schema-stamped, and the validator refuses a record
    written by a newer schema."""
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    with TELEMETRY.enabled_scope(sink=sink, manifest=current_manifest()):
        with TELEMETRY.span("obs.test.run"):
            TELEMETRY.emit({"kind": "allocation", "digest": "abc"})
    sink.close()
    assert validate_events(path) == []
    with open(path) as fh:
        kinds = [json.loads(line)["kind"] for line in fh]
    assert kinds[0] == "manifest"
    assert "span" in kinds and "allocation" in kinds
    with open(path, "a") as fh:
        fh.write(json.dumps({"kind": "x", "schema": SCHEMA_VERSION + 1})
                 + "\n")
    assert any("newer than this reader" in e for e in validate_events(path))
    assert validate_events(str(tmp_path / "missing.jsonl"))  # unreadable


# ------------------------------------------------------------ serve metrics
def test_serve_metrics_request_lifecycle_event():
    """submit -> admitted closes the queue-wait and TTFT windows and emits
    one ``kind="request"`` sink event; direct engine admits (no submit
    stamp) have no queue to time; the bench folds events back into
    percentiles with ``percentiles_from_events``."""
    sink = ListSink()
    m = ServeMetrics()
    with TELEMETRY.enabled_scope(sink=sink):
        m.on_submit(7)
        t = now()
        m.on_admitted(7, bucket=8, admit_start=t, first_token_t=t + 2e-3)
    (req,) = [r for r in sink.records if r["kind"] == "request"]
    assert req["rid"] == 7 and req["bucket"] == 8
    assert req["ttft_us"] >= req["queue_wait_us"] >= 0.0
    s = m.request_summary()
    assert s["admitted"] == 1 and s["ttft_us"]["count"] == 1
    m.on_admitted(8, bucket=8, admit_start=t, first_token_t=t)
    assert m.ttft_us.count == 1  # direct admit: untimed, not mis-timed
    folded = percentiles_from_events(sink.records, "request", "ttft_us")
    assert folded["count"] == 1 and folded["p50"] == req["ttft_us"]
    assert percentiles_from_events([], "request", "ttft_us") is None


# --------------------------------------------------- compiles & zero-retrace
def test_compile_attribution_and_zero_compile_warm_path(no_retrace):
    """Backend compiles are attributed to the innermost open span; once a
    function is warm, running it *under live telemetry* (spans + sink +
    block_on) adds zero traces and zero backend compiles — the host-side
    only contract that keeps recon-chunk and serve-decode jaxprs identical
    with telemetry on or off."""
    installed = compile_events.install()
    assert compile_events.install() == installed  # idempotent

    @jax.jit
    def f(x):
        return x * 2.0 + 1.0

    x = jnp.arange(8, dtype=jnp.float32)
    sink = ListSink()
    with TELEMETRY.enabled_scope(sink=sink):
        with TELEMETRY.span("obs.test.compile"):
            np.asarray(f(x))  # cold call: compiles inside the span
    if installed:
        assert compile_events.compiles_by_span().get(
            "obs.test.compile", 0) >= 1
        assert any(r["kind"] == "compile"
                   and r["span"] == "obs.test.compile"
                   for r in sink.records)
    warm_sink = ListSink()
    with TELEMETRY.enabled_scope(sink=warm_sink):
        with no_retrace(0, xla_budget=0):
            for i in range(3):
                with TELEMETRY.span("obs.test.warm", i=i) as sp:
                    sp.block_on(f(x))
    assert sum(1 for r in warm_sink.records if r["kind"] == "span") == 3


# ------------------------------------------------- spans in the profiler trace
def _host_events(trace_dir, prefix):
    """Host events of a written trace whose name starts with ``prefix``:
    (name, start ns, duration ns, stats dict)."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)))
    return out


def test_span_lands_in_profiler_trace_on_one_clock(tmp_path):
    """An enabled span under an active profiler trace is a host event of
    the same name with its ``span_id`` as a stat and the sink's duration;
    sink starts and trace starts differ by one constant offset."""
    sink = ListSink()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TELEMETRY.enabled_scope(sink=sink):
            for i in range(3):
                with TELEMETRY.span("obs.test.traced", i=i):
                    time.sleep(0.004)
                    with TELEMETRY.span("obs.test.traced_child"):
                        time.sleep(0.002)
                time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    recs = {r["id"]: r for r in sink.records if r["kind"] == "span"}
    events = _host_events(str(tmp_path), "obs.test.traced")
    assert len(events) == len(recs) == 6
    offsets = []
    for name, start, dur, stats in events:
        rec = recs[stats["span_id"]]
        assert rec["name"] == name
        assert abs(dur / 1e3 - rec["dur_us"]) <= max(0.05 * rec["dur_us"],
                                                     50.0)
        offsets.append(rec["t0_ns"] - start)
    assert max(offsets) - min(offsets) <= 50_000
    child = [r for r in recs.values() if r["name"] == "obs.test.traced_child"]
    assert all(recs[c["parent_id"]]["name"] == "obs.test.traced"
               for c in child)


def test_disabled_span_writes_no_host_event(tmp_path):
    """Disabled telemetry under an active trace: no host event, no record,
    no timing histogram."""
    assert not TELEMETRY.enabled
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TELEMETRY.span("obs.test.off_traced") as sp:
            sp.annotate(x=1)
    finally:
        jax.profiler.stop_trace()
    assert sp is _NULL_SPAN
    assert _host_events(str(tmp_path), "obs.test.off_traced") == []
    assert "span.obs.test.off_traced" not in TELEMETRY.histograms


def test_gc_collections_are_spans_while_enabled():
    """A generation-2 collection inside an open span is an ``obs.gc`` span
    child of it; the hook is gone once telemetry is disabled again."""
    sink = ListSink()
    with TELEMETRY.enabled_scope(sink=sink):
        with TELEMETRY.span("obs.test.gc_parent"):
            gc.collect(2)
    assert TELEMETRY._on_gc not in gc.callbacks
    spans = [r for r in sink.records if r["kind"] == "span"]
    parent, = [r for r in spans if r["name"] == "obs.test.gc_parent"]
    gcs = [r for r in spans if r["name"] == "obs.gc"
           and r["attrs"]["generation"] == 2]
    assert gcs and all(r["parent_id"] == parent["id"] for r in gcs)
    assert all(r["attrs"]["collected"] >= 0 for r in gcs)
    gc.collect(2)
    n = len(sink.records)
    gc.collect(2)
    assert len(sink.records) == n


def test_validator_requires_span_ids_from_schema_2(tmp_path):
    """Span records of schema 2 carry id, parent id and start; one that
    lacks them is refused, one of schema 1 is read as it was written."""
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    with TELEMETRY.enabled_scope(sink=sink, manifest=current_manifest()):
        with TELEMETRY.span("obs.test.ids"):
            pass
    sink.close()
    assert validate_events(path) == []
    with open(path, "a") as fh:
        fh.write(json.dumps({"kind": "span", "schema": 1, "name": "old",
                             "dur_us": 1.0}) + "\n")
    assert validate_events(path) == []
    with open(path, "a") as fh:
        fh.write(json.dumps({"kind": "span", "schema": 2, "name": "new",
                             "dur_us": 1.0}) + "\n")
    errs = validate_events(path)
    assert {e.split(": ")[-1] for e in errs} == {
        "span record has no valid 'id'", "span record has no valid 't0_ns'",
        "span record has no valid 'parent_id'"}


def test_spans_from_many_threads_keep_ids_and_parents():
    """Threads that open spans at once, with the interpreter switching
    threads as often as it can, still give every span its own id and each
    child the parent from its own thread."""
    sink = ListSink()
    n_threads, n_spans = 16, 200

    def work(t):
        for i in range(n_spans):
            with TELEMETRY.span("obs.test.stress_outer", thread=t):
                with TELEMETRY.span("obs.test.stress_inner", thread=t):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with TELEMETRY.enabled_scope(sink=sink):
            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    spans = {r["id"]: r for r in sink.records if r["kind"] == "span"
             and r["name"].startswith("obs.test.stress")}
    assert len(spans) == 2 * n_threads * n_spans
    for r in spans.values():
        if r["name"] == "obs.test.stress_inner":
            parent = spans[r["parent_id"]]
            assert parent["name"] == "obs.test.stress_outer"
            assert parent["attrs"]["thread"] == r["attrs"]["thread"]
        else:
            assert r["parent_id"] is None
