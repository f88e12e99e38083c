"""The readers of the program's serving spans (``decode_host_ms``,
``sched_host_ms``, ``detok_lag_ms``) by hand on synthetic span records,
on spans the program records, and in a traced smoke run of each cell."""
import time

import pytest

from bench import run
from bench.metrics import reader
from bench.model import load_config

from test_bench_cell import BENCH, CELLS, cell

NEW = ("decode_host_ms", "sched_host_ms", "detok_lag_ms")


def span(name, id, t0_us, dur_us, parent_id=None, **attrs):
    rec = {"kind": "span", "name": name, "id": id, "parent_id": parent_id,
           "t0_ns": int(t0_us * 1e3), "dur_us": dur_us}
    if attrs:
        rec["attrs"] = attrs
    return rec


def record(spans):
    return run.RunRecord(load_config("smollm-135m-w4a8"), None, {}, spans,
                         {}, None)


def test_decode_host_reader_by_hand():
    spans = [span("serve.decode.dispatch", 1, 0, 300.0),
             span("serve.decode.wait", 2, 300, 20_000.0),
             span("serve.decode.bookkeep", 3, 20_300, 500.0),
             span("serve.decode.dispatch", 4, 30_000, 100.0),
             span("serve.decode.bookkeep", 5, 50_000, 100.0)]
    assert reader("decode_host_ms")(record(spans)) == pytest.approx(0.5)


def test_sched_host_reader_by_hand():
    """Self time: a pump's duration less the union of its children's
    intervals (overlapping children counted once, a grandchild not at all,
    a child's part outside the pump not at all)."""
    spans = [span("serve.pump", 1, 0, 10_000.0),
             span("serve.admit", 2, 1_000, 3_000.0, 1),
             span("serve.prefill", 3, 1_500, 2_000.0, 2),
             span("obs.gc", 4, 3_000, 2_000.0, 1),       # overlaps admit
             span("serve.decode_step", 5, 6_000, 3_000.0, 1),
             span("serve.pump", 6, 20_000, 2_000.0),
             span("serve.emit", 7, 21_500, 1_000.0, 6)]  # ends past the pump
    # pump 1: 10 ms less 1-5 ms and 6-9 ms = 3 ms; pump 6: 2 less 0.5 = 1.5
    assert reader("sched_host_ms")(record(spans)) == pytest.approx(2.25)


def test_detok_lag_reader_by_hand():
    spans = [span("serve.detok", 1, 0, 50.0, tokens=3, lag_us_sum=900.0),
             span("serve.detok", 2, 100, 50.0, tokens=1, lag_us_sum=100.0),
             span("serve.detok", 3, 200, 5.0, tokens=0, lag_us_sum=0.0)]
    assert reader("detok_lag_ms")(record(spans)) == pytest.approx(0.25)


@pytest.mark.parametrize("spans", [
    [],
    # the parent program's records: no id, no start, no detok spans
    [{"kind": "span", "name": "serve.pump", "dur_us": 10.0},
     {"kind": "span", "name": "serve.decode_step", "dur_us": 8.0}],
    [span("serve.detok", 1, 0, 5.0, tokens=0, lag_us_sum=0.0)],
], ids=["none", "without_ids", "no_stamped_token"])
def test_span_readers_return_nothing_without_their_spans(spans):
    for name in NEW:
        assert reader(name)(record(spans)) is None


def test_self_time_of_spans_the_program_records():
    """Self time read from the telemetry's own records: a parent that
    sleeps 20 ms itself around two 10 ms children reads ~20 ms."""
    from repro.obs import ListSink
    from repro.obs.telemetry import TELEMETRY
    sink = ListSink()
    with TELEMETRY.enabled_scope(sink=sink):
        with TELEMETRY.span("serve.pump"):
            time.sleep(0.01)
            for _ in range(2):
                with TELEMETRY.span("serve.decode_step"):
                    time.sleep(0.01)
            time.sleep(0.01)
    got = reader("sched_host_ms")(record(sink.records))
    pump, = [r for r in sink.records if r.get("name") == "serve.pump"]
    kids = [r for r in sink.records if r.get("name") == "serve.decode_step"]
    assert got == pytest.approx(
        (pump["dur_us"] - sum(k["dur_us"] for k in kids)) / 1e3)
    assert 19.0 <= got < pump["dur_us"] / 1e3 - 19.0


@pytest.mark.parametrize("name", CELLS)
def test_traced_smoke_run_reads_the_span_metrics(name, tmp_path):
    """A traced run of the cell at a smoke size reads each new span metric
    (the CPU trace has no device plane, so the device metrics stay out).
    Whether the run is correct is ``test_bench_cell``'s to check."""
    res = cell(name, 2**31 + 17, tmp_path, trace=True)
    want = {m["name"] for m in BENCH["per_layer"]
            if m["source"] == "program_span"
            and name in m.get("workloads", [name])}
    assert set(NEW) <= want <= set(res["metrics"])
    assert all(res["metrics"][n]["value"] >= 0 for n in NEW)
    assert res["attempted"] > 0 and res["failed"] == 0
