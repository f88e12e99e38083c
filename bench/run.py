"""Run one cell of BENCHMARK.json once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children. It checks the device (no TPU, or fewer chips than
the cell asks for: exit 2, no result), turns on JAX's compile cache at the
checkout's fixed path (``.jax_cache``), builds the cell's model from the
seed, warms up the cell's shapes, runs the traffic's untimed ramp, measures
for ``--seconds``, then checks what the window served against the plain
reference. Set-up's phases go to standard error. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``;
``checks`` (each compared number beside its limit) comes last. With
``--trace 0`` the metrics are the cell's end-to-end ones, with ``--trace 1``
its per-layer ones, read from a profiler trace of the window and the
program's spans. Files go to ``.bench_out/`` in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
SRC = ROOT / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
OUT = ROOT / ".bench_out"
CACHE = ROOT / ".jax_cache"


class NoChip(Exception):
    """The run found no TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {len(devs)} {devs[0].platform} "
                     "device(s); the benchmark never falls back")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, jax found {len(devs)}")
    return {"platform": "tpu", "kind": devs[0].device_kind,
            "count": len(devs)}


def open_device(chips: int) -> dict:
    """The chips, with JAX's compile cache at the checkout's fixed path,
    whatever cache the machine names (the program's ``enable_compile_cache``
    takes the one the variable gives), and never trimmed: a machine's cap
    on its size evicts programs, which then compile again in set-up."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_compilation_cache_max_size", -1)
    device = check_device(chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return device


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class RunRecord:
    """What a per-layer metric reader gets to read."""

    def __init__(self, cfg, window, e2e, spans, trace, peaks):
        from bench.reference import layer_bits
        self.cfg, self.window, self.e2e = cfg, window, e2e
        self.arch = cfg["arch"]
        self.layer_bits = [int(b) for b in layer_bits(cfg)]
        self.spans, self.trace, self.peaks = spans, trace, peaks
        self.notes: dict = {}
        self.decode_contexts = decode_contexts(window)

    def span_mean_ms(self, name):
        d = [s["dur_us"] for s in self.spans if s.get("name") == name]
        return sum(d) / len(d) / 1e3 if d else None

    def kernel_time(self, program, kernel):
        """(device seconds, calls) of a Pallas kernel inside a program: the
        trace names each call ``%<kernel>.<n> = ... custom-call(...)``."""
        pat = re.compile(rf"^%{re.escape(kernel)}(\.\d+)? = .*custom-call")
        ops = (self.trace or {}).get("ops_in_program", {}).get(program, {})
        hit = [v for k, v in ops.items() if pat.match(k)]
        if not hit:
            return None
        return (sum(v["seconds"] for v in hit), sum(v["count"] for v in hit))

    def note(self, key, value):
        self.notes[key] = value


def decode_contexts(win) -> list:
    """Positions attended by each token the decode program produced in the
    traced part of the window: token k (k >= 1) of a request attends over
    prompt + k."""
    if win is None:
        return []
    w0, w1 = win.traced
    return [s.req.prompt_len + k for s in win.served.values()
            for k, t in enumerate(s.times) if k >= 1 and w0 <= t < w1]


class _Spans:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def close(self):
        pass


def run_cell(bench: dict, wl: dict, seed: int, seconds: float, trace: bool,
             *, device: dict, backend: str = "auto", cfg: dict = None,
             mix: dict = None, t_process: float = T_PROCESS,
             t_start: float = None, out_dir: pathlib.Path = OUT) -> dict:
    """One run of one serving cell; returns the result line's object.
    ``cfg`` / ``mix`` override the files the cell names (tests)."""
    import jax

    from bench import model as bmodel
    from bench import serving, trace_reduce
    from bench.metrics import reader
    from bench.traffic import load_mix
    from repro.obs.telemetry import TELEMETRY

    t_start = t_start or time.perf_counter()
    cfg = cfg or bmodel.load_config(wl["config"])
    mix = mix or load_mix(wl["traffic"])
    names = {m["name"] for m in bench["end_to_end"]
             if wl["name"] in m.get("workloads", [wl["name"]])}
    layer = [m for m in bench["per_layer"]
             if wl["name"] in m.get("workloads", [wl["name"]])]
    peaks = peaks_for(device["kind"]) if trace else None

    st = serving.setup(cfg, mix, seed, backend)
    setup_s = time.perf_counter() - t_process
    print(f"setup: {setup_s:.3f}s", file=sys.stderr, flush=True)
    phases = dict(devices_s=t_start - t_process, **st.phases)
    phases["other_s"] = setup_s - sum(
        phases[k] for k in ("devices_s", "grid_s", "weights_s", "export_s",
                            "engine_s", "warm_s"))
    print("setup phases: " + json.dumps(
        {k: round(v, 3) if isinstance(v, float) else v
         for k, v in phases.items()}), file=sys.stderr, flush=True)

    spans = _Spans()
    trace_dir = out_dir / f"trace-{wl['name']}-{seed}"
    window_note = []
    annotate = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only, no call graph
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    t_window = []

    def on_start(_win):
        t_window.append(time.perf_counter())
        if trace:
            TELEMETRY.enable(sink=spans)
            ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            ann.__enter__()
            window_note.append(ann)

    def on_trace_end(_win):
        if trace:
            window_note[0].__exit__(None, None, None)
            TELEMETRY.disable()
            jax.profiler.stop_trace()

    win, e2e = serving.serve(st, mix, seed, seconds, annotate=annotate,
                             on_window_start=on_start,
                             on_trace_end=on_trace_end,
                             trace_s=mix.get("trace_s"))
    dev = dict(device, memory_peak_bytes=memory_peak())

    result = {"correct": False, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": {}, "device": dev}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] +
             bench["per_layer"]}
    t_after = time.perf_counter()
    if trace:
        path = trace_reduce.find_xplane(str(trace_dir))
        red = trace_reduce.reduce(trace_reduce.load(path)) if path else {}
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec = RunRecord(cfg, win, e2e, spans.records, red, peaks)
        for m in layer:
            v = reader(m["name"])(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        dev["busy_s"] = red.get("busy_s", 0.0)
        dev["window_s"] = red.get("window_s", 0.0)
        result["breakdown"] = {"device_ops": [[k[:160], v] for k, v in
                                              trace_reduce.top_ops(red)],
                               "idle_gaps": red.get("idle_gaps", [])}
        if rec.notes:
            print(f"notes: {json.dumps(rec.notes)}", file=sys.stderr)
    else:
        e2e["setup_s"] = setup_s
        for n in sorted(names):
            if n in e2e:
                result["metrics"][n] = {"value": e2e[n], "unit": units[n]}

    t_check = time.perf_counter()
    verdict, = serving.judge(cfg, seed, win, st, e2e)
    backlog = win.backlog
    del win
    result["correct"] = verdict["correct"]
    result["checks"] = checks = verdict["checks"]
    print(f"backlog at window open, close: {backlog}; window+drain "
          f"{t_after - t_window[0]:.1f}s, trace reading "
          f"{t_check - t_after:.1f}s, reference check "
          f"{time.perf_counter() - t_check:.1f}s", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_benchmark()
        wl = workload(bench, args.workload)
        device = open_device(wl["chips"])
        t_start = time.perf_counter()
        OUT.mkdir(exist_ok=True)
        result = run_cell(bench, wl, args.seed, args.seconds,
                          bool(args.trace), device=device, t_start=t_start)
    except (NoChip, KeyError, FileNotFoundError, ImportError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
