"""Find the highest arrival rate a serving cell's configuration sustains.

    python3 bench/sweep.py --workload <cell> --rates 20,30,40 [--seconds 10]

One process, one set-up; each rate runs the cell's traffic mix at that rate
for ``--seconds`` and prints one JSON line: requests due, how many were
answered in full by the drain, the TTFT tail, and the backlog at the start
and end of the window. A rate is sustained when the backlog does not grow
over the window and every request due is answered. The cell's mix then
runs at about four fifths of the highest such rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import model as bmodel  # noqa: E402
from bench import serving  # noqa: E402
from bench.run import load_benchmark, open_device, workload  # noqa: E402
from bench.traffic import load_mix  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    wl = workload(bench, args.workload)
    open_device(wl["chips"])
    cfg, mix = bmodel.load_config(wl["config"]), load_mix(wl["traffic"])
    st = serving.setup(cfg, mix, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        win, e2e = serving.serve(st, dict(mix, rate_per_s=rate), args.seed,
                                 args.seconds)
        print(json.dumps({
            "rate_per_s": rate, "seed": args.seed, "due": e2e["attempted"],
            "failed": e2e["failed"], "backlog_start": win.backlog[0],
            "backlog_end": win.backlog[1],
            "ttft_p95_ms": e2e.get("ttft_p95_ms"),
            "itl_p95_ms": e2e.get("itl_p95_ms"),
            "output_tokens_per_s": e2e["output_tokens_per_s"]}), flush=True)
        eng = st.engine
        while eng.active:
            eng.step()
        eng.drain_finished()
    return 0


if __name__ == "__main__":
    sys.exit(main())
