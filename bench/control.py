"""Readings that set a cell's correctness limit: the program and the control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

One process. For each seed it runs the cell's set-up and its traffic at the
cell's own load for ``--seconds``, then judges one sample of served
requests twice, by the same decision and limits as ``bench/run.py``: (a)
the program, the widest gap by which a served token's float32 reference
logit lies below the reference's best, and (b) the control, the same gap
for the token that the float8 reference puts first at each of those
positions. Each prints ``correct`` beside the numbers compared. The
program's readings over a dozen seeds or more set the lower end of the
limit, the control's smallest reading the upper end; the control has to
come out not correct. The benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    wl = workload(bench, args.workload)
    open_device(wl["chips"])
    cfg, mix = bmodel.load_config(wl["config"]), load_mix(wl["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        st = serving.setup(cfg, mix, seed)
        win, e2e = serving.serve(st, mix, seed, args.seconds)
        program, control = serving.judge(cfg, seed, win, st, e2e,
                                         controls=(False, True))
        del win
        print(json.dumps({"seed": seed, "attempted": e2e["attempted"],
                          "program": program, "control": control}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
