"""Each cell end to end on the CPU at a smoke size, through the harness's
own functions with interpreted Pallas kernels (the chip check is skipped,
everything after it runs): the check passes on the sound path, fails when
the timed path is broken underneath, and the float8 control reads far
above the program."""
import copy
import pathlib

import numpy as np
import pytest

from bench import run, serving
from bench.model import load_config
from bench.traffic import load_mix

BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def smoke(name):
    """The cell at a smoke size: every width cut, the structure kept."""
    wl = run.workload(BENCH, name)
    cfg = copy.deepcopy(load_config(wl["config"]))
    mha = cfg["arch"]["n_kv_heads"] == cfg["arch"]["n_heads"]
    cfg["arch"].update(n_layers=2, d_model=64, n_heads=4, head_dim=16,
                       n_kv_heads=4 if mha else 2, d_ff=128, vocab=128)
    cfg["engine"].update(slots=4, max_len=64, min_bucket=16, prefill_group=2)
    cfg["calibration"].update(sequences=2, tokens=32)
    cfg["check"].update(requests=3, min_tokens=10, max_logit_gap=0.01)
    mix = copy.deepcopy(load_mix(wl["traffic"]))
    mix["prompt"].update(median=12, min=4, max=24)
    mix["ramp_s"] = 0.5
    if mix["kind"] == "open_loop":
        mix.update(rate_per_s=5.0, tail_s=2.0)
        mix["output"].update(median=8, min=2, max=16)
    else:
        mix.update(clients=4, per_client=400)
        mix["output"].update(min=8, max=24)
    return wl, cfg, mix


def cell(name, seed, tmp_path, trace=False, break_engine=None):
    wl, cfg, mix = smoke(name)
    if break_engine is not None:
        setup = serving.setup

        def broken(*a, **k):
            st = setup(*a, **k)
            break_engine(st.engine, cfg)
            return st
        serving.setup = broken
    try:
        return run.run_cell(BENCH, wl, seed, 2.0, trace, device=CPU,
                            backend="pallas", cfg=cfg, mix=mix,
                            out_dir=pathlib.Path(tmp_path))
    finally:
        if break_engine is not None:
            serving.setup = setup


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name, tmp_path):
    res = cell(name, 2**31 + 11, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "checks"


def _alter_tokens(engine, cfg):
    """A token altered where it is produced: the decode program's emitted
    ids come back shifted by one."""
    decode = engine._decode_exec
    vocab = cfg["arch"]["vocab"]

    def wrong(*args):
        cache, meta, emitted = decode(*args)
        return cache, meta, np.where(np.asarray(emitted) >= 0,
                                     (np.asarray(emitted) + 1) % vocab, -1)
    engine._decode_exec = wrong


def _drop_half(engine, cfg):
    """Half of the batch left out: the odd slots' tokens never reach the
    client."""
    decode = engine._decode_exec

    def half(*args):
        cache, meta, emitted = decode(*args)
        e = np.asarray(emitted).copy()
        e[1::2] = -1
        return cache, meta, e
    engine._decode_exec = half
    step = engine.step

    def step_half():
        return [(r, t) for r, t in step() if t >= 0]
    engine.step = step_half


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_alter_tokens, _drop_half],
                         ids=["altered_token", "half_batch"])
def test_broken_timed_path_is_not_correct(name, fault, tmp_path):
    res = cell(name, 2**31 + 12, tmp_path, break_engine=fault)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_float8_control_fails_the_limit(name, tmp_path):
    """The control goes through the run's own decision: on one sample of
    served requests the program is correct and the float8 control is not."""
    wl, cfg, mix = smoke(name)
    st = serving.setup(cfg, mix, 5, backend="pallas")
    win, e2e = serving.serve(st, mix, 5, 2.0)
    program, control = serving.judge(cfg, 5, win, st, e2e,
                                     controls=(False, True))
    assert program["correct"], program["checks"]
    assert control["correct"] is False, control["checks"]
    limit = cfg["check"]["max_logit_gap"]
    assert (program["checks"]["logit_gap"]["value"] <= limit
            < control["checks"]["logit_gap"]["value"])
