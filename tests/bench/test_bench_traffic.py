"""Traffic generators: seeded, the same work for every seed, and found by
name, so a mix can be added as a data file alone."""
import json
import statistics

import numpy as np
import pytest

from bench import sizes
from bench.traffic import generator, load_mix

CHAT = load_mix("chat")
LONGGEN = load_mix("longgen")


def _sched(mix, seed, seconds=10.0):
    return generator(mix["kind"]).make(mix, seed, seconds, vocab=1000)


@pytest.mark.parametrize("mix", [CHAT, LONGGEN], ids=["chat", "longgen"])
def test_same_seed_same_schedule(mix):
    a, b = _sched(mix, 2**31 + 7), _sched(mix, 2**31 + 7)
    assert [(r.rid, r.due_s, r.prompt_len, r.max_new) for r in a.requests] == \
        [(r.rid, r.due_s, r.prompt_len, r.max_new) for r in b.requests]


@pytest.mark.parametrize("mix", [CHAT, LONGGEN], ids=["chat", "longgen"])
def test_seeds_permute_one_set_of_sizes(mix):
    a, b = _sched(mix, 1), _sched(mix, 2)
    assert [r.prompt_len for r in a.requests] != \
        [r.prompt_len for r in b.requests]
    assert sorted(r.prompt_len for r in a.requests) == \
        sorted(r.prompt_len for r in b.requests)
    assert sorted(r.max_new for r in a.requests) == \
        sorted(r.max_new for r in b.requests)


def test_open_loop_rate_and_lengths():
    s = _sched(CHAT, 5, seconds=20.0)
    due = np.array([r.due_s for r in s.requests])
    horizon = CHAT["ramp_s"] + 20.0 + CHAT["tail_s"]
    assert len(due) == sizes.count(CHAT["rate_per_s"], horizon)
    assert due[-1] == pytest.approx(horizon, rel=0.1)
    assert np.all(np.diff(due) > 0)
    p = [r.prompt_len for r in s.requests]
    o = [r.max_new for r in s.requests]
    assert min(p) >= CHAT["prompt"]["min"] and max(p) <= CHAT["prompt"]["max"]
    assert min(o) >= CHAT["output"]["min"] and max(o) <= CHAT["output"]["max"]
    assert statistics.median(p) == pytest.approx(CHAT["prompt"]["median"],
                                                 rel=0.02)
    assert statistics.median(o) == pytest.approx(CHAT["output"]["median"],
                                                 rel=0.02)


def test_uniform_lengths_cover_the_range():
    spec = {"dist": "uniform", "min": 512, "max": 1536}
    x = sizes.lengths(spec, 1025, np.random.default_rng(0))
    assert x.min() == 512 and x.max() == 1536
    assert np.mean(x) == pytest.approx(1024, rel=0.01)


def test_closed_loop_releases_a_client_request_on_completion():
    s = _sched(LONGGEN, 3)
    first = s.initial()
    assert len(first) == LONGGEN["clients"]
    assert [r.client for r in first] == list(range(LONGGEN["clients"]))
    assert first[-1].due_s < LONGGEN["ramp_s"]
    nxt = s.on_complete(first[5], 12.5)
    assert nxt.client == 5 and nxt.due_s == 12.5
    for _ in range(LONGGEN["per_client"] - 2):
        assert s.on_complete(first[5], 13.0) is not None
    assert s.on_complete(first[5], 14.0) is None


def test_token_ids_are_seeded():
    a = sizes.token_ids(2**31 + 3, 17, 64, 50304)
    assert np.array_equal(a, sizes.token_ids(2**31 + 3, 17, 64, 50304))
    assert not np.array_equal(a, sizes.token_ids(2**31 + 4, 17, 64, 50304))
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 50304


def test_a_mix_added_as_a_file_alone_loads_by_name(tmp_path):
    mix = dict(CHAT, rate_per_s=3.0, ramp_s=1.0, tail_s=1.0,
               prompt={"dist": "uniform", "min": 8, "max": 8})
    (tmp_path / "throwaway.json").write_text(json.dumps(mix))
    got = load_mix("throwaway", tmp_path)
    s = generator(got["kind"]).make(got, 9, 4.0, vocab=100)
    assert {r.prompt_len for r in s.requests} == {8}
    assert len(s.requests) == sizes.count(3.0, 6.0)
