"""The traffic generator: seeded, the same work for every seed, within
its parameters."""

import numpy as np
import pytest

from bench.harness import BENCH, load_json, load_module

GEN = load_module(BENCH / "traffic" / "gen_requests.py")
MIXES = ["ragged_open"]


def mix(name):
    return load_json(BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = GEN.generate(mix(name), seed=2**31 + 5, seconds=30, vocab=49155)
    b = GEN.generate(mix(name), seed=2**31 + 5, seconds=30, vocab=49155)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    a = GEN.generate(m, seed=1, seconds=30, vocab=49155)
    b = GEN.generate(m, seed=2, seconds=30, vocab=49155)
    sizes = lambda t: sorted(zip(map(len, t["prompts"]), t["outputs"]))
    assert sizes(a) == sizes(b)
    assert a["prompts"] != b["prompts"]
    gaps = lambda t: sorted(np.round(np.diff(t["due_s"] + [30.0]), 9))
    assert gaps(a) == gaps(b)


@pytest.mark.parametrize("name", MIXES)
def test_sizes_within_the_mix(name):
    m = mix(name)
    t = GEN.generate(m, seed=7, seconds=30, vocab=131072)
    plens = [len(p) for p in t["prompts"]]
    assert min(plens) >= m["prompt"]["min"]
    assert max(plens) <= m["prompt"]["max"]
    assert min(t["outputs"]) >= m["output"]["min"]
    assert max(t["outputs"]) <= m["output"]["max"]
    assert all(0 <= x < 131072 for p in t["prompts"] for x in p)
    assert len(set(plens)) == m["prompt"]["levels"]


def test_open_loop_arrivals():
    m = mix("ragged_open")
    t = GEN.generate(m, seed=11, seconds=30, vocab=49155)
    n = round(m["arrivals"]["rate_rps"] * 30)
    assert len(t["due_s"]) == n
    assert t["due_s"][0] == 0.0 and t["due_s"] == sorted(t["due_s"])
    assert t["due_s"][-1] < 30.0
    gaps = np.diff(t["due_s"])
    cv = gaps.std() / gaps.mean()
    assert 1.1 < cv < 1.9                 # bursty: CV 1.5 asked


@pytest.mark.parametrize("name", MIXES)
def test_prompts_share_no_first_token(name):
    t = GEN.generate(mix(name), seed=3, seconds=30, vocab=49155)
    firsts = [p[0] for p in t["prompts"]]
    assert len(set(firsts)) == len(firsts)
    assert not set(firsts) & set(t["spare_first"])


def test_lognormal_levels_are_quantiles():
    lv = GEN.levels({"dist": "lognormal", "median": 512, "sigma": 0.8,
                     "min": 64, "max": 2048, "levels": 32})
    assert lv == sorted(lv) and len(lv) == 32
    assert lv[15] < 512 < lv[16]          # the median lies between the middle levels
    assert lv[-1] == 2048 and lv[0] >= 64


def test_every_seed_gets_the_same_schedule():
    m = mix("ragged_open")
    a = GEN.generate(m, seed=1, seconds=51, vocab=49155)
    b = GEN.generate(m, seed=2**31 + 3, seconds=51, vocab=49155)
    assert [len(p) for p in a["prompts"]] == [len(p) for p in b["prompts"]]
    assert a["outputs"] == b["outputs"] and a["due_s"] == b["due_s"]
    assert a["prompts"] != b["prompts"]
