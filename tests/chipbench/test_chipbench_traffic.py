"""The traffic generator: the same seed gives the same requests, every
seed gives the same amount of work in another order, and the lengths,
sharing and arrivals follow what the mix files state."""
import numpy as np
import pytest

from chipbench import traffic

SEEDS = (7, 2**31 + 5)
MIXES = ["shared", "short"]


def _gen(mix, seed, seconds=40.0):
    return traffic.generate(mix, seed, seconds, vocab=92544)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a, b = _gen(mix, SEEDS[1]), _gen(mix, SEEDS[1])
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_schedule(name):
    mix = traffic.load(name)
    a, b = _gen(mix, SEEDS[0]), _gen(mix, SEEDS[1])
    def key(items):
        sp = mix.get("shared_prefix", {"length": 0})
        own = [len(i.prompt) - (sp["length"] if i.prefix_id >= 0 else 0)
               for i in items]
        return (sorted(own), sorted(i.prefix_id for i in items),
                sorted(i.max_new_tokens for i in items),
                sorted(np.round(np.diff([i.due for i in items]), 9)))
    assert key(a) == key(b)
    # the same schedule, other tokens
    assert [i.due for i in a] == [i.due for i in b]
    assert [len(i.prompt) for i in a] == [len(i.prompt) for i in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # the mix's order_seed orders the work
    other = dict(mix, order_seed=mix["order_seed"] + 1)
    assert [i.due for i in _gen(other, SEEDS[0])] != [i.due for i in a]


def test_shared_lengths_and_sharing():
    mix = traffic.load("shared")
    items = _gen(mix, SEEDS[0], seconds=400.0)
    n = len(items)
    assert n == round(mix["arrival"]["rate"] * 400.0)
    sp = mix["shared_prefix"]
    own = np.array([len(i.prompt) - (sp["length"] if i.prefix_id >= 0
                                     else 0) for i in items])
    out = np.array([i.max_new_tokens for i in items])
    assert own.min() >= mix["prompt"]["min"]
    assert own.max() <= mix["prompt"]["max"]
    assert abs(np.median(own) - mix["prompt"]["median"]) <= 2
    assert abs(np.median(out) - mix["output"]["median"]) <= 2
    assert out.min() >= mix["output"]["min"] and out.max() <= \
        mix["output"]["max"]
    # lognormal spread: log-quartiles 0.6745 sigma from the median
    q1, q3 = np.percentile(np.log(own), [25, 75])
    assert abs((q3 - q1) / 2 - 0.6745 * mix["prompt"]["sigma"]) < 0.02
    shared = [i for i in items if i.prefix_id >= 0]
    assert len(shared) == round(sp["share"] * n)
    heads = {tuple(i.prompt[:sp["length"]]) for i in shared}
    assert len(heads) == sp["count"]
    for i in shared:
        assert len(i.prompt) > sp["length"]
    # each system prompt is picked uniformly: about n_shared / count each
    counts = np.bincount([i.prefix_id for i in shared],
                         minlength=sp["count"])
    assert counts.min() >= 0.6 * len(shared) / sp["count"]


def test_system_prompts_repeat_within_one_window():
    # at the cell's own rate and window, requests share system prompts
    import json
    from pathlib import Path
    bench = json.loads((Path(traffic.HERE).parent
                        / "BENCHMARK.json").read_text())
    mix = traffic.load("shared")
    items = _gen(mix, SEEDS[1], seconds=bench["run_seconds"])
    ids = [i.prefix_id for i in items if i.prefix_id >= 0]
    assert len(ids) - len(set(ids)) >= len(items) // 4


def test_short_shares_nothing():
    mix = traffic.load("short")
    items = _gen(mix, SEEDS[0], seconds=400.0)
    assert all(i.prefix_id == -1 for i in items)
    own = np.array([len(i.prompt) for i in items])
    assert own.min() >= mix["prompt"]["min"]
    assert own.max() <= mix["prompt"]["max"]
    assert abs(np.median(own) - mix["prompt"]["median"]) <= 2


@pytest.mark.parametrize("name", MIXES)
def test_arrivals_inside_window_and_poisson(name):
    mix = traffic.load(name)
    due = np.array([i.due for i in _gen(mix, SEEDS[0], seconds=400.0)])
    assert np.all(np.diff(due) >= 0) and due[0] == 0 and due[-1] < 400.0
    gaps = np.diff(due) * mix["arrival"]["rate"]
    # exponential gaps: mean 1, median ln 2
    assert abs(gaps.mean() - 1) < 0.02
    assert abs(np.median(gaps) - np.log(2)) < 0.03


@pytest.mark.parametrize("key,value", [
    ("arrival", {"kind": "bursts", "rate": 1}),
    ("output", {"dist": "uniform", "min": 8, "max": 128})])
def test_unknown_kinds_are_refused(key, value):
    mix = dict(traffic.load("short"), **{key: value})
    with pytest.raises(ValueError):
        _gen(mix, SEEDS[0])


def test_max_positions():
    assert traffic.max_positions(traffic.load("short")) == 512 + 128
    assert traffic.max_positions(traffic.load("shared")) == 256 + 512 + 128
