"""The traffic generator: deterministic per seed, the stated distributions,
and the same set of sizes on every seed."""
import itertools
import json
import math
import os

import numpy as np
import pytest

from bench import generator, spec


DATA = os.path.join(os.path.dirname(__file__), "data")


def traffic(name):
    """A cell's mix by name; ``chat``, an open-loop mix that no cell uses
    yet (its rate awaits a sweep on the chip), is test data."""
    if name == "chat":
        with open(os.path.join(DATA, "chat.json")) as f:
            return json.load(f)
    return spec.traffic(name)


def take(mix, seed, n, vocab=1000):
    return list(itertools.islice(generator.requests(mix, seed, vocab), n))


@pytest.mark.parametrize("name", ["longdoc", "reasoning", "chat"])
def test_deterministic_per_seed(name):
    mix = traffic(name)
    a, b = take(mix, 2**31 + 17, 40), take(mix, 2**31 + 17, 40)
    assert [(r.prompt, r.max_new, r.due_s) for r in a] == \
        [(r.prompt, r.max_new, r.due_s) for r in b]
    c = take(mix, 5, 40)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", ["longdoc", "reasoning", "chat"])
def test_same_sizes_every_seed(name):
    """Each round holds the same lengths on every seed, in another order."""
    mix = traffic(name)
    n = mix["round"]
    rounds = [take(mix, s, 2 * n) for s in (1, 2, 3)]
    for k in range(2):
        sizes = [sorted((len(r.prompt), r.max_new)[0] for r in rs[k * n:(k + 1) * n])
                 for rs in rounds]
        assert sizes[0] == sizes[1] == sizes[2]
    assert [len(r.prompt) for r in rounds[0][:n]] != \
        [len(r.prompt) for r in rounds[1][:n]]


def test_longdoc_distribution():
    mix = traffic("longdoc")
    rs = take(mix, 3, 4 * mix["round"])
    lens = np.array([len(r.prompt) for r in rs])
    outs = np.array([r.max_new for r in rs])
    assert lens.min() >= 512 and lens.max() <= 2048
    assert outs.min() >= 16 and outs.max() <= 32
    # log-uniform 512-2048: mean (hi - lo) / ln(hi / lo) = 1108
    n = 4096
    drawn = generator.quantile(mix["prompt"], (np.arange(n) + 0.5) / n)
    assert abs(drawn.mean() - 1536 / math.log(4)) < 3
    # a 16-request round's midpoint quantiles sit near the median
    assert abs(np.median(lens) - 1024) < 80
    assert abs(outs.mean() - 24) < 1
    assert all(r.due_s == 0.0 for r in rs)


def test_reasoning_distribution():
    mix = traffic("reasoning")
    rs = take(mix, 4, 2 * mix["round"])
    assert all(128 <= len(r.prompt) <= 512 for r in rs)
    assert all(512 <= r.max_new <= 2048 for r in rs)
    assert abs(np.mean([r.max_new for r in rs]) - 1280) < 50


def test_chat_open_loop_rate():
    mix = traffic("chat")
    n = mix["round"]
    rs = take(mix, 6, 4 * n + 1)
    lens = np.array([len(r.prompt) for r in rs])
    outs = np.array([r.max_new for r in rs])
    assert lens.min() >= 32 and lens.max() <= 256
    assert outs.min() >= 16 and outs.max() <= 128
    assert abs(np.median(lens) - 96) <= 8 and abs(np.median(outs) - 48) <= 5
    dues = np.array([r.due_s for r in rs])
    assert np.all(np.diff(dues) >= 0)
    # whole rounds of midpoint exponential gaps: the rate holds closely
    rate = 4 * n / dues[4 * n]
    assert abs(rate / mix["rate_per_s"] - 1) < 0.02


def test_token_ids_in_vocabulary():
    rs = take(traffic("chat"), 7, 64, vocab=50)
    toks = np.concatenate([r.prompt for r in rs])
    assert toks.min() >= 0 and toks.max() < 50 and len(set(toks)) == 50
