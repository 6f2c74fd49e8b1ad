"""The seeded traffic generator and the window arithmetic."""

import math
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import json  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.traffic import Traffic, quantile_grid  # noqa: E402
from bench.window import (inter_token_gaps, percentile, tokens_in,  # noqa
                          ttfts)

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"
# an open and a resident mix as the generator reads them; no cell of the
# benchmark runs these yet
OTHER = {
    "chat": {"schedule_seed": 0, "loop": "open", "rate_per_s": 1.2,
             "block": 64, "max_len": 1536,
             "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                        "min": 16, "max": 1024},
             "output": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                        "min": 8, "max": 384}},
    "longgen": {"schedule_seed": 0, "loop": "resident", "max_len": 32768,
                "prompt": {"dist": "loguniform", "min": 4096,
                           "max": 8192}},
}


def _mix(name):
    if name in OTHER:
        return dict(OTHER[name])
    return json.loads((MIXES / f"{name}.json").read_text())


def _take(mix, seed, n, vocab=1000, slots=16):
    t = Traffic(mix, seed, vocab, slots)
    return [t.next_request() for _ in range(n)]


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_same_seed_same_requests(name):
    a, b = _take(_mix(name), 7, 130), _take(_mix(name), 7, 130)
    assert [(r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.max_new, r.prompt.tolist()) for r in b]


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_every_seed_serves_the_same_sizes_with_other_tokens(name):
    mix = _mix(name)
    n = 2 * mix["block"]
    a, b = _take(mix, 1, n), _take(mix, 2**31 + 5, n)
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]
    assert a[0].prompt.tolist() != b[0].prompt.tolist()
    # the block is shuffled, not sorted: long prompts are spread out
    first = [len(r.prompt) for r in a[:mix["block"]]]
    assert first != sorted(first)
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    c = _take(other, 1, mix["block"])
    assert Counter(len(r.prompt) for r in c) == Counter(first)


@pytest.mark.parametrize("name,part", [("batch", "prompt"),
                                       ("batch", "output"),
                                       ("chat", "prompt"),
                                       ("chat", "output")])
def test_stated_clips_and_medians(name, part):
    d = _mix(name)[part]
    g = quantile_grid(d, 64)
    assert g.min() >= d["min"] and g.max() <= d["max"]
    assert abs(np.median(g) - d["median"]) <= 0.05 * d["median"]
    reqs = _take(_mix(name), 3, 64)
    got = [len(r.prompt) if part == "prompt" else r.max_new for r in reqs]
    assert sorted(got) == sorted(g.tolist())


def test_requests_fit_the_context_and_vocab():
    mix = _mix("batch")
    for r in _take(mix, 11, 256, vocab=50):
        assert len(r.prompt) + r.max_new <= mix["max_len"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50


def test_open_loop_arrivals_keep_their_rate():
    mix = _mix("chat")
    a = Traffic(mix, 5, 1000, 16).arrivals(60.0)
    b = Traffic(mix, 6, 1000, 16).arrivals(60.0)
    dues = [r.due for r in a]
    assert dues == sorted(dues) and dues[-1] < 60.0
    # a whole block of mid-quantile gaps spans block / rate seconds
    assert abs(len(a) - 60.0 * mix["rate_per_s"]) <= mix["block"]
    assert dues == [r.due for r in b]


def test_resident_loop_fills_every_slot():
    mix = _mix("longgen")
    reqs = Traffic(mix, 9, 1000, 16).resident()
    assert len(reqs) == 16
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert len(r.prompt) + r.max_new == mix["max_len"]


def test_rate_is_over_the_whole_window():
    stamps = {1: [0.5, 1.0, 9.0], 2: [11.0], 3: [2.0, 3.0]}
    assert tokens_in(stamps, 0.0, 10.0) == 5  # 11.0 falls outside
    assert tokens_in(stamps, 0.0, 10.0) / 10.0 == 0.5


def test_gaps_and_percentile_over_all_requests():
    stamps = {1: [0.0, 1.0, 3.0], 2: [0.5, 0.6], 3: [5.0]}
    gaps = inter_token_gaps(stamps, 0.0, 10.0)
    assert sorted(gaps) == pytest.approx([0.1, 1.0, 2.0])
    assert percentile([4, 1, 3, 2], 0.5) == 2
    assert percentile(range(1, 101), 0.95) == 95
    assert percentile(range(1, 101), 0.90) == 90
    assert percentile([7.0], 0.99) == 7.0


def test_unfinished_requests_rank_slowest():
    due = {1: 0.0, 2: 1.0, 3: 9.5, 4: 12.0}
    first = {1: 0.2, 2: 1.5}
    w = ttfts(due, first, 10.0)
    # uid 4 is due after the close; uid 3 waited 0.5 s and got no token,
    # so it ranks with the slowest served one
    assert sorted(w) == pytest.approx([0.2, 0.5, 0.5])
    assert percentile(w, 1.0) == pytest.approx(0.5)
    w = ttfts({1: 0.0, 2: 2.0}, {1: 0.1}, 10.0)
    assert max(w) == pytest.approx(8.0) and not any(map(math.isinf, w))
