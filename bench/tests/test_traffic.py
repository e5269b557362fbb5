"""The traffic generator: a seed reproduces its requests exactly, every
seed gets the same sizes and gaps, and the copied percentile."""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from traffic import generate

BENCH = pathlib.Path(__file__).resolve().parents[1]
MIX = json.loads((BENCH / "traffic" / "mixes" / "serve-chat.json")
                 .read_text())


def _key(reqs):
    return [(r.rid, r.arrival_s, tuple(r.prompt), r.max_new_tokens)
            for r in reqs]


def test_seed_reproduces_requests():
    a = generate.serve_requests(MIX, 2**31 + 77, 30.0, 32000)
    b = generate.serve_requests(MIX, 2**31 + 77, 30.0, 32000)
    assert _key(a) == _key(b)
    c = generate.serve_requests(MIX, 5, 30.0, 32000)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_work():
    a = generate.serve_requests(MIX, 1, 30.0, 32000)
    b = generate.serve_requests(MIX, 2, 30.0, 32000)
    assert len(a) == len(b) == round(MIX["arrivals"]["rate_per_s"] * 30)
    assert sorted((len(r.prompt), r.max_new_tokens) for r in a) == \
        sorted((len(r.prompt), r.max_new_tokens) for r in b)
    for reqs in (a, b):
        arr = [r.arrival_s for r in reqs]
        assert arr == sorted(arr) and 0 < arr[0] and arr[-1] < 30.0


def test_lengths_keep_to_the_mix():
    reqs = generate.serve_requests(MIX, 3, 60.0, 32000)
    p, o = MIX["prompt"], MIX["output"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert len(r.prompt) % p["round_to"] == 0
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert len(r.prompt) + r.max_new_tokens <= MIX["engine"]["max_len"]
    assert np.median([len(r.prompt) for r in reqs]) == \
        pytest.approx(p["median"], rel=0.25)


def test_warmup_covers_every_last_chunk():
    rems = list(range(16, 129, 16))
    assert generate.warmup_prompt_lengths(MIX) == \
        rems + [128 + r for r in rems]


def test_percentile_interpolates():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert generate.percentile(xs, 0.0) == 1.0
    assert generate.percentile(xs, 1.0) == 4.0
    assert generate.percentile(xs, 0.5) == 2.5
    assert generate.percentile(list(range(101)), 0.95) == 95.0
    assert np.isnan(generate.percentile([], 0.5))
