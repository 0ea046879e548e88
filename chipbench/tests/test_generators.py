"""Every run of a cell offers the same load: the seed permutes, it never
changes how much work a run holds."""

import json
import os
from collections import Counter

import pytest

from chipbench.generators import closed_loop, lengths, open_loop, token_batches

HERE = os.path.dirname(os.path.abspath(__file__))
LIMITS = {"vocab_size": 32768, "max_len": 3072}


def _traffic(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def _shape(reqs):
    return Counter((len(r["tokens"]), r["max_new_tokens"]) for r in reqs)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 11)])
def test_open_loop_same_load_for_every_seed(seeds):
    t = _traffic("chat")
    a, b = (open_loop.plan(t, 48.0, s, LIMITS) for s in seeds)
    assert len(a["arrivals"]) == len(b["arrivals"]) \
        == round(t["rate_per_s"] * 48.0)
    assert _shape(a["arrivals"]) == _shape(b["arrivals"])
    assert _shape(a["standing"]) == _shape(b["standing"])
    assert len(a["standing"]) == round(t["rate_per_s"] * t["mean_life_s"])
    # ... and differ only in order, times and token ids
    assert [r["due_s"] for r in a["arrivals"]] \
        != [r["due_s"] for r in b["arrivals"]]
    assert [len(r["tokens"]) for r in a["arrivals"]] \
        != [len(r["tokens"]) for r in b["arrivals"]]
    assert a["arrivals"][0]["tokens"] != b["arrivals"][0]["tokens"]
    due = [r["due_s"] for r in a["arrivals"]]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 48.0


def test_open_loop_is_deterministic_in_the_seed():
    t = _traffic("chat")
    assert open_loop.plan(t, 20.0, 5, LIMITS) == open_loop.plan(
        t, 20.0, 5, LIMITS)


def test_closed_loop_every_client_walks_the_same_list():
    t = _traffic("longdoc")
    a, b = (closed_loop.plan(t, 48.0, s, LIMITS) for s in (3, 4))
    assert len(a["standing"]) == t["clients"] == len(a["chains"])
    assert _shape(a["standing"]) == _shape(b["standing"])
    walks = [_shape(c) for p in (a, b) for c in p["chains"].values()]
    assert all(w == walks[0] for w in walks)

    def orders(p):   # the walks themselves, whoever holds them
        return sorted(tuple((len(r["tokens"]), r["max_new_tokens"])
                            for r in c) for c in p["chains"].values())

    assert orders(a) == orders(b)          # same work in the same order
    assert len(set(orders(a))) > 1         # the walks differ from each other
    assert a["chains"]["c0"][0]["tokens"] != b["chains"]["c0"][0]["tokens"]
    for r in a["standing"] + a["chains"]["c0"]:
        assert len(r["tokens"]) + r["max_new_tokens"] < LIMITS["max_len"]


def test_stratified_lengths_follow_the_distribution():
    d = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32,
         "max": 2048}
    xs = lengths.stratified(d, 101)
    assert xs == sorted(xs) and xs[50] == 256
    assert xs[0] >= 32 and xs[-1] == 2048
    u = lengths.stratified({"dist": "loguniform", "min": 1024, "max": 2816},
                           3)
    assert u[1] == round((1024 * 2816) ** 0.5)
    perm = lengths.fixed_shuffle(list(range(10)), 1)
    assert sorted(perm) == list(range(10)) and perm != list(range(10))


def test_token_batches_rows_all_differ_and_repeat_by_seed():
    t = {"seq": 16}
    g = token_batches.batches(t, 9, 4, 1000)
    b0, b1 = next(g), next(g)
    assert b0["tokens"].shape == (4, 16) == b0["targets"].shape
    assert (b0["tokens"][:, 1:] == b0["targets"][:, :-1]).all()
    rows = {tuple(r) for b in (b0, b1) for r in b["tokens"]}
    assert len(rows) == 8
    again = next(token_batches.batches(t, 9, 4, 1000))
    assert (again["tokens"] == b0["tokens"]).all()
