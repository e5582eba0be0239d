"""The generators: the same seed gives the same inputs; two seeds give the
same multiset of sizes and gaps (the same schedule where the mix fixes one,
else in another order); sizes stay in range."""

import json
import os

import numpy as np

from bench.traffic import closed_loop, open_loop
from bench.traffic.lengths import exp_gaps, lengths

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_same_seed_same_inputs_other_seed_same_work():
    spec = load("chat")
    a = open_loop.make(spec, 2**33 + 1, 30, 49152)
    b = open_loop.make(spec, 2**33 + 1, 30, 49152)
    c = open_loop.make(spec, 12345, 30, 49152)
    assert [(r.due, r.max_new) for r in a.reqs] == [(r.due, r.max_new) for r in b.reqs]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a.reqs, b.reqs))
    # the chat mix fixes its schedule: another seed draws other token ids only
    assert [(r.due, r.max_new, len(r.prompt)) for r in a.reqs] == \
        [(r.due, r.max_new, len(r.prompt)) for r in c.reqs]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a.reqs, c.reqs))
    p = spec["prompt"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a.reqs)
    assert all(len(r.prompt) + r.max_new <= spec["max_total"] for r in a.reqs)


def test_open_loop_without_schedule_seed_shuffles_per_seed():
    spec = {k: v for k, v in load("chat").items() if k != "schedule_seed"}
    a = open_loop.make(spec, 2**33 + 1, 30, 49152)
    c = open_loop.make(spec, 12345, 30, 49152)
    assert [r.due for r in a.reqs] != [r.due for r in c.reqs]
    assert [r.max_new for r in a.reqs] != [r.max_new for r in c.reqs]


def test_two_seeds_draw_one_multiset():
    spec = load("chat")
    pa, oa = lengths(spec, 500, np.random.default_rng(1))
    pc, oc = lengths(spec, 500, np.random.default_rng(2))
    assert not np.array_equal(pa, pc)
    assert np.array_equal(np.sort(pa), np.sort(pc))
    g = exp_gaps(spec["rate_per_s"], 500)
    assert abs(g.mean() - 1 / spec["rate_per_s"]) < 0.02 / spec["rate_per_s"]


def test_open_loop_rate():
    spec = load("chat")
    s = open_loop.make(spec, 7, 30, 49152)
    horizon = spec["warm_s"] + 30
    assert abs(len(s.reqs) - spec["rate_per_s"] * horizon) <= 0.05 * spec["rate_per_s"] * horizon


def test_closed_loop_sends_on_return():
    spec = load("code_batch")
    s = closed_loop.make(spec, 3, 30, 49152)
    first = s.pending(0.0)
    assert len(first) == spec["clients"] and s.pending(0.1) == []
    s.finished(first[5], 1.0)
    (nxt,) = s.pending(1.0)
    assert nxt.client == first[5].client and nxt.due == 1.0
    p, o = spec["prompt"], spec["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] and o["min"] <= r.max_new <= o["max"]
               for r in first)
