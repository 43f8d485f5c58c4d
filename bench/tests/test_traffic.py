import json
import os

import numpy as np

from bench import traffic_gen

from conftest import ROOT

SAT = json.load(open(os.path.join(ROOT, "bench", "traffic", "p512-sat.json")))
CHAT = dict(SAT, prompt_buckets=[128, 256, 512, 1024], prompt_probs=[0.35, 0.30, 0.20, 0.15],
            output_max=256, rate_per_s=0.72, backlog_at_open=0)
BIG = 3_000_000_017   # wider than 32 signed bits, as the driver's seeds are


def _key(s):
    return [(a.due_s, a.prompt.tobytes(), a.max_new_tokens, a.temperature) for a in s]


def test_serve_schedule_repeats_for_a_seed_and_differs_across_seeds():
    a = traffic_gen.serve_schedule(CHAT, BIG, 51, 50304)
    b = traffic_gen.serve_schedule(CHAT, BIG, 51, 50304)
    c = traffic_gen.serve_schedule(CHAT, BIG + 1, 51, 50304)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic_gen.serve_schedule(CHAT, 1, 51, 50304)
    b = traffic_gen.serve_schedule(CHAT, 2, 51, 50304)
    assert len(a) == len(b) == int(CHAT["rate_per_s"] * 51)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == sorted(x.max_new_tokens for x in b)
    gaps = lambda s: sorted(np.round(np.diff([x.due_s for x in s]), 9))
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert max(x.due_s for x in a) < 51
    assert set(len(x.prompt) for x in a) <= set(CHAT["prompt_buckets"])
    assert all(CHAT["output_min"] <= x.max_new_tokens <= CHAT["output_max"] for x in a)
    assert all((x.prompt >= 1).all() and (x.prompt < 50304).all() for x in a)
    assert [x.temperature > 0 for x in a] == [i % 2 == 1 for i in range(len(a))]


def test_output_lengths_follow_the_traffic_file():
    a = traffic_gen.serve_schedule(CHAT, 5, 51, 50304)
    outs = np.array([x.max_new_tokens for x in a])
    assert abs(np.median(outs) - CHAT["output_median"]) <= 2


def test_a_backlog_is_waiting_when_the_window_opens():
    a = traffic_gen.serve_schedule(SAT, BIG, 51, 50304)
    b = SAT["backlog_at_open"]
    assert len(a) == int(SAT["rate_per_s"] * 51)
    assert [x.due_s for x in a[: b + 1]] == [0.0] * (b + 1)
    assert 0 < a[b + 1].due_s and max(x.due_s for x in a) < 51
    assert {len(x.prompt) for x in a} == {512}
    assert max(x.max_new_tokens for x in a) == SAT["output_max"]
