"""The generator is a pure function of (mix, seed, seconds): the same seed
gives the same requests, another seed the same multiset in another order,
and nothing in it can see the system."""

import json
import os

import numpy as np
import pytest

from chipbench import contract, traffic_gen

CHAT = json.load(open(os.path.join(contract.ROOT, "chipbench", "traffic",
                                   "chat.json")))
BIG_SEED = 2 ** 31 + 12345


def _window(sched):
    return [r for r in sched["requests"] if r["phase"] == "window"]


def test_the_same_seed_gives_the_same_requests():
    a = traffic_gen.serve_schedule(CHAT, BIG_SEED, 20, 32768)
    b = traffic_gen.serve_schedule(CHAT, BIG_SEED, 20, 32768)
    assert a == b


def test_seeds_differ_in_order_and_never_in_the_amount_of_work():
    a = _window(traffic_gen.serve_schedule(CHAT, 1, 30, 32768))
    b = _window(traffic_gen.serve_schedule(CHAT, BIG_SEED, 30, 32768))
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert sorted(len(r["prompt"]) for r in a) \
        == sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    gaps = [np.sort(np.diff([r["due_s"] for r in x])) for x in (a, b)]
    assert len(a) == round(CHAT["arrivals"]["rate_per_s"] * 30)
    # the same gaps in another order: the sorted differences nearly agree
    assert abs(gaps[0].sum() - gaps[1].sum()) < 2.0


def test_lengths_follow_the_mix_and_stay_inside_its_clip():
    w = _window(traffic_gen.serve_schedule(CHAT, 7, 200, 32768))
    plen = np.array([len(r["prompt"]) for r in w])
    out = np.array([r["max_new"] for r in w])
    assert plen.min() >= 32 and plen.max() <= 1536
    assert out.min() >= 16 and out.max() <= 384
    assert abs(np.median(plen) - 256) < 16 and abs(np.median(out) - 128) < 8
    assert all(0 < t < 32768 for r in w[:5] for t in r["prompt"])


def test_due_times_lie_in_their_phase_and_in_order():
    s = traffic_gen.serve_schedule(CHAT, 3, 25, 32768)
    due = [r["due_s"] for r in s["requests"]]
    assert due == sorted(due)
    ramp = CHAT["ramp"]["seconds"]
    assert (s["window_from_s"], s["window_to_s"]) == (ramp, ramp + 25)
    for r in s["requests"]:
        lo, hi = {"live": (0, 0), "ramp": (0, ramp),
                  "window": (ramp, ramp + 25),
                  "tail": (ramp + 25, ramp + 25 + CHAT["tail_s"])}[r["phase"]]
        assert lo <= r["due_s"] <= hi
    live = [r for r in s["requests"] if r["phase"] == "live"]
    assert len(live) == CHAT["ramp"]["live"]


@pytest.mark.parametrize("mix", [
    {"prompt_len": {"dist": "loguniform", "min": 1024, "max": 3584},
     "output_len": {"dist": "uniform", "min": 8, "max": 32},
     "arrivals": {"process": "backlog", "requests": 24}},
    {"prompt_len": {"dist": "fixed", "value": 300},
     "output_len": {"dist": "fixed", "value": 10},
     "arrivals": {"process": "poisson", "rate_per_s": 2.0},
     "shared_prefix": {"tokens": 128, "groups": 2}},
])
def test_the_mixes_later_cells_will_bring_need_no_new_code(mix):
    s = traffic_gen.serve_schedule(mix, 5, 10, 1000)
    w = _window(s)
    if mix["arrivals"]["process"] == "backlog":
        assert len(w) == 24 and {r["due_s"] for r in w} == {0.0}
        assert min(len(r["prompt"]) for r in w) >= 1024
    else:
        assert len(w) == 20
        heads = {tuple(r["prompt"][:128]) for r in w}
        assert len(heads) <= 2


def test_train_batches_are_seeded_and_every_row_differs():
    mix = {"global_batch": 8, "seq_len": 64}
    tok, tgt = traffic_gen.train_batch(mix, BIG_SEED, 4, 50257)
    tok2, _ = traffic_gen.train_batch(mix, BIG_SEED, 4, 50257)
    other, _ = traffic_gen.train_batch(mix, BIG_SEED, 5, 50257)
    assert tok.shape == tgt.shape == (8, 64) and tok.dtype == np.int32
    assert (tok == tok2).all() and not (tok == other).all()
    assert (tok[:, 1:] == tgt[:, :-1]).all()
    assert len({row.tobytes() for row in tok}) == 8
    assert tok.max() < 50257
