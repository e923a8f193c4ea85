"""Each generator is a pure function of its file and the seed, and offers
the same work under every seed."""

from collections import Counter

import numpy as np
import pytest

from benchmarks.common import load_json
from benchmarks.generators import lm_batches, serve_backlog

SEEDS = (3, 2**31 + 5)


@pytest.fixture(scope="module")
def backlog():
    return load_json("traffic", "decode_heavy.backlog.json")


def _gen(traffic, seed, max_batch=32):
    return serve_backlog.generate(traffic, seed, vocab=50257, max_batch=max_batch)


def test_backlog_is_a_pure_function_of_file_and_seed(backlog):
    assert _gen(backlog, SEEDS[0]) == _gen(backlog, SEEDS[0])
    assert _gen(backlog, SEEDS[0]) != _gen(backlog, SEEDS[1])


def test_backlog_same_lengths_in_the_same_order_under_two_seeds(backlog):
    want = Counter(tuple(p) for p in backlog["pairs"])
    for seed in SEEDS:
        reqs = _gen(backlog, seed)
        got = Counter((len(r["prompt"]), r["full_output"]) for r in reqs[32:])
        assert got == want
        assert all(len(r["prompt"]) + r["max_new_tokens"] <= 2048 for r in reqs)
        assert all(0 <= t < 50257 for r in reqs for t in r["prompt"])
    a, b = (_gen(backlog, s) for s in SEEDS)
    shape = lambda reqs: [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]  # noqa: E731
    assert shape(a) == shape(b)                      # the same work at the same moments
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]   # other ids


def test_backlog_every_stretch_of_the_queue_holds_the_same_mix(backlog):
    strata = backlog["strata"]
    ranked = sorted((tuple(p) for p in backlog["pairs"]),
                    key=lambda p: (serve_backlog._work(*p), p))
    per = len(ranked) // strata
    cls = {}
    for i, p in enumerate(ranked):
        cls.setdefault(p, set()).add(i // per)
    queue = _gen(backlog, SEEDS[0])[32:]
    for g in range(0, len(queue), strata):
        group = [(len(r["prompt"]), r["full_output"]) for r in queue[g:g + strata]]
        # one pair of every class in every group
        assert set().union(*(cls[p] for p in group)) == set(range(strata))


def _unit_steps(reqs, slots_n, steps):
    """Total context at each step of a server that emits one token per
    slot and step and refills a freed slot from the queue."""
    queue, slots, out = list(reqs), [None] * slots_n, []
    for _ in range(steps):
        for i in range(slots_n):
            if slots[i] is None:
                r = queue.pop(0)
                slots[i] = [len(r["prompt"]), r["max_new_tokens"]]
        out.append(sum(s[0] for s in slots))
        for i, s in enumerate(slots):
            s[0], s[1] = s[0] + 1, s[1] - 1
            if s[1] == 0:
                slots[i] = None
    return out


def test_backlog_window_opens_on_a_full_batch_in_steady_state(backlog):
    reqs = _gen(backlog, SEEDS[1])
    head = reqs[:32]
    # each carries what it had produced in its prompt and owes the rest
    assert all(r["max_new_tokens"] == r["full_output"] - r["produced"] > 0 for r in head)
    assert all(r["produced"] == 0 for r in reqs[32:])
    left = sorted(r["max_new_tokens"] / r["full_output"] for r in head)
    assert len({r["max_new_tokens"] for r in head}) > 16       # not in lockstep
    assert left[7] < 0.5 < left[-8]                           # spread over the whole range
    # the context the window opens on is the context it keeps
    ctx = _unit_steps(reqs, 32, 800)
    later = sum(ctx[100:]) / len(ctx[100:])
    assert abs(ctx[0] - later) < 0.15 * later
    # a cold start (every request fresh) would open far below it
    cold = _unit_steps(reqs[32:], 32, 1)[0]
    assert cold < 0.75 * later


@pytest.mark.parametrize("name", ["pretrain_2k", "pretrain_2k.mesh4"])
def test_lm_batches_rows(name):
    traffic = load_json("traffic", name + ".json")
    gb = traffic["rows_per_step"]
    a = lm_batches.generate(traffic, SEEDS[1], vocab=50257, global_batch=gb)
    b = lm_batches.generate(traffic, SEEDS[1], vocab=50257, global_batch=gb)
    c = lm_batches.generate(traffic, SEEDS[0], vocab=50257, global_batch=gb)
    assert a.shape == c.shape == (traffic["steps_per_epoch"] * gb, 2049)
    assert a.dtype == np.int32 and (a == b).all() and not (a == c).all()
    assert 0 <= a.min() and a.max() < 50257
    assert len({row.tobytes() for row in a}) == len(a)  # all rows differ
