"""``serve_backlog``: requests that are all present when the server starts.

A pure function of the traffic file and the seed.  The file fixes the
multiset of (prompt length, output length) pairs and, through
``order_seed``, the order they queue in; the run's seed draws the token
ids (and, elsewhere, the weights).  Every seed so offers the same work at
the same moments.  (Letting the seed permute even inside groups of eight
moved the window's mean context by 1.5 % between seeds in a simulation,
and the paged-decode kernel's time follows context.)

Stream: the pairs are ranked by the decode work they cost and cut into
``strata`` equal classes; the stream is a sequence of groups holding one
pair of each class, ``order_seed`` choosing which pair of a class lands in
which group and the order inside a group.  Any stretch of the queue then
holds the same mix.

Stationary start: the server is simulated over one whole pass of the
stream (``max_batch`` slots, one token per slot and step, a freed slot
taking the next request) and the requests in flight when the pass ends
are the first ``max_batch`` of the queue, each with the tokens it had
already produced appended to its prompt and only the rest left to
produce.  The window so opens on the contexts and the spread of
completions the backlog would have reached by itself; the whole stream
follows behind them.
"""

from __future__ import annotations

import heapq

import numpy as np


def _work(prompt: int, output: int) -> float:
    """Keys read while decoding: the cost that grows with context."""
    return output * (prompt + output / 2.0)


def stream(traffic: dict) -> list[tuple[int, int]]:
    """The file's stream: group after group of ``strata`` pairs, one of
    each class in each."""
    pairs = [tuple(p) for p in traffic["pairs"]]
    strata = int(traffic["strata"])
    if len(pairs) % strata:
        raise ValueError(f"{len(pairs)} pairs do not divide into {strata} strata")
    rng = np.random.Generator(np.random.PCG64(int(traffic["order_seed"])))
    ranked = sorted(pairs, key=lambda p: (_work(*p), p))
    per = len(pairs) // strata
    columns = [[ranked[i * per + j] for j in rng.permutation(per)]
               for i in range(strata)]
    return [columns[i][g] for g in range(per) for i in rng.permutation(strata)]


def in_flight_after_one_pass(queue: list[tuple[int, int]],
                             max_batch: int) -> list[tuple[int, int, int]]:
    """Simulate ``max_batch`` slots over one pass of ``queue``; ->
    ``(prompt, output, tokens already produced)`` of the requests in
    flight at the step the last request of the pass is admitted, the
    stream having started over behind them."""
    running: list[tuple[int, int, int]] = []  # (finish step, start step, index)
    now = 0
    for i in range(len(queue)):
        if len(running) == max_batch:
            now = heapq.heappop(running)[0]
        heapq.heappush(running, (now + queue[i][1], now, i))
    return [(*queue[i], now - start)
            for _, start, i in sorted(running, key=lambda r: r[2])]


def generate(traffic: dict, seed: int, *, vocab: int, max_batch: int) -> list[dict]:
    """-> requests in queue order: ``{"rid", "prompt": [ids], "max_new_tokens",
    "full_output", "produced"}`` (``full_output`` is the pair's output
    length, ``produced`` how much of it the stationary start counts as
    already produced and carries in the prompt)."""
    queue = stream(traffic)
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    order = in_flight_after_one_pass(queue, max_batch) + [(*p, 0) for p in queue]
    return [{"rid": i,
             "prompt": rng.integers(0, vocab, size=int(prompt + produced)).tolist(),
             "max_new_tokens": int(output - produced),
             "full_output": int(output), "produced": int(produced)}
            for i, (prompt, output, produced) in enumerate(order)]
