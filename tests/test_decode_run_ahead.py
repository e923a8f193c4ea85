"""ISSUE 32: the decode loop one step deep.

``Scheduler.step`` launches decode step n before it reads step n-1's
tokens.  What must not change is what is served: every stream here is
compared, token for token, with a plain loop over the SYNCHRONOUS
``InferenceEngine.decode`` (prefill, then one call a token, each call
reading its own launch) — for ``TransformerLM``, ``HybridLM`` and a looped
``HybridLM``, greedy and sampled, through admission, endings by length and
by stop token, preemption, a deadline's expiry, a prefix-cache hit, a
weight swap and an injected fault.  The rest pins the mechanism: what the
span ring shows of it, its counters, and that the engine builds one decode
program for both kinds of call.
"""

import time

import numpy as np
import pytest

import jax

from theanompi_tpu.resilience.faults import FaultInjected, FaultPlan
from theanompi_tpu.serving import (
    InferenceEngine,
    Request,
    Scheduler,
    blocks_for,
    serve_report,
)
from theanompi_tpu.telemetry import spans

KINDS = ("dense", "hybrid", "looped")
BLOCK, SLOTS, BLOCKS = 4, 3, 14      # 13 usable blocks of 4: 52 tokens
VOCAB = 61

_CONFIGS = {
    "hybrid": {"pattern": "M*E", "dim": 32, "vocab": VOCAB, "seq_len": 32,
               "mamba_heads": 4, "mamba_head_dim": 16, "state_size": 8,
               "n_groups": 2, "chunk_size": 8, "heads": 4, "kv_heads": 2,
               "head_dim": 8, "n_experts": 4, "top_k": 2, "latent": 16,
               "expert_dim": 24, "shared_dim": 32},
    "looped": {"pattern": "*-", "dim": 32, "vocab": VOCAB, "seq_len": 32,
               "heads": 4, "kv_heads": 4, "head_dim": 8, "ffn_dim": 48,
               "loops": 2, "post_norm": True, "rope_theta": 1e4},
}


def _engine(model, params):
    return InferenceEngine(model, params, block_size=BLOCK, max_batch=SLOTS,
                           num_blocks=BLOCKS, seed=3)


@pytest.fixture(scope="module")
def models(dense_model):
    """kind -> (model, params); the dense one is the session's lightly
    trained ``TransformerLM`` (read-only)."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    out = {"dense": dense_model[:2]}
    for kind, cfg in _CONFIGS.items():
        model = HybridLM(dict(cfg))
        out[kind] = (model, model.init_params(jax.random.PRNGKey(0))[0])
    return out


@pytest.fixture(scope="module")
def engines(models):
    """kind -> one engine, shared by the tests that leave its weights
    alone (its pools are scratch)."""
    made: dict = {}

    def get(kind):
        if kind not in made:
            made[kind] = _engine(*models[kind])
        return made[kind]
    return get


def _prompt(rid, n):
    rng = np.random.RandomState(100 + rid)
    return rng.randint(1, VOCAB, size=n).tolist()


def plain(engine, prompt, n, temp=0.0, rid=0, stop=None):
    """The synchronous loop: a prefill, then one ``engine.decode`` a token,
    each reading its own launch; alone in slot 0."""
    row = list(range(1, blocks_for(len(prompt) + n, BLOCK) + 1))
    tok, _ = engine.prefill(row[:blocks_for(len(prompt), BLOCK)], prompt,
                            temp, rid, slot=0)
    out = [tok]
    tables = np.zeros((SLOTS, engine.max_blocks_per_seq), np.int32)
    tables[0, :len(row)] = row
    lengths, tokens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    temps, rids = np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32)
    temps[0], rids[0] = temp, rid
    while len(out) < n and out[-1] != stop:
        lengths[0], tokens[0] = len(prompt) + len(out) - 1, out[-1]
        nxt, logits = engine.decode(tables, lengths, tokens, temps, rids)
        assert logits.shape == (SLOTS, VOCAB)  # a direct caller gets them
        if temp == 0.0:
            assert int(nxt[0]) == int(np.argmax(logits[0]))
        out.append(int(nxt[0]))
    return out


def _requests(work, temp=0.0, **kw):
    return [Request(rid=rid, prompt=_prompt(rid, p), max_new_tokens=n,
                    temperature=temp, **kw) for rid, p, n in work]


def _run(sched, reqs):
    """Submit, step to idle; -> every request a step returned."""
    for r in reqs:
        sched.submit(r)
    out = []
    while not sched.idle:
        out += sched.step()
        if sched._unread is not None:
            assert not sched.idle  # a launch is out: not idle
    return out


def _decode_spans(since):
    return [r for r in spans.snapshot()
            if r.name == "serve.decode" and r.t0 >= since]


# the mix: six requests over three slots whose contexts outgrow the pool
MIX = [(0, 8, 12), (1, 7, 12), (2, 8, 11), (3, 5, 6), (4, 6, 9), (5, 3, 1)]


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_streams_are_the_synchronous_loops(engines, kind, temp):
    """Admission, endings by length, slot reuse and preemption on a pool
    too small: the same tokens as one request at a time, read at once."""
    eng = engines(kind)
    sched = Scheduler(eng)
    reqs = _requests(MIX, temp)
    returned = _run(sched, reqs)
    assert sched.n_preemptions > 0 and sched.n_ran_ahead > 0
    assert sorted(r.rid for r in returned) == [r.rid for r in reqs]
    for r in reqs:
        # returned by the step that read its last token: never short
        assert r.state == "done" and len(r.generated) == r.max_new_tokens
    for r in reqs:
        assert r.generated == plain(eng, r.prompt, r.max_new_tokens, temp,
                                    r.rid), r.rid
    # drains (the preemptions) restart the pipeline: those launches and the
    # first went out with nothing unread
    assert 0 < sched.n_steps - sched.n_ran_ahead <= 1 + sched.n_preemptions


@pytest.mark.parametrize("kind", KINDS)
def test_a_stop_token_is_found_one_step_late_and_costs_one_slot_step(
        engines, kind):
    eng = engines(kind)
    temp = 0.8  # sampled: the tiny looped stack's greedy streams repeat
    work = [(10, 5, 8), (11, 4, 8), (12, 5, 7), (13, 5, 6)]
    whole = {rid: plain(eng, _prompt(rid, p), n, temp, rid)
             for rid, p, n in work}
    # a token request 10 produces by a decode step, with steps to go
    stop = next(t for i, t in enumerate(whole[10])
                if 1 <= i < 6 and t not in whole[10][:i])
    want = {rid: plain(eng, _prompt(rid, p), n, temp, rid, stop=stop)
            for rid, p, n in work}
    assert len(want[10]) < 8
    t0 = time.perf_counter()
    sched = Scheduler(eng, eos_token=stop)
    reqs = _requests(work, temp)
    _run(sched, reqs)
    assert sched.n_preemptions == 0
    for r in reqs:
        # the overrun step's token is dropped; the slot's next owner (13
        # waits for the first slot to go) is served as if alone
        assert r.state == "done" and r.generated == want[r.rid], r.rid
    # found late wherever a decode step produced it with steps still to go
    late = [rid for rid, toks in want.items()
            if toks[-1] == stop and 1 < len(toks) < len(whole[rid])]
    assert 10 in late and sched.n_overrun_slots == len(late)
    tags = [r.tags for r in _decode_spans(t0)]
    # counted on the span that reads the overrun launch: a decode call or,
    # for the last launch of all, the drain's ``serve.collect``
    drained = [r.tags for r in spans.snapshot()
               if r.name == "serve.collect" and r.t0 >= t0]
    assert sum(t["overrun_slots"] for t in tags + drained) == len(late)
    report = serve_report({r.rid: r for r in reqs}, 1.0, sched)
    assert report["run_ahead"] == {
        "launched": sum(t["launched"] for t in tags),
        "ran_ahead": sum(t["ran_ahead"] for t in tags),
        "overrun_slots": len(late)}
    assert report["run_ahead"]["launched"] == sched.n_steps == len(tags)


@pytest.mark.parametrize("kind", KINDS)
def test_an_expired_deadline_drains_first(engines, kind):
    """The request that expires keeps every token launched for it, as in
    a loop that never ran ahead; the others go on."""
    eng = engines(kind)
    sched = Scheduler(eng)
    reqs = _requests([(20, 5, 10), (21, 6, 9)]) + _requests(
        [(22, 4, 10)], total_deadline_ms=600_000.0)
    for r in reqs:
        sched.submit(r)
    for _ in range(3):
        assert not sched.step()
    assert [len(r.generated) for r in reqs] == [3, 3, 3]  # one step behind
    assert sched._unread is not None and not sched.idle
    reqs[2].t_submit -= 601.0
    ran_ahead = sched.n_ran_ahead
    out = sched.step()
    assert [r.rid for r in out] == [22] and reqs[2].state == "expired"
    assert len(reqs[2].generated) == 4  # its prefill's and three steps'
    assert sched.n_ran_ahead == ran_ahead  # the launch after a drain
    while not sched.idle:
        sched.step()
    for r in reqs:
        want = plain(eng, r.prompt, r.max_new_tokens, rid=r.rid)
        assert r.generated == want[:len(r.generated)], r.rid
    assert [len(r.generated) for r in reqs] == [10, 9, 4]


def test_a_follow_up_hits_blocks_given_up_at_launch(engines):
    """With the prefix cache a request that ends by length gives its slot
    up at launch, its last fed token still on the device; its blocks reach
    the tree once that token is read, and the follow-up finds them."""
    eng = engines("dense")
    sched = Scheduler(eng, prefix_cache=True)
    first, other = _requests([(30, 8, 6), (31, 5, 12)])
    sched.submit(first)
    sched.submit(other)
    while first.state != "done":
        sched.step()
    assert sched.n_ran_ahead >= 4 and other.state == "active"
    turn = Request(rid=32, prompt=first.prompt + first.generated[:5] + [7, 9],
                   max_new_tokens=5)
    _run(sched, [turn])
    # 8 + 5 tokens had K/V when the slot went: three whole blocks
    assert sched.n_prefix_hits == 1 and sched.prefix_tokens_saved == 12
    for r in (first, other, turn):
        assert r.generated == plain(eng, r.prompt, r.max_new_tokens,
                                    rid=r.rid), r.rid


@pytest.mark.parametrize("kind", KINDS)
def test_a_weight_swap_behind_preempt_all(models, kind):
    model, params = models[kind]
    other = model.init_params(jax.random.PRNGKey(1))[0]
    eng = _engine(model, params)
    sched = Scheduler(eng)
    reqs = _requests([(40, 6, 9), (41, 5, 8), (42, 7, 4)])
    for r in reqs:
        sched.submit(r)
    for _ in range(3):
        sched.step()
    # 42's last step is out: its slot went at the launch, the token is read
    # by the drain and the next step returns it
    assert sched.n_active == 2 and reqs[2].state == "active"
    assert sched.preempt_all() == 2
    held = {r.rid: list(r.generated) for r in reqs}
    assert [len(t) for t in held.values()] == [4, 4, 4]
    assert reqs[2].state == "done" and not sched.idle
    old = eng.swap_params(other)
    returned = _run(sched, [])
    assert [r.rid for r in returned][0] == 42 and len(returned) == 3
    eng.restore_params(old)
    before = {r.rid: plain(eng, r.prompt, 4, rid=r.rid) for r in reqs}
    eng.swap_params(other)
    for r in reqs:
        assert held[r.rid] == before[r.rid]
        left = r.max_new_tokens - 4
        after = plain(eng, r.prompt + held[r.rid], left, rid=r.rid) \
            if left else []
        assert r.generated == held[r.rid] + after, r.rid


def test_an_injected_fault_finds_every_launched_token_on_the_host(engines):
    eng = engines("dense")
    sched = Scheduler(eng, fault_plan=FaultPlan.parse("serve:raise@3"))
    reqs = _requests([(50, 5, 8), (51, 6, 7)])
    for r in reqs:
        sched.submit(r)
    with pytest.raises(FaultInjected):
        while True:
            sched.step()
    assert sched.n_steps == 3 and sched._unread is None
    assert [len(r.generated) for r in reqs] == [4, 4]
    _run(sched, [])
    for r in reqs:
        assert r.generated == plain(eng, r.prompt, r.max_new_tokens,
                                    rid=r.rid)


@pytest.mark.parametrize("kind", KINDS)
def test_the_ring_shows_the_next_launch_ahead_of_the_read(engines, kind):
    eng = engines(kind)
    t0 = time.perf_counter()
    sched = Scheduler(eng)
    _run(sched, _requests([(60, 5, 7), (61, 6, 5)]))
    records = [r for r in spans.snapshot() if r.t0 >= t0]
    decodes = [r for r in records if r.name == "serve.decode"]
    assert [d.tags["ran_ahead"] for d in decodes] == [0] + [1] * 5
    assert [d.tags["step"] for d in decodes] == list(
        range(eng.n_decodes - 6, eng.n_decodes))
    parts = {d.id: {c.name.rsplit(".", 1)[1]: c for c in records
                    if c.parent == d.id} for d in decodes}
    for d in decodes:
        part = parts[d.id]
        assert set(part) == {"place", "dispatch", "wait", "fetch"}
        # step n+1 is with the device before the wait for step n begins
        assert part["dispatch"].t1 <= part["wait"].t0
        # no logits on this path: at most the model's few device counters
        assert part["fetch"].tags["bytes"] <= 16
    # the counters ride one behind: the first call read no step
    counted = [t for t in ("moe_local_hits", "loop_exit_steps")
               if any(t in d.tags for d in decodes)]
    assert bool(counted) == (kind != "dense")
    assert ("loop_exit_steps" in counted) == (kind == "looped")
    for t in counted:
        assert [t in d.tags for d in decodes] == [False] + [True] * 5
    # the last launch is read with no launch of its own
    assert sum(r.name == "serve.collect" for r in records) == 1
    # a direct caller's fetch does carry the logits
    plain(eng, _prompt(60, 5), 2)
    direct = [r for r in spans.snapshot() if r.name == "serve.decode.fetch"][-1]
    assert direct.tags["bytes"] >= SLOTS * VOCAB * 4


_PREFILL_PARTS = ("place", "dispatch", "drain", "wait", "fetch")


def _prefills(records):
    """-> [(a ``serve.prefill`` record, its children by their last name)]
    in the order the calls were made."""
    out = []
    for r in records:
        if r.name == "serve.prefill":
            kids = [c for c in records if c.parent == r.id and not c.instant]
            assert all(c.name.startswith("serve.prefill.") for c in kids)
            out.append((r, {c.name.rsplit(".", 1)[1]: c for c in kids}))
    return sorted(out, key=lambda pair: pair[0].t0)


def _says_a_launch_is_running(eng, monkeypatch):
    """A tiny step on the CPU may be over before the host comes back: the
    engine's one readiness query says "still running" while a launch is
    unread, which is what a chip's step of tens of ms answers."""
    monkeypatch.setattr(eng, "_step_running",
                        lambda: eng._unread is not None, raising=True)


@pytest.mark.parametrize("kind", KINDS)
def test_the_prefill_call_from_inside(engines, kind, monkeypatch):
    """ISSUE 35: ``serve.prefill`` > place, dispatch, wait, fetch on every
    call, in that order and covering it; a drain between dispatch and wait
    exactly where the prefill went out behind an unread launch — not on a
    first admission, not after ``collect``; the tokens served are still the
    synchronous loop's."""
    eng = engines(kind)
    _says_a_launch_is_running(eng, monkeypatch)
    t0 = time.perf_counter()
    sched = Scheduler(eng)
    reqs = _requests([(90, 5, 6), (91, 6, 6), (92, 3, 4)])
    sched.submit(reqs[0])
    sched.submit(reqs[1])
    sched.step()                 # both admitted: nothing is out yet
    sched.submit(reqs[2])
    sched.step()                 # behind the first step's launch
    assert sched.preempt_all() == 3 and eng._unread is None  # collected
    _run(sched, [])              # all three again, from an empty pipeline
    calls = _prefills([r for r in spans.snapshot() if r.t0 >= t0])
    assert [p.tags["request"] for p, _ in calls] == [90, 91, 92, 92, 91, 90]
    assert ["drain" in parts for _, parts in calls] == [
        False, False, True, False, False, False]
    for whole, parts in calls:
        assert set(parts) - {"drain"} == set(_PREFILL_PARTS) - {"drain"}
        order = [parts[n] for n in _PREFILL_PARTS if n in parts]
        assert whole.t0 <= order[0].t0 and order[-1].t1 <= whole.t1
        assert all(a.t1 <= b.t0 for a, b in zip(order, order[1:]))
        assert parts["fetch"].tags["bytes"] == 4 * VOCAB  # [V] float32
        assert whole.tags["tokens"] == (whole.tags["prompt"]
                                        - whole.tags["prefix_len"])
        assert whole.tags["tokens"] <= whole.tags["bucket"] == eng.pad_len(
            whole.tags["tokens"])
    # the parts account for the call: what is left is a few spans' own cost
    shares = sorted(sum(c.dur for c in parts.values()) / whole.dur
                    for whole, parts in calls)
    assert 0.8 <= shares[len(shares) // 2] <= 1.0
    for r in reqs:
        assert r.generated == plain(eng, r.prompt, r.max_new_tokens,
                                    rid=r.rid), r.rid


def test_a_prefix_hits_suffix_prefill_has_the_same_parts(engines,
                                                         monkeypatch):
    eng = engines("dense")
    _says_a_launch_is_running(eng, monkeypatch)
    sched = Scheduler(eng, prefix_cache=True)
    first, other = _requests([(93, 8, 6), (94, 5, 14)])
    sched.submit(first)
    sched.submit(other)
    while first.state != "done":
        sched.step()
    t0 = time.perf_counter()
    turn = Request(rid=95, prompt=first.prompt + first.generated[:5] + [7, 9],
                   max_new_tokens=3)
    sched.submit(turn)
    sched.step()
    assert sched.n_prefix_hits == 1 and other.state == "active"
    ((whole, parts),) = _prefills([r for r in spans.snapshot() if r.t0 >= t0])
    assert whole.tags["prefix_len"] == 12 and whole.tags["tokens"] == 3
    assert whole.tags["bucket"] == 4
    assert set(parts) == set(_PREFILL_PARTS)  # behind ``other``'s launch
    _run(sched, [])
    assert turn.generated == plain(eng, turn.prompt, 3, rid=95)


@pytest.mark.parametrize("kind", KINDS)
def test_a_launch_says_whether_it_found_the_device_idle(engines, kind,
                                                        monkeypatch):
    """``starved``: 1 on a launch into an empty pipeline and — asked of
    the device itself — on the launch after a prefill, which is synchronous
    and queued behind the step before it; 0 on a launch that ran ahead of a
    step still running."""
    eng = engines(kind)
    work = [(96, 5, 5), (97, 4, 8)]

    def serve():
        t0 = time.perf_counter()
        sched = Scheduler(eng)
        reqs = _requests(work)
        sched.submit(reqs[0])
        for _ in range(3):
            sched.step()
        sched.submit(reqs[1])    # its prefill lies in the fourth step
        _run(sched, [])
        return [d.tags for d in _decode_spans(t0)]

    tags = serve()
    assert [t["ran_ahead"] for t in tags] == [0] + [1] * (len(tags) - 1)
    assert tags[0]["starved"] == 1 and tags[3]["starved"] == 1
    assert {t["starved"] for t in tags} <= {0, 1}
    # a direct caller reads its own launch: every call finds nothing queued
    t0 = time.perf_counter()
    plain(eng, _prompt(96, 5), 3)
    assert [d.tags["starved"] for d in _decode_spans(t0)] == [1, 1]
    _says_a_launch_is_running(eng, monkeypatch)
    tags = serve()
    assert [t["starved"] for t in tags] == [1] + [0] * (len(tags) - 1)


@pytest.mark.parametrize("kind", KINDS)
def test_one_decode_program_serves_both_kinds_of_call(models, kind):
    t0 = time.perf_counter()
    eng = _engine(*models[kind])
    _run(Scheduler(eng), _requests([(70, 5, 6), (71, 4, 4)]))
    plain(eng, _prompt(70, 5), 4, rid=70)
    built = [r.tags for r in spans.snapshot()
             if r.name == "jit.build" and r.t0 >= t0
             and "_decode_impl" in r.tags["fn"] and not r.tags.get("nested")]
    assert [t["phase"] for t in built if t["phase"] != "cache_load"] == [
        "trace", "lower", "compile_or_load"]


def test_a_launch_is_read_by_whoever_left_it_unread(engines):
    """A loop abandoned with a launch out leaves nothing behind: a direct
    call reads its own launch, a new scheduler starts its own pipeline."""
    eng = engines("dense")
    left = Scheduler(eng)
    for r in _requests([(80, 5, 9)]):
        left.submit(r)
    left.step()
    left.step()
    assert left._unread is not None and eng._unread is not None
    want = plain(eng, _prompt(81, 6), 7, rid=81)  # direct calls: their own
    assert eng._unread is None
    left = Scheduler(eng)
    for r in _requests([(80, 5, 9)]):
        left.submit(r)
    left.step()
    left.step()
    fresh = Scheduler(eng)
    reqs = _requests([(81, 6, 7)])
    _run(fresh, reqs)
    assert reqs[0].generated == want
    with pytest.raises(ValueError, match="no launch of this caller"):
        eng.decode(np.zeros((SLOTS, eng.max_blocks_per_seq), np.int32),
                   np.array([5, 0, 0], np.int32), np.array([-1, 0, 0]),
                   np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32))
