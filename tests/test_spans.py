"""The span ring (ISSUE 25): parenting, bound, subscribers, what it costs,
``jit.build`` instants, and the token gaps the scheduler records.

- **ring** — nesting and ``parent`` across ``with`` and ``begin``/``end``;
  threads do not parent across each other; the bound and the wrapped-ring
  signal; an exception closes the span and tags ``error``.
- **sink** — a ``Telemetry`` subscriber writes the same ``serve.*`` /
  ``train.step`` / ``recorder.*`` / ``prefetch.dequeue`` events as the
  hand-written call sites did, now with ``id`` / ``parent``.
- **cost** — 100 000 open/close pairs with no subscriber and no profiler.
- **jit.build** — a new prefill bucket's compile lies under the
  ``serve.prefill`` span that caused it.
- **token gaps** — ``Scheduler.token_ms`` holds a stall for another
  request's prefill.
"""

import statistics
import threading
import time

import numpy as np
import pytest

from theanompi_tpu.serving import InferenceEngine, Request, Scheduler, blocks_for
from theanompi_tpu.telemetry import Telemetry, read_events, sink_files, spans
from theanompi_tpu.telemetry.spans import SpanRing


# -- the ring ------------------------------------------------------------------

def test_parent_follows_nesting_across_with_and_begin_end():
    ring = SpanRing()
    with ring.span("outer", a=1) as outer:
        h = ring.begin("started")
        with ring.span("inner") as inner:
            ring.mark("tick", n=3)
        h.end(b=2)
        with ring.span("sibling") as sibling:
            pass
    recs = {r.name: r for r in ring.snapshot()}
    assert outer.parent is None
    assert recs["started"].parent == outer.id
    assert inner.parent == recs["started"].id  # opened while it was open
    assert recs["tick"].parent == inner.id and recs["tick"].instant
    assert recs["tick"].t0 == recs["tick"].t1 and recs["tick"].tags == {"n": 3}
    assert sibling.parent == outer.id  # `started` had ended by then
    assert recs["started"].tags == {"b": 2} and recs["outer"].tags == {"a": 1}
    # closed in order: a child is recorded before its parent, ids are unique
    assert [r.name for r in ring.snapshot()] == [
        "tick", "inner", "started", "sibling", "outer"]
    assert len({r.id for r in ring.snapshot()}) == 5
    assert [r.seq for r in ring.snapshot()] == list(range(5))
    assert all(r.t1 >= r.t0 for r in ring.snapshot())
    assert ring._stack() == []


def test_out_of_order_stop_and_cancel_leave_the_stack_clean():
    ring = SpanRing()
    a, b = ring.begin("a"), ring.begin("b")
    a.end()  # a start/stop pair closed before the one it encloses
    assert ring._stack() == [b]
    b.cancel()
    b.end()  # after a cancel: nothing
    assert ring._stack() == []
    assert [r.name for r in ring.snapshot()] == ["a"]
    assert a.end() == 0.0 and len(ring.snapshot()) == 1  # end is once only


def test_a_second_threads_spans_do_not_parent_under_the_main_threads():
    ring = SpanRing()
    seen = {}

    def work():
        with ring.span("worker") as w:
            with ring.span("worker.child") as c:
                pass
        seen["worker"], seen["child"] = w, c

    with ring.span("main") as m:
        t = threading.Thread(target=work, name="spans-test-worker")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert seen["worker"].parent is None
    assert seen["child"].parent == seen["worker"].id
    assert m.parent is None and len(ring.snapshot()) == 3


def test_the_ring_is_bounded_and_says_when_it_has_wrapped():
    ring = SpanRing(maxlen=8)
    for i in range(8):
        ring.mark("m", i=i)
    assert ring.dropped() == 0 and len(ring.snapshot()) == 8
    for i in range(8, 11):
        ring.mark("m", i=i)
    recs = ring.snapshot()
    assert len(recs) == 8 and ring.dropped() == 3
    assert [r.tags["i"] for r in recs] == list(range(3, 11))  # oldest first
    assert [r.seq for r in recs] == list(range(3, 11))
    ring.clear()
    assert ring.snapshot() == [] and ring.dropped() == 0
    ring.mark("again")
    assert ring.snapshot()[0].seq == 0


def test_an_exception_closes_the_span_and_tags_the_error():
    ring = SpanRing()
    with pytest.raises(ValueError):
        with ring.span("outer"):
            with ring.span("doomed"):
                raise ValueError("boom")
    doomed, outer = ring.snapshot()
    assert doomed.tags["error"] == "ValueError" == outer.tags["error"]
    with ring.span("after") as after:
        pass
    assert after.parent is None


def test_subscribers_see_every_closed_record_until_they_leave():
    ring = SpanRing()
    got = []
    ring.subscribe(got.append)
    with ring.span("a"):
        ring.mark("m")
    ring.record("late", 1.0, 2.5, k="v")
    ring.unsubscribe(got.append)
    with ring.span("unseen"):
        pass
    assert [r.name for r in got] == ["m", "a", "late"]
    assert got[2].t0 == 1.0 and got[2].t1 == 2.5 and not got[2].instant


# -- what it costs -------------------------------------------------------------

def test_open_close_cost_with_no_subscriber_and_no_profiler():
    """Expected under 3 us a pair; the ceiling is loose enough that a
    loaded six-worker test machine cannot flake it."""
    import jax  # noqa: F401  (the annotation class is picked up: the real path)

    ring = SpanRing()
    per_pair = []
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(10_000):
            with ring.span("x"):
                pass
        per_pair.append((time.perf_counter() - t0) / 10_000 * 1e6)
    median = statistics.median(per_pair)
    print(f"span open/close: median {median:.2f} us a pair "
          f"(runs {', '.join(f'{x:.2f}' for x in per_pair)})")
    assert spans._ANNOTATION is not None  # the TraceAnnotation was in the loop
    assert median < 50.0


# -- a Telemetry writes what the call sites used to ------------------------------

@pytest.fixture(scope="module")
def served_events(dense_model, tmp_path_factory):
    """A tiny serving run with a sink; -> (its events, the scheduler)."""
    from theanompi_tpu.serving import run_open_loop

    model, params, _ = dense_model
    d = str(tmp_path_factory.mktemp("tel_serve"))
    tel = Telemetry(d)
    engine = InferenceEngine(model, params, block_size=4, max_batch=2,
                             num_blocks=11, seed=0)
    # a tiny step on the CPU is over before the host comes back: say it is
    # still running while its launch is unread, as it is on the chip
    engine._step_running = lambda: engine._unread is not None
    sched = Scheduler(engine, telemetry=tel)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=5)
            for i in range(3)]
    results, _ = run_open_loop(sched, reqs)
    assert len(results) == 3
    tel.close()
    return [e for p in sink_files(d) for e in read_events(p)], sched


@pytest.fixture(scope="module")
def trained_events(tmp_path_factory):
    """A 4-step BSP run on one device with a sink; -> its events."""
    import jax

    from theanompi_tpu import BSP

    d = str(tmp_path_factory.mktemp("tel_train"))
    rule = BSP(config={"verbose": False, "telemetry_dir": d, "print_freq": 2})
    rule.init(devices=jax.devices()[:1], model_config={
        "depth": 10, "widen": 1, "batch_size": 2, "image_size": 8,
        "n_train": 8, "n_val": 2, "n_epochs": 1, "precision": "fp32",
        "augment": False, "verbose": False})
    rule.wait()
    return [e for p in sink_files(d) for e in read_events(p)]


def _spans_named(events, name):
    return [e for e in events if e["kind"] == "span" and e["name"] == name]


@pytest.mark.parametrize("name,tags,parent", [
    ("serve.step", {"step"}, None),
    ("serve.admit", {"queued"}, "serve.step"),
    ("serve.prefill", {"request", "prompt", "tokens", "bucket", "prefix_len"},
     "serve.admit"),
    ("serve.prefill.place", set(), "serve.prefill"),
    ("serve.prefill.dispatch", set(), "serve.prefill"),
    ("serve.prefill.drain", set(), "serve.prefill"),
    ("serve.prefill.wait", set(), "serve.prefill"),
    ("serve.prefill.fetch", {"bytes"}, "serve.prefill"),
    ("serve.decode", {"step", "batch", "requests", "starved"}, "serve.step"),
    ("serve.decode.place", set(), "serve.decode"),
    ("serve.decode.dispatch", set(), "serve.decode"),
    ("serve.decode.wait", set(), "serve.decode"),
    ("serve.decode.fetch", {"bytes"}, "serve.decode"),
])
def test_a_sink_gets_the_serving_spans_with_id_and_parent(served_events, name,
                                                           tags, parent):
    events, sched = served_events
    found = _spans_named(events, name)
    assert found, name
    by_id = {e["id"]: e for e in events if "id" in e}
    for e in found:
        assert {"ts", "dur", "tid", "id", "parent", "rank"} <= e.keys()
        assert tags <= e.keys(), (name, sorted(e))
        if parent is None:
            assert e["parent"] is None
        else:
            assert by_id[e["parent"]]["name"] == parent
    if name == "serve.decode":
        assert len(found) == sched.n_steps
        assert [e["step"] for e in found] == list(range(sched.n_steps))
        assert {r for e in found for r in e["requests"]} == {0, 1, 2}
        assert all(e["batch"] == len(e["requests"]) for e in found)
    if name == "serve.prefill":
        assert sorted(e["request"] for e in found) == [0, 1, 2]
        assert all(e["bucket"] == 4 and e["prompt"] == e["tokens"] == 3
                   for e in found)
    if name.startswith("serve.prefill."):
        # one a prefill; a drain only behind a launch: the third request,
        # admitted into the slot the first to end gave up
        drained = name == "serve.prefill.drain"
        assert len(found) == (1 if drained else 3)
    if name == "serve.prefill.fetch":
        vocab = sched.engine.model.config["vocab"]
        assert all(e["bytes"] == 4 * vocab for e in found)  # [V] float32
    if name == "serve.decode.fetch":
        # the scheduler's path: no logits, and a dense model counts nothing
        assert all(e["bytes"] == 0 for e in found)


def test_a_model_that_counts_experts_tags_its_decode_spans(served_events):
    """``HybridLM``'s two device counters ride on ``serve.decode``
    (``SERVE_DECODE_MOE_TAGS``); a model without expert layers adds none."""
    import jax

    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.telemetry.metrics import (
        SERVE_DECODE_MOE_TAGS,
        SERVE_MOE_PRODUCT_TAGS,
    )

    events, _ = served_events
    assert not any(t in e for name in ("serve.decode", "serve.prefill")
                   for e in _spans_named(events, name)
                   for t in SERVE_DECODE_MOE_TAGS + SERVE_MOE_PRODUCT_TAGS)
    model = HybridLM({"pattern": "ME", "dim": 32, "vocab": 61, "seq_len": 32,
                      "mamba_heads": 4, "mamba_head_dim": 16, "state_size": 8,
                      "n_groups": 2, "chunk_size": 8, "n_experts": 4, "top_k": 2,
                      "latent": 16, "expert_dim": 24, "shared_dim": 32})
    engine = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                             block_size=4, max_batch=2)
    sched = Scheduler(engine)
    sched.submit(Request(rid=5, prompt=[1, 2, 3], max_new_tokens=3))
    while not sched.idle:
        sched.step()
    ours = [r for r in spans.snapshot()
            if r.name == "serve.decode" and r.tags["requests"] == [5]]
    assert len(ours) == 2
    # the counters come with the tokens, one step late: the first launch
    # read nothing, the second read the first's (the last step's were
    # drained with no launch to ride on)
    assert not any(t in ours[0].tags for t in SERVE_DECODE_MOE_TAGS)
    # every expert held, one E layer, one slot: top_k hits
    assert ours[1].tags["moe_local_hits"] == 2
    assert ours[1].tags["moe_load_peak"] == 1
    assert ours[1].tags["ran_ahead"] == 1
    for r in ours:
        # two grouped products; off the TPU "auto" leaves them to ragged_dot
        assert [r.tags[t] for t in SERVE_MOE_PRODUCT_TAGS] == [2, 0]


def test_decode_spans_count_the_context_and_a_looped_stacks_exit_steps(
        served_events):
    """``kv_tokens`` rides on every model's ``serve.decode`` (each active
    slot's context with the token it writes); ``loop_exit_steps``
    (``SERVE_DECODE_LOOP_TAGS``) only on a looped ``HybridLM``'s."""
    import jax

    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.telemetry.metrics import SERVE_DECODE_LOOP_TAGS

    events, _ = served_events
    decodes = _spans_named(events, "serve.decode")
    assert decodes and all(e["kv_tokens"] >= 2 * e["batch"] for e in decodes)
    assert not any(t in e for e in decodes for t in SERVE_DECODE_LOOP_TAGS)
    model = HybridLM({"pattern": "*-", "dim": 32, "vocab": 61, "seq_len": 32,
                      "heads": 4, "kv_heads": 4, "head_dim": 8, "ffn_dim": 48,
                      "loops": 2, "post_norm": True, "rope_theta": 1e4})
    engine = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                             block_size=4, max_batch=2)
    sched = Scheduler(engine)
    sched.submit(Request(rid=6, prompt=[1, 2, 3], max_new_tokens=3))
    while not sched.idle:
        sched.step()
    ours = [r for r in spans.snapshot()
            if r.name == "serve.decode" and r.tags["requests"] == [6]]
    assert [r.tags["kv_tokens"] for r in ours] == [4, 5]
    # one slot, exit_threshold 1: the head read the last of the two steps
    # (read one step late: the first launch had no step to read)
    assert [r.tags.get("loop_exit_steps") for r in ours] == [None, 2]


def test_a_model_with_window_layers_tags_the_keys_each_kind_attends(
        served_events):
    """``kv_full_tokens`` and ``kv_window_tokens`` (``SERVE_DECODE_WINDOW_TAGS``)
    ride on the ``serve.decode`` of a ``HybridLM`` with a ``w`` layer: each
    active slot's context, and the same capped at the window; no other
    model's span carries them."""
    import jax

    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.telemetry.metrics import SERVE_DECODE_WINDOW_TAGS

    events, _ = served_events
    assert not any(t in e for e in _spans_named(events, "serve.decode")
                   for t in SERVE_DECODE_WINDOW_TAGS)
    model = HybridLM({"pattern": "*-w-", "dim": 32, "vocab": 61, "seq_len": 32,
                      "heads": 4, "window_heads": 8, "kv_heads": 2,
                      "head_dim": 8, "ffn_dim": 48, "window": 6,
                      "attn_gate": True, "rope_theta": 1e4, "rope_share": 0.5,
                      "window_rope_theta": 1e4})
    engine = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                             block_size=4, max_batch=2)
    sched = Scheduler(engine)
    sched.submit(Request(rid=7, prompt=[1, 2, 3, 4], max_new_tokens=5))
    sched.submit(Request(rid=8, prompt=[5, 6], max_new_tokens=5))
    while not sched.idle:
        sched.step()
    ours = [r for r in spans.snapshot()
            if r.name == "serve.decode" and r.tags["requests"] == [7, 8]]
    assert [r.tags["kv_tokens"] for r in ours] == [8, 10, 12, 14]
    assert [r.tags["kv_full_tokens"] for r in ours] == [8, 10, 12, 14]
    # contexts (5, 3), (6, 4), (7, 5), (8, 6) against a window of 6
    assert [r.tags["kv_window_tokens"] for r in ours] == [8, 10, 11, 12]
    assert engine.resolved_paths()["window_attention"] == (
        "slot ring of 6 tokens, masked grouped softmax")


def test_the_decode_parts_add_up_to_the_decode_span(served_events):
    events, _ = served_events
    parts: dict = {}
    for e in events:
        if e["kind"] == "span" and e["name"].startswith("serve.decode."):
            parts[e["parent"]] = parts.get(e["parent"], 0.0) + e["dur"]
    decodes = _spans_named(events, "serve.decode")
    assert len(parts) == len(decodes)
    for d in decodes:
        assert parts[d["id"]] <= d["dur"]
    total = sum(d["dur"] for d in decodes)
    assert sum(parts.values()) >= 0.9 * total


@pytest.mark.parametrize("name,tags,parent", [
    ("train.step", {"step", "epoch"}, None),
    ("recorder.calc", set(), "train.step"),
    ("recorder.wait", set(), ("train.step", None)),
    ("prefetch.dequeue", {"qsize"}, "recorder.wait"),
])
def test_a_sink_gets_the_training_spans_with_id_and_parent(trained_events, name,
                                                            tags, parent):
    found = _spans_named(trained_events, name)
    by_id = {e["id"]: e for e in trained_events if "id" in e}
    assert found, name
    for e in found:
        assert {"ts", "dur", "tid", "id", "parent"} <= e.keys()
        assert tags <= e.keys()
        up = by_id[e["parent"]]["name"] if e["parent"] is not None else None
        assert up in (parent if isinstance(parent, tuple) else (parent,))
    if name == "train.step":
        assert [e["step"] for e in found] == [0, 1, 2, 3]
        # the loss rides the fenced steps only (print_freq 2)
        assert [("loss" in e) for e in found] == [False, True, False, True]
    if name == "recorder.wait":
        # one around the dequeue (no parent), one inside the step
        assert {by_id[e["parent"]]["name"] if e["parent"] else None
                for e in found} == {"train.step", None}
    if name == "prefetch.dequeue":
        assert len(found) == 4  # the end of the epoch is not a batch


# -- jit.build -----------------------------------------------------------------

def test_a_new_prefill_buckets_compile_lies_under_its_prefill_span(dense_model):
    model, params, _ = dense_model
    engine = InferenceEngine(model, params, block_size=4, max_batch=2,
                             num_blocks=11, seed=0)
    spans.RING.clear()
    row = list(range(1, 1 + blocks_for(7, 4)))
    engine.prefill(row, [1, 2, 3, 4, 5, 6, 7], 0.0, rid=5)   # bucket 8: new
    engine.prefill(row, [7, 6, 5, 4, 3, 2], 0.0, rid=6)      # bucket 8 again
    recs = spans.snapshot()
    first, second = [r for r in recs if r.name == "serve.prefill"]
    assert first.tags["request"] == 5 and first.tags["bucket"] == 8
    built = [r for r in recs if r.name == spans.JIT_BUILD
             and r.tags["fn"].endswith("_prefill_impl)")]
    assert {r.tags["phase"] for r in built} >= {"lower", "compile_or_load"}
    # the jitted call is the prefill's ``.dispatch``: the build lies there
    (dispatch,) = [r for r in recs if r.name == "serve.prefill.dispatch"
                   and r.parent == first.id]
    assert all(r.parent == dispatch.id and r.instant for r in built)
    assert all(r.tags["seconds"] > 0 for r in built)
    # the program's own trace is one instant; the traces inside it (inner
    # jits, the kernel's body) say that their seconds are part of it
    traced = [r for r in recs if r.name == spans.JIT_BUILD
              and r.tags["phase"] == "trace" and r.parent == dispatch.id]
    (own,) = [r for r in traced if r.tags["fn"] == "_prefill_impl"]
    inside = [r for r in traced if r.tags.get("nested")]
    assert not own.tags.get("nested") and inside
    assert own.tags["seconds"] >= max(r.tags["seconds"] for r in inside)
    # the second call of the bucket builds nothing
    beneath = {second.id} | {r.id for r in recs if r.parent == second.id}
    assert not [r for r in recs if r.name == spans.JIT_BUILD
                and r.parent in beneath]


# -- token gaps ----------------------------------------------------------------

class _StubEngine:
    """The scheduler's surface with no XLA behind it; a prefill stalls."""

    def __init__(self, prefill_s: float, max_batch=2, block_size=4,
                 num_blocks=32, max_context=64):
        self.prefill_s = prefill_s
        self.max_batch, self.block_size = max_batch, block_size
        self.num_blocks, self.max_context = num_blocks, max_context
        self.max_blocks_per_seq = blocks_for(max_context, block_size)
        self.params_version = 0

    def prefill(self, row, tokens, temperature=0.0, rid=0, prefix_len=0):
        time.sleep(self.prefill_s)
        return 7, None

    def decode(self, tables, lengths, tokens, temps, rids):
        return np.full((self.max_batch,), 5, np.int32), None


def test_token_ms_holds_a_stall_for_another_requests_prefill():
    """Request 0 decodes alone, then request 1 arrives and its prefill
    sleeps 20 ms inside the step: request 0's next token is that much
    later, and its gap says so (the old series repeated the decode call's
    wall, well under a millisecond here)."""
    sched = Scheduler(_StubEngine(prefill_s=0.02))
    sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=12))
    for _ in range(4):
        sched.step()
    quiet = list(sched.token_ms)
    assert len(quiet) == 4
    sched.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=4))
    sched.step()  # prefill of request 1 (20 ms), then a decode of both
    stalled = sched.token_ms[len(quiet):]
    assert len(stalled) == 2  # one gap each
    # request 0 was active across the stall: its gap holds the prefill
    assert max(stalled) >= 20.0
    # request 1's first decode token comes right after its prefill's token
    assert min(stalled) < 20.0
    # every step's decode call itself took well under the stall
    assert max(sched.step_ms) < 20.0
    # gaps are per request: the first token of each opens its series
    n_tokens = sum(len(r.generated) for r in sched.slots if r is not None)
    assert len(sched.token_ms) == n_tokens - 2
