"""The prefill call from inside (ISSUE 35): the reader ``span_stat`` on a
ring built by hand, and the nine metrics over ``serve.prefill``'s children
and the ``starved`` tag after a tiny rehearsal of each serving driver on
the CPU — every one reads a value through its own declaration, the parts
account for the call, and the fill share is the requests' own."""

import importlib
import json
import os

import pytest

import arch_tiny
import bench_tiny
import laguna_tiny
import ouro_tiny

from benchmarks import run as bench_run
from benchmarks.common import ROOT, load_json
from benchmarks.readers import program_span, span_stat
from theanompi_tpu.serving import InferenceEngine
from theanompi_tpu.telemetry import spans

METRICS = (
    "engine.prefill_device_ms_p50", "engine.prefill_drain_ms_p50",
    "engine.prefill_place_ms_p50", "engine.prefill_dispatch_ms_p50",
    "engine.prefill_fetch_ms_p50", "engine.prefill_device_ms_per_step",
    "engine.prefill_fill_share", "engine.starved_launch_share",
    "engine.decode_stall_ms_in_window")
SERVING_CELLS = [bench_tiny.WORKLOADS["serve"], arch_tiny.WORKLOAD,
                 ouro_tiny.WORKLOAD, laguna_tiny.WORKLOAD]
REHEARSALS = {"bench_tiny": lambda: bench_tiny.rehearse("serve"),
              "arch_tiny": arch_tiny.rehearse, "ouro_tiny": ouro_tiny.rehearse,
              "laguna_tiny": laguna_tiny.rehearse}
PARTS = ("place", "dispatch", "drain", "wait", "fetch")


def put_in_ring(records) -> None:
    spans.RING.clear()
    spans.RING._records.extend(records)


# -- the reader, on a ring built by hand ---------------------------------------

def built_by_hand(first_seq: int = 0) -> list:
    """Four ``serve.step`` roots of 100 ms; ``x`` beneath the last three:
    10 ms, then 10 and 12 ms under one ``serve.admit``, then 50 ms."""
    rows = [(1, None, "serve.step", 0.0, 0.1), (2, 1, "x", 0.01, 0.09),
            (3, None, "serve.step", 1.0, 1.1), (4, 3, "x", 1.01, 1.02),
            (5, None, "serve.step", 2.0, 2.1), (6, 5, "serve.admit", 2.0, 2.05),
            (7, 6, "x", 2.0, 2.01), (8, 6, "x", 2.02, 2.032),
            (9, None, "serve.step", 3.0, 3.1), (10, 9, "x", 3.01, 3.06)]
    out = []
    for seq, (id_, parent, name, t0, t1) in enumerate(rows, first_seq):
        r = spans.Span(spans.RING, name, {})
        r.seq, r.id, r.parent, r.t0, r.t1 = seq, id_, parent, t0, t1
        out.append(r)
    return out


@pytest.fixture()
def by_hand():
    put_in_ring(built_by_hand())
    yield {"counters": {"steps": 3}}  # the first root lies before the window
    spans.RING.clear()


def test_span_stat_reads_its_three_statistics(by_hand):
    read = lambda stat, **kw: span_stat.read(  # noqa: E731
        by_hand, "serve.step", "x", stat, **kw)
    assert read("p50") == program_span.read(by_hand, "serve.step", "x", "p50")
    assert read("p50") == pytest.approx(11.0)
    assert read("ms_per_root") == pytest.approx((10 + 10 + 12 + 50) / 3)
    # one span passes 3 x the median of 11 ms: what it has over the median
    assert read("excess_ms", factor=3) == pytest.approx(50 - 11.0)
    assert read("excess_ms", factor=10) == 0.0
    # a root is a span of its own family
    assert span_stat.read(by_hand, "serve.step", "serve.step",
                          "ms_per_root") == pytest.approx(100.0)


def test_span_stat_reads_nothing_where_nothing_is_and_refuses_the_rest(by_hand):
    for stat in ("p50", "ms_per_root", "excess_ms"):
        assert span_stat.read(by_hand, "serve.step", "no.such.span", stat,
                              factor=3) is None
    with pytest.raises(ValueError, match="p99"):
        span_stat.read(by_hand, "serve.step", "x", "p99")
    with pytest.raises(ValueError, match="factor"):
        span_stat.read(by_hand, "serve.step", "x", "excess_ms")
    with pytest.raises(RuntimeError, match="the window had"):
        span_stat.read({"counters": {"steps": 5}}, "serve.step", "x",
                       "ms_per_root")
    put_in_ring(built_by_hand(first_seq=7))  # the ring let seven records go
    for stat in ("p50", "ms_per_root", "excess_ms"):
        with pytest.raises(RuntimeError, match="wrapped"):
            span_stat.read(by_hand, "serve.step", "x", stat, factor=3)


# -- the nine declarations -----------------------------------------------------

def test_the_nine_declarations_are_whole_and_read_the_new_spans():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    accepted = load_json("metrics", "engine.run_ahead_share.json")
    for name in METRICS:
        decl = load_json("metrics", name + ".json")
        assert decl["name"] == name
        assert decl["reader"] in ("span_stat", "span_tags")  # not the counted
        assert decl["workloads"] == SERVING_CELLS
        assert decl["moves"] == "serve_tokens_per_s"
        assert decl["layer"] == accepted["layer"]
        assert decl["better"] in ("lower", "higher")
        assert (decl["unit"], decl["source"]) in (
            ("ms", "program_span"), ("ratio", "program_counter"))
        assert decl["args"]["root"] == "serve.step"
        assert decl["args"]["span"] in decl["what"] and len(decl["what"]) > 80
        # listed, wherever in the list (test_bench_files checks the entry)
        assert name in listed


# -- after a rehearsal of each serving driver ------------------------------------

@pytest.fixture(scope="module")
def rehearsed():
    """driver -> (the readers' ``run``, the ring as the rehearsal left it).

    A tiny step on the CPU is over before the host comes back, so no
    prefill would find a decode step to wait out: the engine's one
    readiness query answers "still running" while a launch is unread, as a
    chip's step of tens of ms does."""
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(InferenceEngine, "_step_running",
                      lambda self: self._unread is not None)
        for driver, rehearse in REHEARSALS.items():
            spans.RING.clear()
            line = rehearse()
            assert line["correct"] and not line["failed"]
            out[driver] = ({"counters": {"steps": line["extra"]["steps"]}},
                           spans.snapshot())
    spans.RING.clear()
    return out


@pytest.fixture()
def ring_of(rehearsed):
    def restore(driver):
        put_in_ring(rehearsed[driver][1])
        return rehearsed[driver][0]
    yield restore
    spans.RING.clear()


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("driver", REHEARSALS)
def test_every_metric_reads_a_value_in_every_serving_driver(ring_of, driver,
                                                            metric):
    run = ring_of(driver)
    decl = load_json("metrics", metric + ".json")
    reader = importlib.import_module(f"benchmarks.readers.{decl['reader']}")
    value = reader.read(run, **decl["args"])
    assert value is not None
    if decl["unit"] == "ratio":
        assert 0.0 <= value <= 1.0
    elif metric == "engine.decode_stall_ms_in_window":
        assert 0.0 <= value < 60_000.0
    else:
        assert 0.0 < value < 60_000.0  # a span's milliseconds


@pytest.mark.parametrize("driver", REHEARSALS)
def test_the_result_line_would_hold_all_nine(ring_of, driver):
    """Through the runner's own ``per_layer``, each entry as its file
    declares it."""
    run = ring_of(driver)
    entries = [{k: load_json("metrics", name + ".json")[k]
                for k in ("name", "unit", "workloads")} for name in METRICS]
    for cell in SERVING_CELLS:
        got = bench_run.per_layer({"bench": {"per_layer": entries}}, cell, run)
        assert list(got) == list(METRICS)
        assert {got[m]["unit"] for m in METRICS} == {"ms", "ratio"}
    assert bench_run.per_layer({"bench": {"per_layer": entries}},
                               bench_tiny.WORKLOADS["train"], run) == {}


@pytest.mark.parametrize("driver", REHEARSALS)
def test_the_parts_account_for_the_prefill_call(ring_of, driver):
    run = ring_of(driver)
    records, roots = program_span.window(run, "serve.step")
    calls, children = program_span.descendants(records, roots, "serve.prefill")
    assert calls
    shares, drained = [], 0
    for call in calls:
        parts = {c.name.rsplit(".", 1)[1]: c for c in children[call.id]}
        assert set(parts) - {"drain"} == set(PARTS) - {"drain"}
        order = [parts[n] for n in PARTS if n in parts]
        assert all(a.t1 <= b.t0 for a, b in zip(order, order[1:]))
        drained += "drain" in parts
        shares.append(sum(c.t1 - c.t0 for c in order) / (call.t1 - call.t0))
    # a saturated backlog admits into a slot a step has just given up: every
    # prefill of the window went out behind a launch
    assert drained == len(calls)
    shares.sort()
    assert 0.8 <= shares[len(shares) // 2] <= 1.0
    read = lambda s, stat="p50": program_span.read(  # noqa: E731
        run, "serve.step", s, stat)
    assert read("serve.prefill", "self_p50") <= 0.2 * read("serve.prefill")
    # the device's part and the step waited out are what the whole span
    # holds beside the host's three
    assert sum(read("serve.prefill." + p) for p in PARTS) <= \
        1.25 * read("serve.prefill")


@pytest.mark.parametrize("driver", REHEARSALS)
def test_the_fill_share_is_the_requests_own(ring_of, driver):
    """``tokens`` over ``bucket``, recomputed from the prompts the window
    prefilled and the engine's documented buckets: the smallest
    power-of-two number of blocks that holds the prompt."""
    run = ring_of(driver)
    records, roots = program_span.window(run, "serve.step")
    calls, _ = program_span.descendants(records, roots, "serve.prefill")
    block = 8  # every tiny cell's ``block_size``
    tokens = buckets = 0
    for call in calls:
        assert call.tags["prefix_len"] == 0
        n_blocks = 1
        while n_blocks * block < call.tags["prompt"]:
            n_blocks *= 2
        tokens += call.tags["prompt"]
        buckets += n_blocks * block
    decl = load_json("metrics", "engine.prefill_fill_share.json")
    value = importlib.import_module("benchmarks.readers.span_tags").read(
        run, **decl["args"])
    assert value == pytest.approx(tokens / buckets) and 0.5 < value <= 1.0
