"""The benchmark's readers of the program's span ring (ISSUE 25), after a
tiny rehearsal of each driver on the CPU: every new per-layer metric of
the cell's kind reads a value, the decode call's four parts add up to the
call, and a ring that does not hold the window is an error."""

import importlib
import json
import os

import pytest

from bench_tiny import WORKLOADS, rehearse

from benchmarks.common import HERE, ROOT, load_json
from benchmarks.readers import program_mark, program_span
from theanompi_tpu.telemetry import spans

READERS = ("program_span", "program_mark")


def new_metrics(kind: str) -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m["name"] for m in bench["per_layer"]
            if WORKLOADS[kind] in m["workloads"]
            and load_json("metrics", m["name"] + ".json")["reader"] in READERS]


@pytest.fixture(scope="module")
def runs():
    """One rehearsal of each driver, each into an emptied ring; -> kind ->
    (the readers' ``run``, the ring's records as the run left them)."""
    out = {}
    for kind in ("serve", "train"):
        spans.RING.clear()
        line = rehearse(kind)
        steps = line["extra"]["steps"] if kind == "serve" else line["attempted"]
        out[kind] = ({"counters": {"steps": steps}}, spans.snapshot())
    return out


@pytest.fixture()
def ring_of(runs):
    """Put a rehearsal's records back into the process's ring."""
    def restore(kind, records=None):
        spans.RING.clear()
        spans.RING._records.extend(
            runs[kind][1] if records is None else records)
        return runs[kind][0]
    yield restore
    spans.RING.clear()


@pytest.mark.parametrize("kind,metric", [
    (k, m) for k in ("serve", "train") for m in new_metrics(k)])
def test_every_new_metric_reads_a_value(ring_of, kind, metric):
    run = ring_of(kind)
    decl = load_json("metrics", metric + ".json")
    reader = importlib.import_module(f"benchmarks.readers.{decl['reader']}")
    value = reader.read(run, **decl["args"])
    assert value is not None and value >= 0.0
    if metric.endswith("builds_in_window"):
        assert value == 0.0  # every shape was warmed up before the window
    elif metric.endswith("build_s_setup"):
        assert value > 0.0   # the programs were built in set-up
    else:
        assert 0.0 < value < 60_000.0  # a span's milliseconds
    assert decl["args"]["root"] in decl["what"] or decl["args"].get(
        "span", decl["args"].get("name")) in decl["what"]


def test_both_kinds_have_their_metrics():
    assert len(new_metrics("serve")) == 9 and len(new_metrics("train")) == 5
    assert new_metrics("bsp4") == new_metrics("train")
    for name in new_metrics("serve") + new_metrics("train"):
        assert os.path.isfile(os.path.join(HERE, "metrics", name + ".json"))


def test_the_decode_parts_add_up_to_the_decode_span(ring_of):
    run = ring_of("serve")
    records, roots = program_span.window(run, "serve.step")
    decodes, children = program_span.descendants(records, roots, "serve.decode")
    assert len(decodes) == sum(
        1 for r in roots if any(c.name == "serve.decode"
                                for c in children.get(r.id, ())))
    names = {"serve.decode." + p for p in ("place", "dispatch", "wait", "fetch")}
    shares = []
    for d in decodes:
        parts = [c for c in children[d.id] if c.name in names]
        assert {c.name for c in parts} == names
        shares.append(sum(c.t1 - c.t0 for c in parts) / (d.t1 - d.t0))
    shares.sort()
    assert 0.95 <= shares[len(shares) // 2] <= 1.0  # per step, the median one
    whole = sum(d.t1 - d.t0 for d in decodes)
    assert sum(c.t1 - c.t0 for d in decodes for c in children[d.id]
               if c.name in names) >= 0.95 * whole
    # the span metric and its four parts, as the result line would hold them
    read = lambda s: program_span.read(run, "serve.step", s, "p50")  # noqa: E731
    assert sum(read(n) for n in names) <= 1.05 * read("serve.decode")
    # the scheduler's own time is what its children leave
    self_ms = program_span.read(run, "serve.step", "serve.step", "self_p50")
    assert 0.0 < self_ms < program_span.read(run, "serve.step", "serve.step",
                                             "p50")


def test_the_window_is_the_last_steps_roots_and_builds_split_around_it(ring_of):
    run = ring_of("train")
    records, roots = program_span.window(run, "train.step")
    every = [r for r in records if r.name == "train.step"]
    assert len(roots) == run["counters"]["steps"] < len(every)  # check + warm-up
    assert roots == every[-len(roots):]
    args = dict(root="train.step", name="jit.build")
    n_setup = program_mark.read(run, where="setup", field="count", **args)
    built = [r for r in records if r.name == "jit.build"
             and not r.tags.get("nested")]  # inner traces: the outer's seconds
    assert len(built) < sum(r.name == "jit.build" for r in records)
    after = [r for r in built if r.t0 > roots[-1].t1]  # the reference's
    assert after and n_setup == len(built) - len(after)
    assert program_mark.read(run, where="window", field="count", **args) == 0
    only = program_mark.read(run, where="setup", field="seconds",
                             phases=["compile_or_load"], **args)
    assert 0 < only < program_mark.read(run, where="setup", field="seconds",
                                        **args)
    with pytest.raises(ValueError):
        program_mark.read(run, where="later", field="count", **args)
    with pytest.raises(ValueError):
        program_span.read(run, "train.step", "train.step", "p99")


@pytest.mark.parametrize("kind,root", [("serve", "serve.step"),
                                       ("train", "train.step")])
def test_a_ring_that_does_not_hold_the_window_is_an_error(ring_of, runs, kind,
                                                          root):
    run = ring_of(kind)
    more = {"counters": {"steps": run["counters"]["steps"] + 10_000}}
    with pytest.raises(RuntimeError, match="the window had"):
        program_span.read(more, root, root, "p50")
    with pytest.raises(RuntimeError, match="the window had"):
        program_mark.read(more, root, "jit.build", "window", "count")
    # a ring that has let go of its oldest records: wrapped
    ring_of(kind, runs[kind][1][5:])
    with pytest.raises(RuntimeError, match="wrapped"):
        program_span.read(run, root, root, "p50")
    # a span the window never opened reads nothing, and raises nothing
    ring_of(kind)
    assert program_span.read(run, root, "no.such.span", "p50") is None
