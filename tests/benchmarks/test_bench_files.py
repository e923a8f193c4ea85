"""BENCHMARK.json against the files it names and the contract's characters."""

import copy
import json
import os
import re

import pytest

from benchmarks.common import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_named_file_exists(bench):
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(HERE, "cells", w["name"] + ".json"))
        assert w["config"] in {c["name"] for c in bench["configs"]}
    for m in bench["per_layer"]:
        decl = os.path.join(HERE, "metrics", m["name"] + ".json")
        assert os.path.isfile(decl), decl
        with open(decl) as f:
            d = json.load(f)
        assert os.path.isfile(os.path.join(HERE, "readers", d["reader"] + ".py"))
        # the declaration and the entry say the same thing
        for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
            assert d[key] == m[key], (m["name"], key)


def check_names_units_and_lengths(bench: dict) -> None:
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in ("device_trace", "program_span",
                                       "program_counter", "host_clock")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))  # a configuration under a mix, once


def test_names_units_and_lengths(bench):
    check_names_units_and_lengths(bench)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def check_metrics_cover_every_cell(bench: dict) -> None:
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = [w["name"] for w in bench["workloads"]]
    for cell in cells:
        own = [m for m in bench["end_to_end"]
               if m["name"] != "setup_s" and cell in m.get("workloads", cells)]
        assert own, f"{cell} reports nothing but setup_s"
        layers = [m for m in bench["per_layer"] if cell in m.get("workloads", cells)]
        assert layers
        for m in layers:  # a per-layer metric's cells report what it moves
            assert cell in e2e[m["moves"]].get("workloads", cells), m["name"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_metrics_cover_every_cell(bench):
    check_metrics_cover_every_cell(bench)


def appended(bench: dict) -> dict:
    """``bench`` with what a ``model_config`` PR adds at the ends of its
    lists: a configuration, a one-chip cell on it that the serving rate
    lists, and a per-layer metric declared for that cell alone."""
    out = copy.deepcopy(bench)
    out["configs"].append(dict(out["configs"][-1], name="room-test-model",
                               file="benchmarks/configs/room-test-model.json"))
    out["workloads"].append({"name": "roomtest.serve.backlog",
                             "config": "room-test-model",
                             "traffic": "room_test.backlog", "chips": 1,
                             "why": "a cell appended after every other"})
    rate = next(m for m in out["end_to_end"] if m["name"] == "serve_tokens_per_s")
    rate["workloads"].append("roomtest.serve.backlog")
    out["per_layer"].append(dict(out["per_layer"][-1], name="sched.room_test",
                                 workloads=["roomtest.serve.backlog"]))
    return out


def test_the_benchmark_takes_a_configuration_its_cell_and_metric_by_addition():
    """Every check of ``BENCHMARK.json``'s lists holds over a copy with
    entries appended: a check that pins an entry to a position fails here
    first, before a ``model_config`` PR's appended cell meets it."""
    from test_bench_laguna import check_this_cells_entries

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = appended(json.load(f))
    assert bench["workloads"][-1]["name"] == "roomtest.serve.backlog"
    check_names_units_and_lengths(bench)
    check_metrics_cover_every_cell(bench)
    check_this_cells_entries(bench)


def test_configs_state_published_sizes_and_refuse_unlisted_changes(bench):
    from benchmarks.run import check_config

    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        check_config(cfg, c)
        for key in ("n_embd", "n_head", "n_inner", "n_positions", "vocab_size"):
            assert cfg[key] == cfg["published"][key]  # no width is cut
        assert cfg["assumed"] and cfg["departures"]
        changed = dict(cfg, n_embd=1024)
        with pytest.raises(SystemExit):
            check_config(changed, c)


def test_no_accelerator_is_an_exit_without_a_result(capsys):
    from benchmarks.run import EXIT_NO_CHIP, find_chips

    with pytest.raises(SystemExit) as e:
        find_chips(1)  # the test session runs on the CPU backend
    assert e.value.code == EXIT_NO_CHIP
    assert capsys.readouterr().out == ""
