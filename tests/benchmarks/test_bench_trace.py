"""The reduction from trace rows to numbers: by hand on a made-up trace
whose answers are plain, and on a small trace recorded on four v5e chips
(two chips' rows of six BSP steps, cut from ``cgpt13.train.bsp4``)."""

import gzip
import json
import os

import pytest

from benchmarks import trace as T

OPS, ASYNC, MODS = T.OPS_LINE, T.ASYNC_LINE, T.MODULES_LINE


def test_op_name_reads_real_hlo_text():
    real = [
        ("%while.4 = (s32[]{:T(128)}, f32[2048,50257]{0,1:T(8,128)}, f32[50257]{0:T(1024)}, "
         "bf16[4,1335,2048]{2,1,0:T(8,128)(2,1)}) while(%tuple.3), condition=%cond, body=%body",
         ("while.4", "while")),
        ("%_decode_impl.2 = bf16[8,16,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[8,128]{1,0:T(8,128)} %p)",
         ("_decode_impl.2", "custom-call")),
        ("%all-reduce-start.3 = (f32[2048,2048]{1,0:T(8,128)}, f32[2048,2048]{1,0:T(8,128)}) "
         "all-reduce-start(f32[2048,2048]{1,0:T(8,128)} %g)", ("all-reduce-start.3", "all-reduce-start")),
        ("%convolution_add_fusion.7 = f32[2048,50257]{0,1:T(8,128)} fusion(f32[2048,50257]{0,1:T(8,128)} %x)",
         ("convolution_add_fusion.7", "fusion")),
    ]
    for text, want in real:
        assert T.op_name(text) == want
    assert T.label("_decode_impl.2", "custom-call") == "custom-call/_decode_impl"
    assert T.label("fusion.310", "fusion") == "fusion"


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.length(T.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.subtract([(1, 2)], []) == [(1, 2)]


def _made_up():
    """Two chips, three runs of program ``step`` each 100 ns long from
    t = 0, 100, 200.  Per run: a fusion 0-40, a kernel 40-60, an all-reduce
    60-90 of which 70-80 is under a compute op; then 10 ns idle."""
    dev, host = [], []
    for chip in (0, 1):
        for r in range(3):
            t = 100.0 * r
            dev.append([chip, MODS, "step(123)", t, 90.0])
            dev.append([chip, OPS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", t, 40.0])
            dev.append([chip, OPS, "%flash.2 = f32[8]{0} custom-call(f32[8]{0} %a)", t + 40, 20.0])
            dev.append([chip, OPS, "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %g)", t + 60, 30.0])
            dev.append([chip, OPS, "%add.4 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)", t + 70, 10.0])
            # a container spanning its body is not work of its own
            dev.append([chip, OPS, "%while.5 = (s32[]) while((s32[]) %t), body=%b", t, 60.0])
    host.append(["train_iter", 85.0, 20.0])      # covers the gap 90-100
    host.append(["fence", 150.0, 100.0])         # covers the gap 190-200
    host.append(["train_iter", 0.0, 300.0])      # the outer span: innermost wins
    return {"device": dev, "host": host}


def test_reduce_by_hand():
    r = T.reduce(_made_up(), 2, ("train_iter", "fence"), main_module="step")
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(290e-9)
    assert r["busy_s"] == pytest.approx(270e-9)            # 3 x 90 per chip
    assert r["collective_s"] == pytest.approx(90e-9)
    assert r["exposed_collective_s"] == pytest.approx(60e-9)   # 30 - 10 under add
    assert r["kernel_s"] == pytest.approx(60e-9)
    ops = dict(r["device_ops"])
    assert "while" not in ops and ops["fusion"] == pytest.approx(120e-9)
    assert ops["custom-call/flash"] == pytest.approx(60e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps == {"train_iter": pytest.approx(10e-9), "fence": pytest.approx(10e-9)}
    # per whole run: the first and last run of each chip are dropped
    per = r["per_run"]
    assert per["runs"] == 1 and per["run_s"] == pytest.approx(90e-9)
    assert per["kernel_s"] == pytest.approx(20e-9)
    assert per["collective_s"] == pytest.approx(30e-9)
    assert per["exposed_collective_s"] == pytest.approx(20e-9)


def test_a_trace_without_device_events_is_refused():
    with pytest.raises(ValueError):
        T.reduce({"device": [], "host": []}, 1)


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(os.path.dirname(__file__), "trace_cgpt13.train.bsp4.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_reduce_on_the_recorded_trace(recorded):
    r = T.reduce(recorded, 4, ("train_iter", "data.fetch", "fence"),
                 main_module="jit_local_step")
    assert r["chips"] == 2                      # the cut keeps two chips' rows
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] / r["window_s"] > 0.9    # steps run back to back
    per = r["per_run"]
    assert per["runs"] == 4                     # six runs cut, outer two dropped
    assert 0 < per["exposed_collective_s"] <= per["collective_s"] < per["run_s"]
    assert 0 < per["kernel_s"] < per["busy_s"] <= per["run_s"] * 1.001
    names = [n for n, _ in r["device_ops"]]
    assert any(n.startswith("custom-call/") for n in names)
    assert any(n.startswith("all-reduce") for n in names)
    assert not any(n.startswith("while") for n in names)
