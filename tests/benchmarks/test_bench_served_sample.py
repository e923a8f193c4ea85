"""The served check's sample (``serve_arch.served_sample``): an adapter
that defines ``served_record`` gets ``(prompt, generated, record)`` of the
same picked requests, in the main check and the fp8 control's alike; one
without it gets the ``(prompt, generated)`` pairs it always had."""

import types

import pytest

import ouro_tiny

from benchmarks import run as bench_run
from benchmarks import serve_arch
from benchmarks.arch import ouro


def _request(rid, prompt, generated):
    return types.SimpleNamespace(rid=rid, prompt=tuple(prompt),
                                 generated=list(generated))


PICKS = [_request(7, [1, 2, 3], [4, 5, 6, 7]), _request(2, [8], [9, 10]),
         _request(5, [11, 12], [13])]


def test_without_served_record_the_pairs_are_the_ones_it_always_had():
    assert not hasattr(ouro, "served_record")
    sample = serve_arch.served_sample(ouro, PICKS)
    # the expression serve_arch.run built the sample with before the hook
    assert sample == [(list(r.prompt), list(r.generated)) for r in PICKS]
    assert all(type(s) is tuple and len(s) == 2 for s in sample)
    assert all(type(p) is list and type(g) is list for p, g in sample)


def test_served_record_rides_third_beside_its_own_request():
    arch = types.SimpleNamespace(served_record=lambda r: ("record", r.rid))
    sample = serve_arch.served_sample(arch, PICKS)
    assert sample == [(list(r.prompt), list(r.generated), ("record", r.rid))
                      for r in PICKS]


@pytest.fixture(scope="module")
def through_the_driver():
    """A tiny ``ouro`` rehearsal with the calibration's fp8 control on,
    through a stand-in adapter: the real one plus ``served_record``, whose
    ``served_gaps`` keeps what it was handed and checks the real one's
    pairs."""
    import jax

    from theanompi_tpu.telemetry import spans

    calls = []

    def served_gaps(cfg, seed, sample, control=False):
        calls.append((control, sample))
        return ouro.served_gaps(cfg, seed, [(p, g) for p, g, _ in sample],
                                control=control)

    stub = types.SimpleNamespace(**vars(ouro))
    stub.served_record = lambda r: {"rid": r.rid, "prompt": list(r.prompt),
                                    "generated": list(r.generated)}
    stub.served_gaps = served_gaps
    loaded = ouro_tiny.tiny_cell()
    loaded["traffic"]["calibrate_control"] = True
    spans.RING.clear()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(serve_arch, "adapter_for", lambda cfg: stub)
        line = bench_run.execute(loaded, ouro_tiny.WORKLOAD, seed=2**31 + 91,
                                 seconds=2.0, trace=0,
                                 devices=jax.devices()[:1])
    spans.RING.clear()
    return line, calls


def test_the_check_gets_triples_of_the_sampled_requests(through_the_driver):
    line, calls = through_the_driver
    assert line["correct"] is True, line["compared"]
    (control, sample), _ = calls
    assert control is False and len(sample) == 3  # check_requests
    rids = [rec["rid"] for _, _, rec in sample]
    assert len(set(rids)) == len(rids)
    for prompt, generated, rec in sample:
        assert (rec["prompt"], rec["generated"]) == (prompt, generated)
    # the longest request the window finished is the first, as before
    lengths = [len(p) + len(g) for p, g, _ in sample]
    assert lengths[0] == max(lengths)


def test_the_fp8_control_gets_the_same_shape_as_the_main_check(through_the_driver):
    line, calls = through_the_driver
    assert [c for c, _ in calls] == [False, True]
    assert calls[1][1] == calls[0][1]
    assert line["extra"]["control_fp8_widest_logit_gap"] > 0
