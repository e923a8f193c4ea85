"""The architecture cell's files and driver (``serve_arch`` + the
``nemotron_h`` adapter): the configuration against the harness's check,
the traffic against the cache, the adapter's counts of work against hand
counts, the two readers this cell brought, and a rehearsal of the whole
run at a tiny size through the real entries on the CPU."""

import copy
import json
import os
import types

import numpy as np
import pytest

from arch_tiny import TINY_LIMIT, WORKLOAD, rehearse, tiny_cell
from bench_tiny import failed_names, well_formed

from benchmarks import run as bench_run
from benchmarks.arch import nemotron_h as arch
from benchmarks.common import ROOT, import_generator
from benchmarks.readers import hbm_rate, span_tags


@pytest.fixture(scope="module")
def loaded():
    return bench_run.load_cell(WORKLOAD)


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return next(c for c in bench["configs"] if c["name"] == "nemotron3-super-ep4")


def test_the_configuration_states_every_published_width(loaded):
    cfg = loaded["cfg"]
    bench_run.check_config(cfg, _entry())
    arch.check_sizes(cfg)
    widths = dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=2,
                  head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
                  ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
                  moe_latent_size=1024, moe_intermediate_size=2688,
                  moe_shared_expert_intermediate_size=5376, router_experts=512,
                  num_experts_per_tok=22, routed_scaling_factor=5)
    for key, value in widths.items():
        assert cfg[key] == value, key
        assert key not in cfg["reduced"]
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_rows_held",
                              "num_nextn_predict_layers"]
    # the cut is the model's own first period, at the published ratio
    published = cfg["published"]["hybrid_override_pattern"]
    assert published.startswith(cfg["hybrid_override_pattern"])
    assert (published.count("M"), published.count("E"), published.count("*")) \
        == (40, 40, 8)
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] == [5, 5, 1]
    assert "4 chips share each layer" in cfg["deployment"]
    assert "8 stages of 4 chips" in cfg["deployment"]


@pytest.mark.parametrize("key,value", [("hidden_size", 2048), ("head_dim", 64),
                                       ("moe_latent_size", 512),
                                       ("num_experts_per_tok", 8),
                                       ("ssm_state_size", 64)])
def test_a_changed_width_is_refused(loaded, key, value):
    with pytest.raises(SystemExit):
        bench_run.check_config(dict(loaded["cfg"], **{key: value}), _entry())


def test_the_traffic_fits_the_cache_and_draws_held_rows(loaded):
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    run = cfg["run"]
    requests = import_generator(traffic).generate(
        traffic, 2**31 + 9, vocab=arch.vocab(cfg), max_batch=run["max_batch"])
    assert len(requests) == len(traffic["pairs"]) + run["max_batch"]
    for r in requests:
        assert len(r["prompt"]) + r["max_new_tokens"] <= run["max_context"]
        assert max(r["prompt"]) < cfg["vocab_rows_held"]
    for p, o in traffic["pairs"]:
        assert 64 <= p <= 2048 and 128 <= o <= 2048
    # every slot can hold a whole context: the pool never preempts
    per_seq = -(-run["max_context"] // run["block_size"])
    assert run["num_blocks"] >= run["max_batch"] * per_seq + 1
    # more answers than the window and the lead-in could finish at the floor
    floor_s = arch.decode_bytes(cfg, 128 * 1024, 128) / 819e9
    assert sum(o for _, o in traffic["pairs"]) > 1.2 * 44 / floor_s * 128


def test_work_counts_against_hand_counts(loaded):
    cfg = loaded["cfg"]
    w = arch.weight_bytes(cfg)
    assert w["mamba"] == 2 * (4096 * (8192 + 10240 + 128) + 8192 * 4096
                              + 4 * 10240 + 10240 + 3 * 128 + 8192 + 4096)
    assert w["attn"] == 2 * (4096 * (4096 + 256 + 256) + 4096 * 4096 + 4096)
    assert w["moe"] == 2 * (128 * 2 * 1024 * 2688 + 4096 * 512 + 512
                            + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096)
    assert w["top"] == 2 * (2 * 32768 * 4096 + 4096)
    held = 5 * w["mamba"] + 5 * w["moe"] + w["attn"] + w["top"]
    assert 9.29e9 < held < 9.31e9                        # the issue's 9.30 GB
    state = 5 * (4 * 128 * 64 * 128 + 2 * 3 * 10240)
    assert arch.state_bytes_per_slot(cfg) == state       # 21.3 MB a slot
    tokens, slots = 128 * 1500, 128
    want = (held - 2 * 32768 * 4096 + 2 * slots * 4096   # embedding: rows read
            + 2 * slots * state                          # state in and out
            + 1024 * (tokens + slots)                    # K/V: 1 KiB a token
            + 4 * slots * 32768)                         # float32 logits
    assert arch.decode_bytes(cfg, tokens, slots) == want
    assert 17.0e-3 < want / 819e9 < 18.5e-3              # the 17.7 ms floor
    assert arch.expected_local_hits(cfg) == 5.5
    per_token = (
        5 * (2 * 4096 * 18560 + 2 * 8192 * 4096 + 2 * 4 * 10240
             + 5 * 8192 * 128)
        + 2 * 4096 * 4608 + 2 * 4096 * 4096
        + 5 * (2 * 4096 * 512 + 4 * 4096 * 1024 + 4 * 4096 * 5376
               + 5.5 * 4 * 1024 * 2688)
        + 2 * 4096 * 32768)
    assert arch.decode_flops(cfg, 1000) == per_token + 4 * 1000 * 4096
    assert arch.prefill_flops(cfg, 3) == (
        3 * (per_token - 2 * 4096 * 32768) + 4 * 4096 * 6 + 2 * 4096 * 32768)


def _span(name, id_, parent, t0, **tags):
    return types.SimpleNamespace(name=name, id=id_, parent=parent, t0=t0,
                                 t1=t0 + 1.0, instant=False, tags=tags)


def test_the_span_tag_reader(monkeypatch):
    from theanompi_tpu.telemetry import spans

    records = [_span("serve.step", 1, None, 0.0),
               _span("serve.decode", 2, 1, 0.1, batch=4, moe_local_hits=40,
                     moe_load_peak=3),
               _span("serve.step", 3, None, 2.0),
               _span("serve.decode", 4, 3, 2.1, batch=2, moe_local_hits=26,
                     moe_load_peak=4),
               _span("serve.step", 5, None, 4.0),
               _span("serve.decode", 6, 5, 4.1, batch=2)]  # counts nothing
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    run = {"counters": {"steps": 3, "moe_layers": 2, "experts_held": 8}}
    args = dict(root="serve.step", span="serve.decode")
    assert span_tags.read(run, num="moe_local_hits", den="batch",
                          over=["moe_layers"], **args) == 66 / 6 / 2
    assert span_tags.read(run, num="moe_load_peak", den="moe_local_hits",
                          times=["moe_layers", "experts_held"], **args) \
        == pytest.approx(7 / 66 * 16)
    # a program that tags nothing (the parent) reads nothing, and says so
    run["counters"]["steps"] = 1
    assert span_tags.read(run, num="moe_local_hits", den="batch", **args) is None


def test_the_hbm_rate_reader():
    run = {"counters": {"hbm_bytes": 819e9 * 5.0, "mfu_s": 10.0}, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert hbm_rate.read(run) == pytest.approx(50.0)
    assert hbm_rate.read({"counters": {"mfu_s": 10.0}}) is None


@pytest.fixture(scope="module")
def sound():
    from theanompi_tpu.telemetry import spans

    spans.RING.clear()  # the tests below count this rehearsal's steps alone
    return rehearse()


def test_a_tiny_run_through_the_real_driver_is_correct(sound):
    """Sound tiny runs read at most 0.0014 on the CPU (5 runs, 4 seeds:
    0.0014, 0.0011, 0, 0, 0); the fp8 control at least 0.034 (3 seeds: 0.034,
    0.037, 0.050): ``TINY_LIMIT`` 0.02 lies between."""
    well_formed(sound, "serve_tokens_per_s")
    # the traffic file keeps the tail out of this cell's end-to-end metrics
    assert set(sound["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert sound["correct"] is True, sound["compared"]
    assert sound["extra"]["tokens_compared"] > 20


def test_the_new_metrics_read_the_tiny_runs_counters(sound):
    """What ``--trace 1`` would report from the program's ring and the
    run's counters, minus the device trace."""
    loaded = tiny_cell()
    from theanompi_tpu.telemetry import spans

    steps = len([r for r in spans.snapshot() if r.name == "serve.step"])
    cfg = loaded["cfg"]
    run = {"counters": {"steps": min(steps, sound["extra"]["steps"]),
                        "moe_layers": 2, "experts_held": 8}}
    hits = span_tags.read(run, root="serve.step", span="serve.decode",
                          num="moe_local_hits", den="batch", over=["moe_layers"])
    # 8 of 32 experts held, top-8: 2 a token under a uniform router
    assert arch.expected_local_hits(cfg) == 2.0
    assert 1.0 < hits < 3.0
    skew = span_tags.read(run, root="serve.step", span="serve.decode",
                          num="moe_load_peak", den="moe_local_hits",
                          times=["moe_layers", "experts_held"])
    assert skew >= 1.0


def test_a_traffic_file_without_the_key_reports_both_end_to_end_metrics():
    loaded = tiny_cell()
    assert loaded["traffic"].pop("end_to_end") == ["serve_tokens_per_s"]
    # no cell reports the tail end to end: a benchmark that did lists it
    loaded["bench"]["end_to_end"].append(
        {"name": "tpot_ms_p90", "unit": "ms", "better": "lower", "bound": 0.04,
         "source": "host_clock"})
    import jax

    line = bench_run.execute(loaded, WORKLOAD, seed=6, seconds=1.5, trace=0,
                             devices=jax.devices()[:1])
    assert line["metrics"]["tpot_ms_p90"]["value"] > 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_fp8_control_is_not_correct():
    """Over a prompt and the reference's own greedy tokens the served gap is
    0; the token the fp8 control puts first lies beyond the limit."""
    from benchmarks.arch import nemotron_h_reference as ref

    cfg = tiny_cell()["cfg"]
    rng = np.random.Generator(np.random.PCG64(3))
    sample = []
    for _ in range(3):
        prompt = rng.integers(0, cfg["vocab_rows_held"], size=12).tolist()
        toks = list(prompt)
        for _ in range(20):
            padded = np.zeros((1, cfg["run"]["max_context"]), np.int32)
            padded[0, :len(toks)] = toks
            toks.append(int(np.argmax(ref.logits(cfg, 11, padded)[0, len(toks) - 1])))
        sample.append((prompt, toks[len(prompt):]))
    assert arch.served_gaps(cfg, 11, sample)["widest_logit_gap"] == 0.0
    control = arch.served_gaps(cfg, 11, sample, control=True)
    assert control["widest_logit_gap"] > TINY_LIMIT, control


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from theanompi_tpu.serving.engine import InferenceEngine

    real = InferenceEngine.decode

    def altered(self, tables, lengths, tokens, temps, rids):
        nxt, logits = real(self, tables, lengths, tokens, temps, rids)
        self._n_altered = getattr(self, "_n_altered", 0) + 1
        if self._n_altered % 4 == 0:
            nxt = (np.array(nxt) + 1) % self.model.data.vocab
        return nxt, logits
    monkeypatch.setattr(InferenceEngine, "decode", altered)
    line = rehearse()
    assert line["correct"] is False
    assert failed_names(line) == ["widest_logit_gap"]


def test_a_request_that_outgrows_the_cache_is_refused_before_the_run():
    import jax

    loaded = copy.deepcopy(tiny_cell())
    loaded["traffic"]["pairs"][0] = [40, 40]
    with pytest.raises(ValueError, match="max_context"):
        bench_run.execute(loaded, WORKLOAD, seed=5, seconds=1.0, trace=0,
                          devices=jax.devices()[:1])
