"""The looped-stack cell's files and driver (``serve_arch`` + the ``ouro``
adapter): the configuration against the harness's check and the catalog's
numbers, the traffic against the cache, the adapter's counts of work
against hand counts, the reader this cell brought, and a rehearsal of the
whole run at a tiny size through the real entries on the CPU."""

import json
import os
import types

import numpy as np
import pytest

from bench_tiny import failed_names, well_formed
from ouro_tiny import TINY_LIMIT, WORKLOAD, rehearse, tiny_cell

from benchmarks import run as bench_run
from benchmarks.arch import ouro as arch
from benchmarks.arch import ouro_reference as ref
from benchmarks.common import ROOT, import_generator
from benchmarks.readers import roofline_arch


@pytest.fixture(scope="module")
def loaded():
    return bench_run.load_cell(WORKLOAD)


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")


def test_the_configuration_is_the_whole_published_model(loaded):
    cfg = loaded["cfg"]
    bench_run.check_config(cfg, _entry())
    arch.check_sizes(cfg)
    published = dict(
        hidden_size=2048, intermediate_size=5632, num_hidden_layers=48,
        num_attention_heads=16, num_key_value_heads=16, head_dim=128,
        rope_theta=1000000, rms_norm_eps=1e-6, total_ut_steps=4,
        early_exit_threshold=1, vocab_size=49152, max_position_embeddings=65536,
        tie_word_embeddings=False, hidden_act="silu", sliding_window=None,
        model_type="ouro")
    for key, value in published.items():
        assert cfg[key] == value == cfg["published"][key], key
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert cfg["reduced"] == [] == _entry()["reduced"]      # nothing is cut
    assert "one chip holds the whole model" in cfg["deployment"]
    for item in ("bias", "norms", "final norm", "exit gate", "cache index",
                 "initialisation"):
        assert item in cfg["assumed"], item
    assert cfg["run"] == dict(precision="bf16", weights="bf16", max_batch=16,
                              max_context=768, block_size=16, num_blocks=321)
    # the model's name: 48 x 51 388 416 + 2 x 49152 x 2048 + 4097
    assert ref.parameter_count(cfg) == 2_667_974_657
    model = arch.model_config(cfg)
    assert model["pattern"] == "*-" * 48 and model["loops"] == 4
    assert model["post_norm"] and model["rope_theta"] == 1e6


@pytest.mark.parametrize("key,value", [("hidden_size", 1024), ("head_dim", 64),
                                       ("intermediate_size", 4096),
                                       ("total_ut_steps", 2),
                                       ("num_hidden_layers", 24)])
def test_a_changed_size_is_refused(loaded, key, value):
    with pytest.raises(SystemExit):
        bench_run.check_config(dict(loaded["cfg"], **{key: value}), _entry())


def test_the_program_model_has_the_models_parameters_and_cache(loaded):
    """Shapes only (nothing of 2.7 B parameters is made): the program's
    tree counts the model's name, and its cache 1 572 864 bytes a token."""
    import jax
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = loaded["cfg"]
    model = HybridLM(arch.model_config(cfg))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 2_667_974_657
    kv = model.cache_spec()["kv"]
    assert kv == {"layers": 192, "heads": 16, "head_dim": 128}
    assert 2 * kv["layers"] * kv["heads"] * kv["head_dim"] * 2 == 1_572_864 \
        == arch.kv_bytes_per_token(cfg)


def test_the_traffic_fits_the_cache_and_leaves_the_pool_room(loaded):
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    run = cfg["run"]
    assert len(traffic["pairs"]) == 512
    requests = import_generator(traffic).generate(
        traffic, 2**31 + 9, vocab=arch.vocab(cfg), max_batch=run["max_batch"])
    assert len(requests) == 512 + run["max_batch"]
    for r in requests:
        assert len(r["prompt"]) + r["max_new_tokens"] <= 704 <= run["max_context"]
        assert max(r["prompt"]) < cfg["vocab_size"]
    for p, o in traffic["pairs"]:
        assert 16 <= p <= 256 and 48 <= o <= 448
    assert 79 < np.mean([p for p, _ in traffic["pairs"]]) < 81
    assert 185 < np.mean([o for _, o in traffic["pairs"]]) < 187
    # the pool is NOT sized for the worst case: admission is by tokens
    pool = run["num_blocks"] - 1
    assert pool < run["max_batch"] * -(-704 // run["block_size"])
    # one simulated pass from the generator's stationary start: a token a
    # slot a step, a freed slot taking the next request
    bs, queue = run["block_size"], list(requests)
    active = [[len(r["prompt"]), r["max_new_tokens"]]
              for r in queue[:run["max_batch"]]]
    nxt, used = run["max_batch"], []
    while nxt < len(queue):
        used.append(sum(-(-(n + 1) // bs) for n, _ in active))
        for a in active:
            a[0], a[1] = a[0] + 1, a[1] - 1
            if a[1] <= 0 and nxt < len(queue):
                a[:] = [len(queue[nxt]["prompt"]), queue[nxt]["max_new_tokens"]]
                nxt += 1
    assert np.mean(used) < 0.85 * pool and max(used) < pool
    assert 0.55 * pool < np.mean(used)        # and it is not idle either
    # four times the answers the lead-in and the window finish at the floor
    floor_s = arch.decode_bytes(cfg, 16 * 205, 16) / 819e9
    assert sum(o for _, o in traffic["pairs"]) > 3.5 * 44 / floor_s * 16


def test_work_counts_against_hand_counts(loaded):
    cfg = loaded["cfg"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert arch.layer_params(cfg) == layer == 51_388_416
    weights = 2 * (4 * (48 * layer + 2048 + 2049) + 2048 * 49152)
    assert arch.step_weight_bytes(cfg) == weights
    assert 19.92e9 < weights < 19.94e9                   # the issue's 19.93 GB
    tokens, slots = 3300, 16
    want = (weights + 2 * slots * 2048                   # embedding rows read
            + 1_572_864 * (tokens + slots)               # K/V read and written
            + 4 * slots * 49152)                         # float32 logits
    assert arch.decode_bytes(cfg, tokens, slots) == want
    assert 30e-3 < want / 819e9 < 31.5e-3                # the 31 ms floor
    assert arch.paged_decode_bytes(cfg, tokens, slots) == (
        1_572_864 * tokens + 192 * 2 * slots * 2048 * 2)
    assert arch.paged_decode_flops(cfg, tokens) == 192 * 4 * tokens * 2048
    per_token = (192 * 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
                 + 4 * 2 * 2048 + 2 * 2048 * 49152)
    assert arch.decode_flops(cfg, 200) == per_token + 192 * 4 * 200 * 2048
    assert arch.prefill_flops(cfg, 3) == (
        3 * (per_token - 2 * 2048 * 49152) + 192 * 4 * 2048 * 6
        + 2 * 2048 * 49152)


def _span(name, id_, parent, t0, **tags):
    return types.SimpleNamespace(name=name, id=id_, parent=parent, t0=t0,
                                 t1=t0 + 1.0, instant=False, tags=tags)


def test_the_adapter_roofline_reader(monkeypatch, loaded):
    from theanompi_tpu.telemetry import spans

    records = [_span("serve.step", 1, None, 0.0),
               _span("serve.decode", 2, 1, 0.1, batch=16, kv_tokens=3000),
               _span("serve.step", 3, None, 2.0),
               _span("serve.decode", 4, 3, 2.1, batch=16, kv_tokens=3600)]
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    cfg = loaded["cfg"]
    run = {"counters": {"steps": 2}, "cfg": cfg,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"per_run": {"kernel_s": 0.040}}}
    args = dict(roof="hbm", root="serve.step", span="serve.decode")
    least = arch.paged_decode_bytes(cfg, 3300, 16) / 819e9
    assert roofline_arch.read(run, **args) == pytest.approx(100 * least / 0.040)
    assert 15 < roofline_arch.read(run, **args) < 17
    with pytest.raises(ValueError, match="binds"):
        roofline_arch.read(run, **dict(args, roof="compute"))
    # a program that tags no kv_tokens (the parent), and an untraced run,
    # read nothing
    for r in records:
        r.tags.pop("kv_tokens", None)
    assert roofline_arch.read(run, **args) is None
    assert roofline_arch.read(dict(run, trace=None), **args) is None


@pytest.fixture(scope="module")
def sound():
    from theanompi_tpu.telemetry import spans

    spans.RING.clear()  # the tests below count this rehearsal's steps alone
    return rehearse()


def test_a_tiny_run_through_the_real_driver_is_correct(sound):
    """Sound tiny runs read at most 0.0016 on the CPU and the fp8 control
    at least 0.032 (5 seeds each, beside ``TINY_LIMIT`` in ``ouro_tiny.py``)."""
    well_formed(sound, "serve_tokens_per_s")
    assert set(sound["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert sound["correct"] is True, sound["compared"]
    assert sound["extra"]["tokens_compared"] > 20


def test_the_new_metrics_read_the_tiny_runs_ring(sound):
    """What ``--trace 1`` would report from the program's ring, minus the
    device trace: every token reads the last of the three steps."""
    from benchmarks.readers import span_tags
    from theanompi_tpu.telemetry import spans

    steps = len([r for r in spans.snapshot() if r.name == "serve.step"])
    run = {"counters": {"steps": min(steps, sound["extra"]["steps"])}}
    assert span_tags.read(run, root="serve.step", span="serve.decode",
                          num="loop_exit_steps", den="batch") == 3.0


def test_the_fp8_control_is_not_correct():
    """Over a prompt and the reference's own greedy tokens the served gap is
    0; the token the fp8 control puts first lies beyond the limit."""
    cfg = tiny_cell()["cfg"]
    rng = np.random.Generator(np.random.PCG64(3))
    sample = []
    for _ in range(3):
        prompt = rng.integers(0, cfg["vocab_size"], size=12).tolist()
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
