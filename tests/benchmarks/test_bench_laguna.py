"""The window-and-full-attention cell's files and driver (``serve_arch`` +
the ``laguna`` adapter): the configuration against the harness's check and
the catalog's numbers, the traffic against its stated law and the cache,
the adapter's counts of work against hand counts, the counter pair this
cell brought, and a rehearsal of the whole run at a tiny size through the
real entries on the CPU."""

import json
import os
import types
from statistics import NormalDist

import numpy as np
import pytest

from bench_tiny import failed_names, well_formed
from laguna_tiny import TINY_LIMIT, WORKLOAD, rehearse, tiny_config

from benchmarks import run as bench_run
from benchmarks.arch import laguna as arch
from benchmarks.arch import laguna_reference as ref
from benchmarks.common import ROOT, import_generator
from benchmarks.readers import span_tags


@pytest.fixture(scope="module")
def loaded():
    return bench_run.load_cell(WORKLOAD)


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return next(c for c in bench["configs"] if c["name"] == "laguna-xs2-pp8")


REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer"]


def test_the_configuration_is_the_published_model_cut_in_depth_only(loaded):
    cfg = loaded["cfg"]
    bench_run.check_config(cfg, _entry())
    arch.check_sizes(cfg)
    widths = dict(
        hidden_size=2048, intermediate_size=8192, num_attention_heads=48,
        num_key_value_heads=8, head_dim=128, num_experts=256,
        num_experts_per_tok=8, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, sliding_window=512,
        vocab_size=100352, rms_norm_eps=1e-6, max_position_embeddings=262144,
        moe_routed_scaling_factor=2.5, partial_rotary_factor=0.5, gating=True,
        tie_word_embeddings=False, attention_bias=False, model_type="laguna")
    for key, value in widths.items():
        assert cfg[key] == value == cfg["published"][key], key
    assert cfg["rope_parameters"] == cfg["published"]["rope_parameters"]
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_theta"], full["factor"], full["beta_fast"],
            full["beta_slow"], full["original_max_position_embeddings"],
            full["partial_rotary_factor"]) == (500000, 64, 64, 1, 4096, 0.5)
    assert full["attention_factor"] == 1.4158883083359672
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    # the cut: depth only, the lists' first five entries
    assert cfg["reduced"] == REDUCED == _entry()["reduced"]
    assert cfg["num_hidden_layers"] == 5 and cfg["published"]["num_hidden_layers"] == 40
    for key in REDUCED[1:]:
        assert cfg[key] == cfg["published"][key][:5], key
    assert cfg["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert "8 pipeline stages of 5 whole layers" in cfg["deployment"]
    for item in ("gating", "router", "q/k norm", "window", "shared expert",
                 "rotary", "initialisation"):
        assert item in cfg["assumed"], item
    assert cfg["departures"][0].startswith("none intended")
    assert cfg["run"] == dict(precision="bf16", weights="bf16", max_batch=32,
                              max_context=16384, block_size=16,
                              num_blocks=cfg["assumed"]["num_blocks"])
    model = arch.model_config(cfg)
    assert model["pattern"] == "*-wEwEwE*E" == cfg["hybrid_override_pattern"]
    assert (model["heads"], model["window_heads"], model["window"]) == (48, 64, 512)
    assert model["latent"] is None and model["expert_act"] == "silu_gated"
    assert model["rope_yarn"]["attention_factor"] == 1.4158883083359672
    assert model["window_rope_yarn"] is None and model["window_rope_theta"] == 1e4


def test_the_parameter_counts_are_the_models_name(loaded):
    cfg = loaded["cfg"]
    # five layers: 3 870 M parameters, 7.74 GB in bf16
    assert ref.parameter_count(cfg) == 3_869_857_792
    # all forty: the published 33.4 B, which settles the gate's form
    whole = dict(cfg, **{k: cfg["published"][k] for k in REDUCED})
    assert 33.43e9 < ref.parameter_count(whole) < 33.45e9
    experts = 39 * 256 * arch.expert_params(cfg)
    assert 31.40e9 < experts < 31.42e9


@pytest.mark.parametrize("key,value", [("hidden_size", 1024), ("head_dim", 64),
                                       ("moe_intermediate_size", 256),
                                       ("num_experts_per_tok", 4),
                                       ("sliding_window", 256),
                                       ("num_experts", 64)])
def test_a_changed_size_is_refused(loaded, key, value):
    with pytest.raises(SystemExit):
        bench_run.check_config(dict(loaded["cfg"], **{key: value}), _entry())


def test_the_program_model_has_the_models_parameters_and_cache(loaded):
    """Shapes only (nothing of 3.9 B parameters is made)."""
    import jax
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = loaded["cfg"]
    model = HybridLM(arch.model_config(cfg))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 3_869_857_792 + 4 * 256          # + the routers' zero b_corr
    assert shapes["03_moe"]["mixer"]["w1"].shape == (256, 2048, 1024)
    assert shapes["03_moe"]["mixer"]["w2"].shape == (256, 512, 2048)
    assert shapes["02_attn_w"]["mixer"]["gate"]["w"].shape == (2048, 64)
    assert shapes["00_attn"]["mixer"]["q"]["w"].shape == (2048, 48 * 128)
    spec = model.cache_spec()
    assert spec["kv"] == {"layers": 2, "heads": 8, "head_dim": 128}
    assert spec["window"] == {"layers": 3, "size": 512, "heads": 8,
                              "head_dim": 128}
    assert arch.kv_bytes_per_token(cfg) == {"full": 8192, "window": 12288}


def _quantiles(n, median, sigma, lo, hi):
    inv = NormalDist().inv_cdf
    return [int(min(hi, max(lo, round(median * np.exp(sigma * inv((i + 0.5) / n))))))
            for i in range(n)]


def test_the_traffic_is_its_stated_law_and_fits_the_cache(loaded):
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    run = cfg["run"]
    prompts = _quantiles(768, 3072, 1.0, 256, 14336)
    outputs = _quantiles(768, 256, 0.7, 64, 1536)
    perm = np.random.Generator(np.random.PCG64(traffic["pair_seed"])).permutation(768)
    assert traffic["pairs"] == [[prompts[i], outputs[int(perm[i])]]
                                for i in range(768)]
    assert traffic["order_seed"] == 20261004 and traffic["strata"] == 8
    assert (traffic["lead_in_s"], traffic["trace_seconds"],
            traffic["check_requests"]) == (4.0, 6.0, 6)
    assert traffic["end_to_end"] == ["serve_tokens_per_s"]
    requests = import_generator(traffic).generate(
        traffic, 2**31 + 9, vocab=arch.vocab(cfg), max_batch=run["max_batch"])
    assert len(requests) == 768 + 32
    for r in requests:
        assert len(r["prompt"]) + r["max_new_tokens"] <= run["max_context"]
        assert max(r["prompt"]) < cfg["vocab_size"]
    # 96 % of the prompts are past the window before they produce a token
    assert np.mean([p > 512 for p, _ in traffic["pairs"]]) > 0.95
    # one simulated pass from the generator's stationary start
    bs, queue = run["block_size"], list(requests)
    active = [[len(r["prompt"]), r["max_new_tokens"]] for r in queue[:32]]
    nxt, used, context = 32, [], []
    while nxt < len(queue):
        used.append(sum(-(-(n + 1) // bs) for n, _ in active))
        context.append(np.mean([n for n, _ in active]))
        for a in active:
            a[0], a[1] = a[0] + 1, a[1] - 1
            if a[1] <= 0 and nxt < len(queue):
                a[:] = [len(queue[nxt]["prompt"]), queue[nxt]["max_new_tokens"]]
                nxt += 1
    pool = run["num_blocks"] - 1
    assert 4500 < np.mean(context) < 4900          # 4.7 k tokens in flight
    assert max(used) < pool and 0.6 * pool < np.mean(used) < 0.85 * pool
    assert pool < 32 * run["max_context"] // bs    # not the worst case
    # the queue outlasts lead-in + window at the step's floor
    floor_s = arch.decode_bytes(cfg, 32 * 4700, 32) / 819e9
    assert len(used) * floor_s > 44


def test_work_counts_against_hand_counts(loaded):
    cfg = loaded["cfg"]
    d, hkv = 2048, 8 * 128
    attn = {h: 2 * d * (2 * h * 128 + 2 * hkv + h) for h in (48, 64)}
    expert = 3 * 2048 * 512
    sparse = 2 * d * 256 + 6 * d * 512 + 8 * 2 * expert
    per_token = 2 * attn[48] + 3 * attn[64] + 6 * d * 8192 + 4 * sparse
    head = 2 * d * 100352
    assert arch.matmul_flops_per_token(cfg) == per_token + head
    # a window layer's keys are capped at the window, a full layer's are not
    full, band = 2 * 48 * 128, 3 * 64 * 128
    assert arch.attn_flops_token(cfg, 100) == 4 * 100 * (full + band)
    assert arch.attn_flops_token(cfg, 5000) == 4 * (5000 * full + 512 * band)
    assert arch.decode_flops(cfg, 5000) == per_token + head + 4 * (
        5000 * full + 512 * band)
    p = 3000
    pairs_band = 512 * 513 / 2 + (p - 512) * 512
    assert arch.prefill_flops(cfg, p) == p * per_token + 4 * (
        p * (p + 1) / 2 * full + pairs_band * band) + head
    assert arch.prefill_flops(cfg, 300) == 300 * per_token + 4 * (
        300 * 301 / 2 * (full + band)) + head
    # the experts that receive a row, not all 256
    hit = 256 * (1 - (255 / 256) ** 256)
    assert arch.expected_experts_hit(cfg, 32) == pytest.approx(hit)
    assert 161 < hit < 163
    assert arch.expected_experts_hit(cfg, 1) == pytest.approx(
        256 * (1 - (255 / 256) ** 8))
    fixed = 3_869_857_792 - 4 * 256 * expert - 100352 * d
    weights = 2 * (fixed + 4 * hit * expert + 32 * d)
    assert arch.step_weight_bytes(cfg, 32) == pytest.approx(weights)
    tokens = 32 * 4700
    want = (weights + 8192 * tokens + 12288 * 32 * 512   # K/V read
            + (8192 + 12288) * 32 + 4 * 32 * 100352)      # K/V written, logits
    assert arch.decode_bytes(cfg, tokens, 32) == pytest.approx(want)
    assert 7.7e-3 < want / 819e9 < 7.9e-3                 # the issue's 7.8 ms
    # inside the window the cap does not bind
    assert arch.decode_bytes(cfg, 32 * 100, 32) == pytest.approx(
        weights + (8192 + 12288) * (32 * 100 + 32) + 4 * 32 * 100352)
    # the shares these feed stay under the roofs at the predicted step
    assert arch.decode_flops(cfg, 4700) * 32 / 24e-3 < 0.02 * 197e12


def _span(name, id_, parent, t0, **tags):
    return types.SimpleNamespace(name=name, id=id_, parent=parent, t0=t0,
                                 t1=t0 + 1.0, instant=False, tags=tags)


def test_the_window_keys_share_reads_the_decode_spans_two_tags(monkeypatch):
    from benchmarks.common import load_json
    from theanompi_tpu.telemetry import spans

    decl = load_json("metrics", "kv.window_keys_share_swa.json")
    assert decl["reader"] == "span_tags" and decl["workloads"] == [WORKLOAD]
    records = [_span("serve.step", 1, None, 0.0),
               _span("serve.decode", 2, 1, 0.1, batch=2, kv_tokens=5000,
                     kv_full_tokens=5000, kv_window_tokens=812),
               _span("serve.step", 3, None, 2.0),
               _span("serve.decode", 4, 3, 2.1, batch=2, kv_tokens=5002,
                     kv_full_tokens=5002, kv_window_tokens=814)]
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    run = {"counters": {"steps": 2}}
    assert span_tags.read(run, **decl["args"]) == pytest.approx(1626 / 10002)
    for r in records:  # a program without window layers (the parent) tags none
        r.tags.pop("kv_window_tokens", None)
        r.tags.pop("kv_full_tokens", None)
    assert span_tags.read(run, **decl["args"]) is None


def test_the_paged_kernel_share_reads_the_decode_spans_two_tags(monkeypatch):
    from benchmarks.common import load_json
    from theanompi_tpu.telemetry import spans

    decl = load_json("metrics", "kv.paged_kernel_share.json")
    assert decl["reader"] == "span_tags"
    assert decl["workloads"] == ["nemotron3s.serve.backlog", WORKLOAD]
    records = [_span("serve.step", 1, None, 0.0),
               _span("serve.decode", 2, 1, 0.1, batch=2, paged_layers=2,
                     paged_kernel_layers=2),
               _span("serve.step", 3, None, 2.0),
               _span("serve.decode", 4, 3, 2.1, batch=2, paged_layers=2,
                     paged_kernel_layers=2)]
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    run = {"counters": {"steps": 2}}
    assert span_tags.read(run, **decl["args"]) == 1.0
    for r in records[1::2]:  # the engine's gate sent the pool to the gather
        r.tags["paged_kernel_layers"] = 0
    assert span_tags.read(run, **decl["args"]) == 0.0
    for r in records:  # a program that tags neither reads nothing
        r.tags.pop("paged_layers", None)
        r.tags.pop("paged_kernel_layers", None)
    assert span_tags.read(run, **decl["args"]) is None


def check_this_cells_entries(bench: dict) -> None:
    """What this cell brought to ``BENCHMARK.json``, found by name and
    membership, never by position, so entries appended after it leave it
    whole: its 14 ``_swa`` metrics declared for it alone, the cell once on
    one chip over its configuration, and the rate it reports listing it."""
    mine = [m for m in bench["per_layer"] if m["name"].endswith("_swa")]
    assert len(mine) == 14 and all(m["workloads"] == [WORKLOAD] for m in mine)
    cells = [w for w in bench["workloads"] if w["name"] == WORKLOAD]
    assert len(cells) == 1 and cells[0]["chips"] == 1
    assert cells[0]["config"] == "laguna-xs2-pp8"
    assert [c["name"] for c in bench["configs"]].count("laguna-xs2-pp8") == 1
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert WORKLOAD in e2e["workloads"]


def test_every_new_metric_is_declared_for_this_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        check_this_cells_entries(json.load(f))


def test_the_tiny_configuration_passes_the_adapters_check():
    cfg = tiny_config()
    arch.check_sizes(cfg)
    assert arch.pattern(cfg) == "*-wE*E"
    with pytest.raises(ValueError, match="query heads"):
        arch.model_config(dict(cfg, num_attention_heads_per_layer=[4, 8, 2]))
    with pytest.raises(ValueError, match="letters"):
        arch.check_sizes(dict(cfg, hybrid_override_pattern="*-*E*E"))


@pytest.fixture(scope="module")
def sound():
    from theanompi_tpu.telemetry import spans

    spans.RING.clear()  # the tests below count this rehearsal's steps alone
    return rehearse()


def test_a_tiny_run_through_the_real_driver_is_correct(sound):
    """Sound tiny runs read at most 0.0014 on the CPU (5 seeds) and the fp8
    control at least 0.009 (beside ``TINY_LIMIT`` in ``laguna_tiny.py``)."""
    well_formed(sound, "serve_tokens_per_s")
    assert set(sound["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert sound["correct"] is True, sound["compared"]
    assert sound["extra"]["tokens_compared"] > 20


def test_the_new_tags_ride_on_the_tiny_runs_decode_spans(sound):
    """What ``--trace 1`` would report from the program's ring: contexts of
    26 to 57 tokens against a window of 16."""
    from theanompi_tpu.telemetry import spans

    steps = len([r for r in spans.snapshot() if r.name == "serve.step"])
    run = {"counters": {"steps": min(steps, sound["extra"]["steps"]),
                        "moe_layers": 2, "experts_held": 8}}
    share = span_tags.read(run, root="serve.step", span="serve.decode",
                           num="kv_window_tokens", den="kv_full_tokens")
    assert 0.2 < share < 0.7
    # the fixture cleared the ring: these are this rehearsal's decode spans
    decodes = [r for r in spans.snapshot()
               if r.name == "serve.decode" and "kv_full_tokens" in r.tags]
    assert decodes and all(r.tags["kv_full_tokens"] == r.tags["kv_tokens"] for r in decodes)
    assert all(r.tags["kv_window_tokens"] <= 16 * r.tags["batch"] for r in decodes)
    assert span_tags.read(run, root="serve.step", span="serve.decode",
                          num="moe_load_peak", den="moe_local_hits",
                          times=["moe_layers", "experts_held"]) >= 1.0


def _greedy_sample(cfg, seed, n=3):
    """Prompts and the reference's own greedy tokens after them."""
    rng = np.random.Generator(np.random.PCG64(3))
    sample = []
    for _ in range(n):
        prompt = rng.integers(0, cfg["vocab_size"], size=20).tolist()
        toks = list(prompt)
        for _ in range(24):
            padded = np.zeros((1, cfg["run"]["max_context"]), np.int32)
            padded[0, :len(toks)] = toks
            toks.append(int(np.argmax(ref.logits(cfg, seed, padded)[0, len(toks) - 1])))
        sample.append((prompt, toks[len(prompt):]))
    return sample


def test_the_fp8_control_and_a_dropped_term_are_not_correct(monkeypatch):
    """Over a prompt and the reference's own greedy tokens the served gap is
    0; the token the fp8 control puts first lies beyond the limit, and so
    do the greedy tokens of a model without its gate, its window, its
    shared expert or its routed scale."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    cfg = tiny_config()
    sample = _greedy_sample(cfg, 11)
    assert arch.served_gaps(cfg, 11, sample)["widest_logit_gap"] == 0.0
    control = arch.served_gaps(cfg, 11, sample, control=True)
    assert control["widest_logit_gap"] > TINY_LIMIT, control
    assert control["tokens_compared"] == 3 * 24
    for drop in ("gate", "window", "shared", "route_scale"):
        gap = arch.served_gaps(cfg, 11, sample, drop=drop)["widest_logit_gap"]
        assert gap > TINY_LIMIT, (drop, gap)


def test_only_positions_whose_routing_is_decided_are_compared(monkeypatch):
    """``check.routing_margin``: a served position counts where the float32
    reference decides every sparse layer's top-k by more than that many
    router logits; too few such positions is no comparison."""
    import jax.numpy as jnp

    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    cfg = tiny_config()
    sample = _greedy_sample(cfg, 11)
    everything = arch.served_gaps(cfg, 11, sample, control=True)
    assert everything["tokens_compared"] == everything["tokens_served"] == 72
    # the margin itself: last selected logit less first unselected, per token
    p = ref.layer_leaves(cfg, ref.seed_key(11), "sparse", 1)
    u = jnp.asarray(np.random.RandomState(0).randn(5, 64), jnp.float32)
    z = np.sort(np.asarray(ref.router_logits(p, u)), axis=-1)[:, ::-1]
    np.testing.assert_allclose(ref.routing_margin(cfg, p, u), z[:, 1] - z[:, 2],
                               rtol=1e-6)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :20] = sample[0][0]
    least = np.asarray(ref.hidden(cfg, 11, toks, margins=True)[2])
    assert least.shape == (1, 32) and (least >= 0).all()
    cfg["check"]["routing_margin"] = float(np.median(least))
    some = arch.served_gaps(cfg, 11, sample, control=True)
    assert 8 <= some["tokens_compared"] < some["tokens_served"] == 72
    assert some["widest_logit_gap"] <= everything["widest_logit_gap"]
    cfg["check"]["routing_margin"] = 10.0
    with pytest.raises(RuntimeError, match="too few"):
        arch.served_gaps(cfg, 11, sample)


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
