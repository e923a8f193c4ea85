"""The latent-attention cell's files and harness (``serve_arch`` + the
``joyai_llm_flash`` adapter): the configuration against the harness's check
and the catalog's numbers, its arithmetic, the traffic against its stated
law and the cache, the adapter's counts of work against hand counts, the
readers of the metrics this cell brought, and a rehearsal of the whole run
at a tiny size through the real entries on the CPU."""

import json
import os
import types
from statistics import NormalDist

import numpy as np
import pytest

from bench_tiny import failed_names, well_formed
from joyflash_tiny import TINY_LIMIT, WORKLOAD, rehearse, tiny_config

from benchmarks import run as bench_run
from benchmarks.arch import joyai_llm_flash as arch
from benchmarks.arch import joyai_llm_flash_reference as ref
from benchmarks.common import ROOT, import_generator, load_json
from benchmarks.readers import kernel_per_run, kernel_roofline, span_tags

CONFIG = "joyai-llm-flash-ep4"
REDUCED = ["num_hidden_layers", "n_routed_experts", "num_nextn_predict_layers"]
METRICS = [
    "kernels.paged_decode_latent_roofline_mla",
    "kernels.paged_decode_latent_ms_per_step_mla",
    "kv.latent_kernel_share_mla", "engine.step_mfu_serve_mla",
    "engine.step_hbm_share_serve_mla", "engine.decode_step_ms_p50_mla",
    "engine.prefill_span_ms_p50_mla", "moe.load_peak_over_mean_mla",
    "kv.pool_peak_use_share_mla", "kv.preemptions_mla",
    "sched.batch_occupancy_mla", "device.idle_share_serve_mla",
    "device.peak_hbm_share_serve_mla"]


@pytest.fixture(scope="module")
def loaded():
    return bench_run.load_cell(WORKLOAD)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry():
    return next(c for c in _bench()["configs"] if c["name"] == CONFIG)


def test_the_configuration_is_the_catalogs_row_cut_in_depth_and_share(loaded):
    cfg = loaded["cfg"]
    bench_run.check_config(cfg, _entry())
    arch.check_sizes(cfg)
    widths = dict(
        hidden_size=2048, intermediate_size=7168, num_attention_heads=32,
        num_key_value_heads=32, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, qk_head_dim=192,
        v_head_dim=128, moe_intermediate_size=768, n_shared_experts=1,
        num_experts_per_tok=8, first_k_dense_replace=1, moe_layer_freq=1,
        n_group=1, topk_group=1, routed_scaling_factor=2.5,
        rope_theta=32000000, rope_interleave=True, rope_scaling=None,
        rms_norm_eps=1e-6, vocab_size=129280, max_position_embeddings=131072,
        scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
        tie_word_embeddings=False, attention_bias=False,
        model_type="joyai_llm_flash")
    for key, value in widths.items():
        assert cfg[key] == value == cfg["published"][key], key
    assert cfg["reduced"] == REDUCED == _entry()["reduced"]
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]) \
        == (5, 40)
    assert (cfg["n_routed_experts"], cfg["published"]["n_routed_experts"],
            cfg["router_experts"], cfg["experts_held"]) == (64, 256, 256,
                                                            [0, 64])
    assert (cfg["num_nextn_predict_layers"],
            cfg["published"]["num_nextn_predict_layers"]) == (0, 1)
    for item in ("b_corr", "rotary"):
        assert item in cfg["assumed"], item
    assert "expert parallelism" in cfg["deployment"]
    assert cfg["run"] == dict(precision="bf16", weights="bf16", max_batch=128,
                              max_context=16384, block_size=16,
                              num_blocks=98305)
    model = arch.model_config(cfg)
    assert model["pattern"] == "L-LELELELE" == cfg["hybrid_override_pattern"]
    assert (model["heads"], model["q_lora"], model["kv_lora"],
            model["qk_nope"], model["qk_rope"], model["v_head"]) == (
        32, 1536, 512, 128, 64, 128)
    assert (model["n_experts"], model["experts_held"], model["top_k"],
            model["route_scale"], model["router"]) == (
        256, (0, 64), 8, 2.5, "sigmoid")
    assert model["rope_interleave"] is True and model["latent"] is None


def test_the_arithmetic_of_the_stage(loaded):
    """1 934 263 296 parameters (3.87 GB in bf16), 5 760 B of latent rows a
    token; the program's model holds exactly those leaves, and a pool of
    98 304 blocks, laid out as the chip holds it (640 lanes a row), leaves
    weights + pool at 82 % of the backend's 16.9 GB."""
    import jax
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = loaded["cfg"]
    mla = 2048 + 2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 \
        + 512 * 32 * 256 + 32 * 128 * 2048
    dense = 2048 + 3 * 2048 * 7168
    sparse = 2048 + 2048 * 256 + 256 + 64 * 3 * 2048 * 768 + 3 * 2048 * 768
    top = 2 * 129280 * 2048 + 2048
    assert (mla, dense, sparse, top) == (26_349_568, 44_042_240, 307_235_072,
                                         529_532_928)
    assert ref.parameter_count(cfg) == 5 * mla + dense + 4 * sparse + top \
        == 1_934_263_296 == cfg["parameters"]
    assert ref.cache_bytes_per_token(cfg) == 5 * 576 * 2 == 5760 \
        == cfg["cache_bytes_per_token"]
    model = HybridLM(arch.model_config(cfg))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 1_934_263_296
    assert shapes["03_moe"]["mixer"]["w1"].shape == (64, 2048, 1536)
    assert shapes["03_moe"]["mixer"]["router"]["w"].shape == (2048, 256)
    assert shapes["00_mla"]["mixer"]["kv_b"]["w"].shape == (512, 32 * 256)
    assert model.cache_spec()["kv"] == {"layers": 5, "latent": 576,
                                        "values": 512}
    pool = 5 * cfg["run"]["num_blocks"] * 16 * 640 * 2
    assert 10.0e9 < pool < 10.1e9
    assert 0.80 < (2 * 1_934_263_296 + pool) / 16.909e9 < 0.83
    # the whole model without its draft layer: 48.94 B, the release's "48B"
    whole = dict(cfg, num_hidden_layers=40, n_routed_experts=256,
                 experts_held=[0, 256])
    assert ref.parameter_count(whole) == 48_942_542_592


def test_the_correction_bias_is_sized_by_the_routers_own_spread(loaded):
    """``assumed.b_corr``: N(0, 0.05) is a quarter of the spread of the
    sigmoid scores over the experts on the seeded weights and about ten
    times the median lead of the 8th score over the 9th."""
    got = arch.b_corr_spread(loaded["cfg"], seed=3, n=1024)
    assert 0.18 < got["score_std"] < 0.21
    assert 0.003 < got["gap_8_9_median"] < 0.007
    assert ref.B_CORR_STD == 0.05
    b = ref.leaf(loaded["cfg"], ref.seed_key(3), "sparse", "b_corr", 1)
    assert b.shape == (256,) and 0.04 < float(np.std(np.asarray(b, np.float32))) < 0.06


@pytest.mark.parametrize("key,value", [("hidden_size", 1024),
                                       ("kv_lora_rank", 256),
                                       ("qk_rope_head_dim", 32),
                                       ("moe_intermediate_size", 512),
                                       ("num_experts_per_tok", 4),
                                       ("rope_interleave", False)])
def test_a_changed_size_is_refused(loaded, key, value):
    with pytest.raises(SystemExit):
        bench_run.check_config(dict(loaded["cfg"], **{key: value}), _entry())


def test_the_adapter_refuses_what_the_program_does_not_have(loaded):
    cfg = loaded["cfg"]
    for key, value in [("num_nextn_predict_layers", 1), ("n_group", 8),
                       ("scoring_func", "softmax"), ("parameters", 1),
                       ("hybrid_override_pattern", "L-L-LELELE")]:
        with pytest.raises(ValueError):
            arch.check_sizes(dict(cfg, **{key: value}))


def _quantiles(n, median, sigma, lo, hi):
    inv = NormalDist().inv_cdf
    return [int(min(hi, max(lo, round(median * np.exp(sigma * inv((i + 0.5) / n))))))
            for i in range(n)]


def test_the_traffic_is_its_stated_law_and_fits_the_cache(loaded):
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    run = cfg["run"]
    prompts = _quantiles(1280, 6144, 0.7, 1024, 14336)
    outputs = _quantiles(1280, 512, 0.6, 128, 2048)
    perm = np.random.Generator(np.random.PCG64(traffic["pair_seed"])).permutation(1280)
    assert traffic["pairs"] == [[prompts[i], outputs[int(perm[i])]]
                                for i in range(1280)]
    assert traffic["order_seed"] == 20261017 and traffic["strata"] == 8
    assert (traffic["lead_in_s"], traffic["trace_seconds"],
            traffic["check_requests"]) == (4.0, 6.0, 6)
    assert traffic["end_to_end"] == ["serve_tokens_per_s"]
    assert traffic["main_module"] == "jit__decode_impl"
    requests = import_generator(traffic).generate(
        traffic, 2**31 + 9, vocab=arch.vocab(cfg), max_batch=run["max_batch"])
    assert len(requests) == 1280 + 128
    for r in requests:
        assert len(r["prompt"]) + r["max_new_tokens"] <= run["max_context"]
        assert max(r["prompt"]) < cfg["vocab_size"]
    # one simulated pass from the generator's stationary start
    bs, queue = run["block_size"], list(requests)
    active = [[len(r["prompt"]), r["max_new_tokens"]] for r in queue[:128]]
    nxt, used, context = 128, [], []
    while nxt < len(queue):
        used.append(sum(-(-(n + 1) // bs) for n, _ in active))
        context.append(np.mean([n for n, _ in active]))
        for a in active:
            a[0], a[1] = a[0] + 1, a[1] - 1
            if a[1] <= 0 and nxt < len(queue):
                a[:] = [len(queue[nxt]["prompt"]), queue[nxt]["max_new_tokens"]]
                nxt += 1
    pool = run["num_blocks"] - 1
    assert 7400 < np.mean(context) < 7800          # 7.6 k tokens in flight
    assert max(used) < 0.7 * pool and 0.55 * pool < np.mean(used)
    assert pool < 128 * run["max_context"] // bs    # not the worst case
    # the queue outlasts lead-in + window at the step's floor
    floor_s = arch.decode_bytes(cfg, 128 * 7600, 128) / 819e9
    assert 10.5e-3 < floor_s < 11.5e-3
    # and with the prompts' FLOPs at the MXU's peak beside it, twice
    prefill_s = sum(arch.prefill_flops(cfg, p) for p, _ in traffic["pairs"]) \
        / 197e12
    assert len(used) * floor_s > 1.5 * 44
    assert len(used) * floor_s + prefill_s > 2 * 44


def test_work_counts_against_hand_counts(loaded):
    cfg = loaded["cfg"]
    d, h = 2048, 32
    proj = 2 * (d * 1536 + 1536 * h * 192 + d * 576 + h * 128 * d)
    expert = 3 * 2048 * 768
    ffn = 6 * d * 7168 + 4 * (2 * d * 256 + 6 * d * 768 + 8 * 0.25 * 2 * expert)
    head = 2 * d * 129280
    # a decode token, absorbed: W_UK and W_UV a head, 2 H (576 + 512) a key
    absorb = 2 * 2 * h * 512 * 128
    assert arch.paged_decode_flops(cfg, 1000) == 5 * 2 * h * 1088 * 1000
    assert arch.decode_flops(cfg, 1000) == pytest.approx(
        5 * (proj + absorb) + ffn + 5 * 2 * h * 1088 * 1000 + head)
    # a prompt, decompressed: W_kvb a token, q.k of 192 and p.v of 128
    p = 3000
    assert arch.prefill_flops(cfg, p) == pytest.approx(
        p * (5 * (proj + 2 * 512 * h * 256) + ffn)
        + 5 * 2 * p * (p + 1) / 2 * h * (192 + 128) + head)
    # the mean prompt: ~6 TFLOP, three quarters of it MLA
    mean = arch.prefill_flops(cfg, 7078)
    assert 5.5e12 < mean < 6.5e12
    # the held experts a step reaches: 62.9 of 64 under 1 024 assignments
    hit = 64 * (1 - (255 / 256) ** 1024)
    assert arch.expected_experts_hit(cfg, 128) == pytest.approx(hit)
    assert 62.8 < hit < 63.0
    fixed = 1_934_263_296 - 4 * 64 * expert - 129280 * d
    weights = 2 * (fixed + 4 * hit * expert + 128 * d)
    assert arch.step_weight_bytes(cfg, 128) == pytest.approx(weights)
    tokens = 128 * 7700
    want = weights + 5760 * tokens + 5760 * 128 + 4 * 128 * 129280
    assert arch.decode_bytes(cfg, tokens, 128) == pytest.approx(want)
    # the latent rows are most of a step's bytes: 63 %
    assert 0.60 < 5760 * tokens / want < 0.66
    assert arch.paged_decode_bytes(cfg, tokens, 128) == pytest.approx(
        5 * 2 * (576 * tokens + 128 * h * (576 + 512)))
    # the kernel's roof is HBM: 60 FLOP a byte against the chip's 240
    assert arch.paged_decode_flops(cfg, tokens) / arch.paged_decode_bytes(
        cfg, tokens, 128) < 197e12 / 819e9


def _span(name, id_, parent, t0, **tags):
    return types.SimpleNamespace(name=name, id=id_, parent=parent, t0=t0,
                                 t1=t0 + 1.0, instant=False, tags=tags)


def _two_steps(monkeypatch, **tags):
    from theanompi_tpu.telemetry import spans

    records = [_span("serve.step", 1, None, 0.0),
               _span("serve.decode", 2, 1, 0.1, batch=128, kv_tokens=985000,
                     **tags),
               _span("serve.step", 3, None, 2.0),
               _span("serve.decode", 4, 3, 2.1, batch=128, kv_tokens=985256,
                     **tags)]
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    return records


def test_the_latent_kernel_share_reads_the_decode_spans_two_tags(monkeypatch):
    decl = load_json("metrics", "kv.latent_kernel_share_mla.json")
    assert decl["reader"] == "span_tags" and decl["workloads"] == [WORKLOAD]
    records = _two_steps(monkeypatch, latent_layers=5, latent_kernel_layers=5)
    run = {"counters": {"steps": 2}}
    assert span_tags.read(run, **decl["args"]) == 1.0
    records[3].tags["latent_kernel_layers"] = 0   # the gate sent it to the gather
    assert span_tags.read(run, **decl["args"]) == 0.5
    for r in records:  # a program without latent layers (the parent) tags none
        r.tags.pop("latent_layers", None)
        r.tags.pop("latent_kernel_layers", None)
    assert span_tags.read(run, **decl["args"]) is None


def test_the_kernels_roofline_and_time_read_its_own_row_per_run(monkeypatch):
    """One kernel's row of the trace over the decode program's runs: no
    other custom call counts; a trace without the row reads nothing."""
    _two_steps(monkeypatch)
    cfg = bench_run.load_cell(WORKLOAD)["cfg"]
    trace = {"kernels": [["custom-call/flash_fwd", 1.8],
                         ["custom-call/paged_decode_latent", 1.4],
                         ["custom-call/grouped_matmul", 0.4]],
             "module_runs": {"jit__decode_impl": 101, "jit__prefill_impl": 9}}
    run = {"counters": {"steps": 2}, "trace": trace, "cfg": cfg,
           "traffic": {"main_module": "jit__decode_impl"},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    decl = load_json("metrics", "kernels.paged_decode_latent_roofline_mla.json")
    assert decl["reader"] == "kernel_roofline" and decl["workloads"] == [WORKLOAD]
    roof = decl["args"]
    least = arch.paged_decode_bytes(cfg, 985128, 128) / 819e9
    assert kernel_roofline.read(run, **roof) == pytest.approx(
        100 * least / (1.4 / 101))
    decl = load_json("metrics", "kernels.paged_decode_latent_ms_per_step_mla.json")
    assert decl["reader"] == "kernel_per_run" and decl["workloads"] == [WORKLOAD]
    assert kernel_per_run.read(run, **decl["args"]) == pytest.approx(
        1e3 * 1.4 / 101)
    trace["kernels"] = [trace["kernels"][0], trace["kernels"][2]]
    assert kernel_roofline.read(run, **roof) is None
    assert kernel_per_run.read(run, **decl["args"]) is None
    assert kernel_per_run.read({"trace": None}, **decl["args"]) is None


def check_this_cells_entries(bench: dict) -> None:
    """What this cell brought to ``BENCHMARK.json``, by name and
    membership: its 13 metrics, each a file of the same declaration,
    declared for it alone; the cell once on one chip over its
    configuration; the rate it reports listing it."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        decl = load_json("metrics", name + ".json")
        assert listed[name]["workloads"] == [WORKLOAD] == decl["workloads"]
        assert listed[name]["moves"] == "serve_tokens_per_s"
    cells = [w for w in bench["workloads"] if w["name"] == WORKLOAD]
    assert len(cells) == 1 and cells[0]["chips"] == 1
    assert cells[0]["config"] == CONFIG
    assert [c["name"] for c in bench["configs"]].count(CONFIG) == 1
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert WORKLOAD in e2e["workloads"]


def test_every_new_metric_is_declared_for_this_cell_alone():
    check_this_cells_entries(_bench())


def test_the_tiny_configuration_passes_the_adapters_check():
    cfg = tiny_config()
    arch.check_sizes(cfg)
    assert arch.pattern(cfg) == "L-LELE"
    with pytest.raises(ValueError, match="letters"):
        arch.check_sizes(dict(cfg, hybrid_override_pattern="L-L-LE"))


@pytest.fixture(scope="module")
def sound():
    from theanompi_tpu.telemetry import spans

    spans.RING.clear()  # the tests below count this rehearsal's steps alone
    return rehearse()


def test_a_tiny_run_through_the_real_harness_is_correct(sound):
    well_formed(sound, "serve_tokens_per_s")
    assert set(sound["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert sound["correct"] is True, sound["compared"]
    assert sound["extra"]["tokens_compared"] > 20


def test_the_new_tags_ride_on_the_tiny_runs_spans(sound):
    """What ``--trace 1`` would report from the program's ring: every decode
    step's three latent layers, none of them through the kernel on the
    CPU's gather; every prefill's three."""
    from theanompi_tpu.telemetry import spans

    steps = len([r for r in spans.snapshot() if r.name == "serve.step"])
    run = {"counters": {"steps": min(steps, sound["extra"]["steps"]),
                        "moe_layers": 2, "experts_held": 8}}
    assert span_tags.read(run, root="serve.step", span="serve.decode",
                          num="latent_kernel_layers",
                          den="latent_layers") == 0.0
    decodes = [r for r in spans.snapshot() if r.name == "serve.decode"]
    prefills = [r for r in spans.snapshot() if r.name == "serve.prefill"]
    assert decodes and all(r.tags["latent_layers"] == 3 for r in decodes)
    assert prefills and all(r.tags["latent_layers"] == 3 for r in prefills)
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
            padded = np.zeros((1, 64), np.int32)
            padded[0, :len(toks)] = toks
            toks.append(int(np.argmax(ref.logits(cfg, seed, padded)[0, len(toks) - 1])))
        sample.append((prompt, toks[len(prompt):]))
    return sample


@pytest.fixture(scope="module")
def greedy():
    old, ref.QUERY_BLOCK = ref.QUERY_BLOCK, 32
    cfg = tiny_config()
    yield cfg, _greedy_sample(cfg, 11)
    ref.QUERY_BLOCK = old


def test_the_fp8_control_and_the_dropped_terms_are_not_correct(greedy,
                                                               monkeypatch):
    """Over a prompt and the reference's own greedy tokens the served gap is
    0; the token the fp8 control puts first lies beyond the limit, and so
    do the greedy tokens of a model without its c_kv norm, its correction
    bias or its routed scale (CPU, seed 11: 0.068, 0.317, 0.166, 0.099).
    The rotary pairing is refused at the cell's size (PERF.md section 6):
    at these widths attention is near uniform over 44 positions, and
    rotate-half in its place moves the logits without reaching the limit."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    cfg, sample = greedy
    assert arch.served_gaps(cfg, 11, sample)["widest_logit_gap"] == 0.0
    control = arch.served_gaps(cfg, 11, sample, control=True)
    assert control["widest_logit_gap"] > TINY_LIMIT, control
    assert control["tokens_compared"] == 3 * 24
    for drop in ("kv_norm", "b_corr", "route_scale"):
        gap = arch.served_gaps(cfg, 11, sample, drop=drop)["widest_logit_gap"]
        assert gap > TINY_LIMIT, (drop, gap)
    toks = np.zeros((1, 64), np.int32)
    toks[0, :44] = sample[0][0] + sample[0][1]
    moved = np.abs(ref.logits(cfg, 11, toks, drop="interleave")
                   - ref.logits(cfg, 11, toks))[0, :44]
    assert moved.max() > 1e-3


def test_only_positions_whose_routing_is_decided_are_compared(greedy,
                                                              monkeypatch):
    """``check.routing_margin``: a served position counts where the float32
    reference decides every expert layer's top-k (by ``s + b_corr``) by
    more than that margin; too few such positions is no comparison."""
    import jax.numpy as jnp

    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    cfg, sample = greedy
    everything = arch.served_gaps(cfg, 11, sample, control=True)
    assert everything["tokens_compared"] == everything["tokens_served"] == 72
    # the margin itself: the k-th selection score less the first unselected
    p = ref.layer_leaves(cfg, ref.seed_key(11), "sparse", 1)
    u = jnp.asarray(np.random.RandomState(0).randn(5, 256), jnp.float32)
    s = np.asarray(1 / (1 + np.exp(-(np.asarray(u) @ np.asarray(p["router_w"])))))
    z = np.sort(s + np.asarray(p["b_corr"]), axis=-1)[:, ::-1]
    np.testing.assert_allclose(ref.route(cfg, p, u)[2], z[:, 3] - z[:, 4],
                               rtol=1e-4, atol=1e-6)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :20] = sample[0][0]
    least = np.asarray(ref.hidden(cfg, 11, toks, [20])[2])[0, :20]
    assert (least >= 0).all()
    cfg = dict(cfg, check=dict(cfg["check"], routing_margin=float(np.median(least))))
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
