"""The block-diffusion cell's files and harness (``serve_arch`` + the
``sdar_moe`` adapter): the configuration against the harness's check and
the catalog's numbers, its arithmetic, the traffic against its stated law
and the cache, the adapter's counts of work against hand counts, the
readers of what this cell brought, and a rehearsal of the whole run at a
tiny size through the real entries on the CPU."""

import json
import os
import types
from statistics import NormalDist

import numpy as np
import pytest

from bench_tiny import failed_names, well_formed
from sdar_tiny import TINY_LIMIT, WORKLOAD, rehearse, tiny_config

from benchmarks import run as bench_run
from benchmarks.arch import sdar_moe as arch
from benchmarks.arch import sdar_moe_reference as ref
from benchmarks.common import ROOT, import_generator, load_json
from benchmarks.readers import kernel_roofline, span_tags

#: what this cell appended to ``BENCHMARK.json``'s ``per_layer``
METRICS = ["engine.step_mfu_serve_blockdiff",
           "engine.step_hbm_share_serve_blockdiff",
           "engine.decode_step_ms_p50_blockdiff",
           "engine.decode_wait_ms_p50_blockdiff",
           "engine.prefill_span_ms_p50_blockdiff",
           "moe.load_peak_over_mean_blockdiff",
           "sched.batch_occupancy_blockdiff", "sched.tpot_ms_p90_blockdiff",
           "kv.pool_peak_use_share_blockdiff",
           "device.idle_share_serve_blockdiff",
           "device.peak_hbm_share_serve_blockdiff",
           "diffusion.commits_per_slot_pass",
           "kernels.paged_decode_grouped_roofline_blockdiff"]


@pytest.fixture(scope="module")
def loaded():
    return bench_run.load_cell(WORKLOAD)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry():
    return next(c for c in _bench()["configs"] if c["name"] == "sdar-30b-a3b-pp8")


def test_the_configuration_is_the_published_model_cut_in_depth_only(loaded):
    cfg = loaded["cfg"]
    bench_run.check_config(cfg, _entry())
    arch.check_sizes(cfg)
    widths = dict(
        hidden_size=2048, intermediate_size=6144, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=128,
        num_experts_per_tok=8, moe_intermediate_size=768, vocab_size=151936,
        rms_norm_eps=1e-6, max_position_embeddings=32768, rope_theta=1000000,
        norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
        rope_scaling=None, use_sliding_window=False, tie_word_embeddings=False,
        attention_bias=False, model_type="sdar_moe")
    for key, value in widths.items():
        assert cfg[key] == value == cfg["published"][key], key
    assert cfg["reduced"] == ["num_hidden_layers"] == _entry()["reduced"]
    assert cfg["num_hidden_layers"] == 6 and cfg["published"]["num_hidden_layers"] == 48
    assert "8 pipeline stages of 6 whole layers" in cfg["deployment"]
    for item in ("block_length", "denoising_passes", "mask_token_id",
                 "attention mask", "logit shift", "q/k norm", "router",
                 "initialisation"):
        assert item in cfg["assumed"], item
    assert (cfg["block_length"], cfg["denoising_passes"], cfg["mask_token_id"]) \
        == (4, 2, 151669)
    assert any("static" in d for d in cfg["departures"])
    assert cfg["run"] == dict(precision="bf16", weights="bf16", max_batch=64,
                              max_context=4096, block_size=16,
                              num_blocks=cfg["assumed"]["num_blocks"])
    model = arch.model_config(cfg)
    assert model["pattern"] == "*E" * 6 == cfg["hybrid_override_pattern"]
    assert (model["router"], model["shared_dim"], model["qk_norm"],
            model["block_len"], model["mask_id"]) == ("softmax", 0, True, 4,
                                                      151669)


def test_the_arithmetic_of_the_stage(loaded):
    """4 361 055 744 parameters (8.72 GB in bf16), 12 288 B of K/V a token,
    a pool of 64 x 4096 tokens + the null block; the program's own tree
    counts the same (no shared expert, no correction bias)."""
    import jax

    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = loaded["cfg"]
    assert ref.kind_params(cfg, "attn") + ref.kind_params(cfg, "moe") \
        == 19_140_864 + 128 * 4_718_592 == 623_120_640
    assert ref.kind_params(cfg, "top") == 622_331_904
    assert ref.parameter_count(cfg) == 4_361_055_744
    assert arch.kv_bytes_per_token(cfg) == 12_288
    assert cfg["run"]["num_blocks"] == 64 * 4096 // 16 + 1
    resident = 2 * ref.parameter_count(cfg) + 12_288 * 16 * cfg["run"]["num_blocks"]
    assert 11.9e9 < resident < 12.0e9          # 74.6 % of 16 GB
    model = HybridLM(arch.model_config(cfg))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 4_361_055_744
    assert shapes["01_moe"]["mixer"]["w1"].shape == (128, 2048, 1536)
    assert shapes["00_attn"]["mixer"]["q_norm"]["scale"].shape == (128,)
    assert model.cache_spec()["kv"] == {"layers": 6, "heads": 4, "head_dim": 128}


@pytest.mark.parametrize("key,value", [("hidden_size", 1024), ("head_dim", 64),
                                       ("moe_intermediate_size", 384),
                                       ("num_experts_per_tok", 4),
                                       ("num_experts", 64), ("vocab_size", 75968)])
def test_a_changed_size_is_refused(loaded, key, value):
    with pytest.raises(SystemExit):
        bench_run.check_config(dict(loaded["cfg"], **{key: value}), _entry())


def _quantiles(n, median, sigma, lo, hi):
    inv = NormalDist().inv_cdf
    return [int(min(hi, max(lo, round(median * np.exp(sigma * inv((i + 0.5) / n))))))
            for i in range(n)]


def test_the_traffic_is_its_stated_law_and_fits_the_cache(loaded):
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    run = cfg["run"]
    prompts = _quantiles(1536, 768, 0.8, 64, 3072)
    answers = _quantiles(1536, 384, 0.6, 128, 1024)
    perm = np.random.Generator(np.random.PCG64(traffic["pair_seed"])).permutation(1536)
    assert traffic["pairs"] == [[prompts[i], answers[int(perm[i])]]
                                for i in range(1536)]
    assert traffic["order_seed"] == 20261015 and traffic["strata"] == 8
    assert traffic["end_to_end"] == ["serve_tokens_per_s"]
    assert traffic["main_module"] == "jit__block_impl"
    requests = import_generator(traffic).generate(
        traffic, 2**31 + 9, vocab=arch.vocab(cfg), max_batch=run["max_batch"])
    assert len(requests) == 1536 + 64
    for r in requests:
        assert len(r["prompt"]) + r["max_new_tokens"] <= run["max_context"]
        assert max(r["prompt"]) < cfg["mask_token_id"]
    # every slot's worst case fits: no preemption by construction
    assert (run["num_blocks"] - 1) * 16 >= run["max_batch"] * run["max_context"]
    # the queue outlasts lead-in + window even at the pass's roofline
    floor_s = arch.decode_bytes(cfg, 64 * 1250, 64) / 819e9
    tokens = sum(r["max_new_tokens"] for r in requests)
    assert tokens / (64 * 4 / 3 / floor_s) > 2 * 44


def test_work_counts_against_hand_counts(loaded):
    cfg = loaded["cfg"]
    d, hq, hkv, e, f = 2048, 32 * 128, 4 * 128, 128, 768
    expert = 3 * d * f
    layer = 2 * d * (2 * hq + 2 * hkv) + 2 * d * e + 8 * 2 * expert
    head = 2 * d * 151936
    assert arch.matmul_flops_per_token(cfg) == 6 * layer + head
    # a pass: 4 rows through everything, each over the cache and the block
    assert arch.decode_flops(cfg, 1001) == 4 * (6 * layer + head) + \
        4 * 4 * (1000 + 4) * hq * 6
    # a prefill: whole blocks only, block-causal, no head
    n = 1003 // 4
    assert arch.prefill_flops(cfg, 1003) == 4 * n * 6 * layer + \
        4 * 4 * 16 * n * (n + 1) / 2 * hq * 6
    # 64 slots x 4 rows x 8 assignments reach every expert of a layer
    assert arch.expected_experts_hit(cfg, 256) > 127.9
    weights = arch.step_weight_bytes(cfg, 256)
    assert 8.0e9 < weights < 8.2e9             # the issue's 8.10 GB a pass
    context = 64 * 1250 + 64                   # serve_arch's count: L + 1 a slot
    want = weights + 12288 * (64 * 1250 + 4 * 64) + 12288 * 256 + 4 * 256 * 151936
    assert arch.decode_bytes(cfg, context, 64) == pytest.approx(want)
    # the kernel's share: keys L + B a slot (the kv_tokens tag)
    kv = 64 * 1254
    assert arch.paged_decode_bytes(cfg, kv, 64) == 12288 * kv + 6 * 2 * 64 * 4 * hq * 2
    assert arch.paged_decode_flops(cfg, kv) == 4 * 4 * kv * hq * 6


def _span(name, id_, parent, t0, **tags):
    return types.SimpleNamespace(name=name, id=id_, parent=parent, t0=t0,
                                 t1=t0 + 1.0, instant=False, tags=tags)


def _two_passes(monkeypatch, **tags):
    from theanompi_tpu.telemetry import spans

    records = [_span("serve.step", 1, None, 0.0),
               _span("serve.decode", 2, 1, 0.1, batch=64, kv_tokens=80256,
                     **tags),
               _span("serve.step", 3, None, 2.0),
               _span("serve.decode", 4, 3, 2.1, batch=64, kv_tokens=80320,
                     **tags)]
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    return records


def test_the_commits_per_pass_read_the_decode_spans_committed_tag(monkeypatch):
    decl = load_json("metrics", "diffusion.commits_per_slot_pass.json")
    assert decl["reader"] == "span_tags" and decl["workloads"] == [WORKLOAD]
    records = _two_passes(monkeypatch, committed=128)
    run = {"counters": {"steps": 2}}
    assert span_tags.read(run, **decl["args"]) == 2.0
    records[3].tags["committed"] = 0   # a pass that writes the block's K/V
    assert span_tags.read(run, **decl["args"]) == 1.0
    for r in records:  # a program that commits one token a step tags none
        r.tags.pop("committed", None)
    assert span_tags.read(run, **decl["args"]) is None


def test_the_kernels_roofline_reads_its_own_row_per_run(monkeypatch):
    """One kernel's row of the trace over the main program's runs, against
    the adapter's bytes at the spans' mean ``kv_tokens`` and ``batch``: no
    other custom call counts; a trace without the row reads nothing."""
    decl = load_json("metrics", "kernels.paged_decode_grouped_roofline_blockdiff.json")
    assert decl["reader"] == "kernel_roofline" and decl["workloads"] == [WORKLOAD]
    _two_passes(monkeypatch)
    cfg = bench_run.load_cell(WORKLOAD)["cfg"]
    trace = {"kernels": [["custom-call/grouped_matmul", 4.0],
                         ["custom-call/paged_decode_grouped", 0.5]],
             "module_runs": {"jit__block_impl": 250, "jit__prefill_blocks_impl": 9}}
    run = {"counters": {"steps": 2}, "trace": trace, "cfg": cfg,
           "traffic": {"main_module": "jit__block_impl"},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    least = arch.paged_decode_bytes(cfg, 80288, 64) / 819e9
    assert kernel_roofline.read(run, **decl["args"]) == pytest.approx(
        100 * least / (0.5 / 250))
    trace["kernels"] = trace["kernels"][:1]
    assert kernel_roofline.read(run, **decl["args"]) is None


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
    assert cells[0]["config"] == "sdar-30b-a3b-pp8"
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert WORKLOAD in e2e["workloads"]


def test_every_new_metric_is_declared_for_this_cell_alone():
    check_this_cells_entries(_bench())


def test_served_record_covers_the_generated_and_the_dropped_positions():
    req = types.SimpleNamespace(
        prompt=[1] * 6, generated=[7, 8, 9],
        committed={6: (7, 0, -1.5), 7: (8, 1, -2.0), 8: (9, 0, -0.5),
                   9: (5, 1, -3.0), 10: (4, 0, -1.0), 11: (3, 0, -0.25)})
    assert arch.served_record(req) == {
        "passes": [0, 1, 0, 1, 0, 0],
        "confidence": [-1.5, -2.0, -0.5, -3.0, -1.0, -0.25],
        "dropped": [5, 4, 3]}
    rows = ref.passes_of(dict(tiny_config(), block_length=4, mask_token_id=99),
                         req.prompt, req.generated, arch.served_record(req))
    # block 4..8 (tail 2): passes 0, 1; block 8..12: passes 0, 1
    assert rows["starts"].tolist() == [4, 4, 8, 8]
    assert rows["inputs"].tolist() == [[1, 1, 99, 99], [1, 1, 7, 99],
                                       [99, 99, 99, 99], [9, 99, 4, 3]]
    assert rows["chosen"].tolist() == [[0, 0, 1, 0], [0, 0, 0, 1],
                                       [1, 0, 1, 1], [0, 1, 0, 0]]
    # each pass carries the confidence of what it committed, 0 elsewhere
    assert rows["conf"].tolist() == [[0, 0, -1.5, 0], [0, 0, 0, -2.0],
                                     [-0.5, 0, -1.0, -0.25], [0, -3.0, 0, 0]]


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
    """What ``--trace 1`` would report from the program's ring: a pass
    commits 4/3 positions a slot over a block's three passes (the first
    and the last blocks move it a little), tags the keys it reads and how
    many of its slots only write K/V; a prefill tags its block's tail."""
    from theanompi_tpu.telemetry import spans

    steps = len([r for r in spans.snapshot() if r.name == "serve.step"])
    run = {"counters": {"steps": min(steps, sound["extra"]["steps"])}}
    share = span_tags.read(run, **load_json(
        "metrics", "diffusion.commits_per_slot_pass.json")["args"])
    assert 1.2 < share < 1.45
    decodes = [r for r in spans.snapshot() if r.name == "serve.decode"]
    assert decodes and all(0 <= r.tags["store_slots"] <= r.tags["batch"]
                           and r.tags["masked_rows"] <= 4 * r.tags["batch"]
                           for r in decodes)
    prefills = [r for r in spans.snapshot() if r.name == "serve.prefill"]
    assert prefills and all(r.tags["block_tail"] == r.tags["prompt"] % 4
                            == r.tags["prompt"] - r.tags["tokens"]
                            for r in prefills)


def _greedy_sample(cfg, seed, n=3):
    """Prompts and what the float32 reference generates after them by the
    configuration's own block diffusion (a pass commits the masked
    positions it finds most probable), with the pass of each."""
    rng = np.random.Generator(np.random.PCG64(3))
    b, mask, per = cfg["block_length"], cfg["mask_token_id"], 2
    sample = []
    for _ in range(n):
        prompt = rng.integers(0, mask, size=10).tolist()
        seq, when = list(prompt), [-1] * len(prompt)
        said = [None] * len(prompt)
        while len(seq) < len(prompt) + 18:
            s = len(seq) - len(seq) % b
            block = seq[s:] + [mask] * (b - len(seq) + s)
            passes = when[s:] + [None] * (b - len(seq) + s)
            confs = said[s:] + [None] * (b - len(seq) + s)
            j = 0
            while None in passes:
                trial = [p if p is not None else j for p in passes]
                record = {"passes": when[len(prompt):s] + trial[max(len(prompt) - s, 0):],
                          "dropped": []}
                record["confidence"] = [None] * len(record["passes"])
                lg = np.asarray(ref.pass_logits(cfg, seed, [(
                    prompt, (seq[:s] + block)[len(prompt):], record)])[0][-1])
                conf = -np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1))
                open_ = [i for i in range(b) if passes[i] is None]
                for i in sorted(open_, key=lambda i: (-conf[i], i))[:per]:
                    block[i], passes[i] = int(lg[i].argmax()), j
                    confs[i] = float(conf[i])
                j += 1
            seq, when = seq[:s] + block, when[:s] + passes
            said = said[:s] + confs
        sample.append((prompt, seq[len(prompt):], {
            "passes": when[len(prompt):], "confidence": said[len(prompt):],
            "dropped": []}))
    return sample


def test_the_fp8_control_and_each_dropped_term_are_not_correct():
    """Over prompts and the reference's own block-diffusion tokens and
    confidences the served gap is 0 but for float32 rounding; the choices
    the fp8 control makes in the same states lie beyond the limit, and so
    do the reference's own choices against a reference without its
    in-block bidirectional attention, its q/k norm or its top-k
    renormalisation."""
    cfg = tiny_config()
    sample = _greedy_sample(cfg, 11)
    own = arch.served_gaps(cfg, 11, sample)
    assert own["widest_logit_gap"] < 1e-5 and own["order_gap"] == 0.0, own
    control = arch.served_gaps(cfg, 11, sample, control=True)
    assert control["widest_logit_gap"] > TINY_LIMIT, control
    for drop in ("bidirectional", "qk_norm", "topk_renorm"):
        gap = arch.served_gaps(cfg, 11, sample, drop=drop)["widest_logit_gap"]
        assert gap > TINY_LIMIT, (drop, gap)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from theanompi_tpu.serving.engine import InferenceEngine

    real = InferenceEngine.decode

    def altered(self, tables, lengths, tokens, temps, rids):
        rows, logits = real(self, tables, lengths, tokens, temps, rids)
        self._n_altered = getattr(self, "_n_altered", 0) + 1
        if len(rows) and self._n_altered % 4 == 0:
            rows = np.array(rows)
            rows[:, 2:6] = (rows[:, 2:6] + 1) % 509
        return rows, logits
    monkeypatch.setattr(InferenceEngine, "decode", altered)
    line = rehearse()
    assert line["correct"] is False
    assert failed_names(line) == ["widest_logit_gap"]


def _least_first(real):
    """A planted fault in the selection: of the masked positions, those
    whose greedy token is the LEAST probable are committed; the tokens are
    still the greedy ones and the confidences true."""
    import jax.numpy as jnp

    def select(self, logits, tokens, masked):
        _, _, conf = real(self, logits, tokens, masked)
        rank = jnp.where(masked, -conf, -jnp.inf)
        i = jnp.arange(tokens.shape[1])
        ahead = (rank[:, None, :] > rank[:, :, None]) | (
            (rank[:, None, :] == rank[:, :, None]) & (i[None, :] < i[:, None]))
        take = masked & (jnp.sum(ahead, axis=-1) < jnp.minimum(
            self.commits_per_pass, jnp.sum(masked, axis=-1))[:, None])
        state = jnp.where(take, 2, jnp.where(masked, 0, 1)).astype(jnp.int32)
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(take, best, tokens), state, conf
    return select


def test_a_pass_that_commits_its_least_confident_positions_is_not_correct(
        monkeypatch):
    """Only the order of a pass's commits is wrong: the check's second
    gap, over pairs of a committed and a masked position, refuses it."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    monkeypatch.setattr(HybridLM, "commit_block",
                        _least_first(HybridLM.commit_block))
    line = rehearse()
    assert line["correct"] is False
    assert failed_names(line) == ["widest_logit_gap"]
