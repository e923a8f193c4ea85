"""``HybridLM`` (Mamba-2 state beside paged K/V, dropless latent experts,
grouped K/V heads) against the plain reference
(``benchmarks/arch/nemotron_h_reference.py``) on the CPU at small widths,
with seeded weights: the ops alone, the model through the engine's cache,
the scheduler's slots, and the programs the other model must keep."""

import dataclasses
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))

from arch_tiny import tiny_cell  # noqa: E402

from benchmarks.arch import nemotron_h as arch  # noqa: E402
from benchmarks.arch import nemotron_h_reference as ref  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration computed in float32 (the weights are bf16
    values either way), its program model and that model's weights."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = tiny_cell()["cfg"]
    cfg["run"].update(precision="fp32", weights="fp32")
    model = HybridLM(arch.model_config(cfg))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          arch.seeded_params(model, cfg, SEED))
    return cfg, model, params


def _layer(tiny, kind):
    """(reference leaves, program params, program mixer) of the first layer
    of ``kind``."""
    cfg, model, params = tiny
    name = next(n for n, k in model.layers if k == kind)
    leaves = ref.layer_leaves(cfg, ref.seed_key(SEED), kind,
                              int(name.split("_")[0]))
    return leaves, params[name]["mixer"], model._mixers[kind]


def _inputs(cfg, t, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (t, cfg["hidden_size"]), jnp.float32)


# -- the ops alone ---------------------------------------------------------------

@pytest.mark.parametrize("t", [5, 8, 24, 27])
def test_mamba_prefill_by_chunks_is_the_scan_over_time(tiny, t):
    """Chunks of 8: shorter than one, whole ones, and a ragged tail."""
    cfg = tiny[0]
    leaves, p, mixer = _layer(tiny, "mamba")
    u = _inputs(cfg, t)
    want = ref.mamba(cfg, ref._ein("fp32"), leaves, u)
    got, _ = mixer.prefill(p, u, jnp.int32(t))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("t,bucket", [(5, 8), (11, 16), (13, 32)])
def test_a_padded_bucket_leaves_the_unpadded_prompts_state(tiny, t, bucket):
    """Padding is invisible to the state and to the real positions, and
    decoding on from that state is the recurrence's next step."""
    cfg = tiny[0]
    leaves, p, mixer = _layer(tiny, "mamba")
    u = _inputs(cfg, t + 1)
    padded = jnp.concatenate(
        [u[:t], 7.0 * _inputs(cfg, bucket - t, seed=1)])  # loud padding
    y_pad, s_pad = mixer.prefill(p, padded, jnp.int32(t))
    y, s = mixer.prefill(p, u[:t], jnp.int32(t))
    np.testing.assert_allclose(y_pad[:t], y, atol=1e-5, rtol=1e-5)
    for name in s:
        np.testing.assert_allclose(np.asarray(s_pad[name], np.float32),
                                   np.asarray(s[name], np.float32),
                                   atol=1e-6, rtol=1e-6)
    # one state layer, one slot: the pools whole, as a server hands them over
    step, _ = mixer.decode(p, u[t:],
                           jax.tree.map(lambda a: a[None, None], s_pad), 0)
    want = ref.mamba(cfg, ref._ein("fp32"), leaves, u)[t]
    # the convolution state is kept in bf16
    np.testing.assert_allclose(step[0], want, atol=3e-3, rtol=3e-2)


def _skew(leaves, p):
    """A router that prefers a few experts strongly: b_corr up to 3."""
    bias = jnp.linspace(3.0, 0.0, leaves["b_corr"].shape[0])
    leaves = dict(leaves, b_corr=bias)
    p = dict(p, router=dict(p["router"], b_corr=bias))
    return leaves, p


#: what multiplies in the expert layer: the default, and the kernel of
#: ``ops/pallas_grouped_matmul.py`` through the Pallas interpreter
PRODUCTS = ["ragged_dot", "kernel_interpret"]


@pytest.mark.parametrize("products", PRODUCTS)
def test_expert_layer_is_the_reference_and_drops_nothing(tiny, products):
    """Top-k of 8 over 32 experts, 8 held, under a skewed router: every
    token's every held expert is computed, whatever the load."""
    cfg = tiny[0]
    leaves, p, mixer = _layer(tiny, "moe")
    mixer = dataclasses.replace(mixer, products=products)
    leaves, p = _skew(leaves, p)
    u = _inputs(cfg, 40)
    want = ref.experts(cfg, ref._ein("fp32"), leaves, u)
    got, stats = mixer.apply_tokens(p, u)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    held = ref.route(cfg, leaves, u)[:, slice(*cfg["experts_held"])] > 0
    assert int(stats["local_hits"]) == int(held.sum()) > 0
    assert int(stats["load_peak"]) == int(held.sum(axis=0).max())
    # skewed: some held expert takes far more than an even share
    assert int(stats["load_peak"]) > 2 * held.sum() / held.shape[1]


@pytest.mark.parametrize("products", PRODUCTS)
def test_the_four_shares_and_the_shared_expert_once_make_the_uncut_layer(
        tiny, products):
    """Each of four chips holds 8 of the 32 experts and routes over all 32;
    their routed parts, plus the shared expert counted once, add up to the
    reference's layer with every expert held."""
    from theanompi_tpu.ops.moe import DroplessMoE

    cfg = dict(tiny[0])
    key = ref.seed_key(SEED)
    u = _inputs(cfg, 24)
    n = cfg["router_experts"]
    whole = dict(cfg, experts_held=(0, n), n_routed_experts=n)
    leaves = ref.layer_leaves(whole, key, "moe", 1)
    want = ref.experts(whole, ref._ein("fp32"), leaves, u)

    def program(lo, hi, **parts):
        layer = DroplessMoE(cfg["hidden_size"], n, cfg["num_experts_per_tok"],
                            cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                            cfg["moe_shared_expert_intermediate_size"],
                            float(cfg["routed_scaling_factor"]), (lo, hi),
                            products)
        p = {"router": {"w": leaves["router_w"], "b_corr": leaves["b_corr"]},
             "down": {"w": leaves["down"]}, "up": {"w": leaves["up"]},
             "w1": leaves["w1"][lo:hi], "w2": leaves["w2"][lo:hi],
             "shared": {"v1": leaves["v1"], "v2": leaves["v2"]}}
        return layer.apply_tokens(p, u, **parts)

    total = program(0, n, routed=False)[0]          # the shared expert, once
    hits = 0
    for lo in range(0, n, n // 4):
        part, stats = program(lo, lo + n // 4, shared=False)
        total = total + part
        hits += int(stats["local_hits"])
    assert hits == u.shape[0] * cfg["num_experts_per_tok"]  # each pick, once
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    uncut, _ = program(0, n)
    np.testing.assert_allclose(uncut, want, atol=2e-5, rtol=2e-4)


def test_rmsnorm_plain_and_grouped_gated():
    from theanompi_tpu.ops.layers import ACTIVATIONS, RMSNorm

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 32))
    g = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    got, _ = RMSNorm(eps=1e-5).apply({"scale": g}, {}, x)
    np.testing.assert_allclose(got, ref._rms(x, g, 1e-5), rtol=1e-5, atol=1e-6)
    got, _ = RMSNorm(eps=1e-5, groups=4, gated=True).apply(
        {"scale": g}, {}, x, gate=z)
    np.testing.assert_allclose(got, ref._rms(x * jax.nn.silu(z), g, 1e-5, 4),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(
        ACTIVATIONS["relu2"](jnp.array([-2.0, 0.5, 3.0])), [0.0, 0.25, 9.0])


def test_grouped_kv_heads_in_the_decode_fallback(tiny):
    """Two K/V heads under four query heads, scattered block tables, ragged
    positions: the fallback's grouped branch is the reference's attention
    row."""
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    cfg = tiny[0]
    leaves, p, mixer = _layer(tiny, "attn")
    t, bs = 21, 8
    u = _inputs(cfg, t)
    want = ref.attention(cfg, ref._ein("fp32"), leaves, u)
    q, k, v = mixer.project_qkv(p, u[None])
    cache = PagedKVCache.create(1, 9, bs, cfg["num_key_value_heads"],
                                cfg["head_dim"], max_batch=2, max_context=32)
    row = jnp.array([5, 2, 7, 0])
    cache = cache.write_prefill(0, jnp.pad(k, ((0, 0), (0, 3), (0, 0), (0, 0))),
                                jnp.pad(v, ((0, 0), (0, 3), (0, 0), (0, 0))),
                                row[:3])
    cache = cache.with_tables(jnp.stack([row, jnp.zeros(4, jnp.int32)]))
    for pos in (0, 7, 8, t - 1):
        ctx = cache.attend_decode(0, jnp.stack([q[0, pos], q[0, 0]]),
                                  jnp.array([pos, 0]))
        got = mixer.project_out(p, ctx[0].reshape(-1))
        np.testing.assert_allclose(got, want[pos], atol=2e-5, rtol=2e-4)
    assert np.isfinite(np.asarray(ctx)).all()  # the inactive slot too


# -- the model through the engine's cache and the scheduler's slots --------------------

def _engine(tiny, **kw):
    from theanompi_tpu.serving.engine import InferenceEngine

    _, model, params = tiny
    return InferenceEngine(model, params, block_size=8, **kw)


def test_the_engine_builds_its_cache_from_the_models_cache_spec(tiny):
    cfg, model, _ = tiny
    spec = model.cache_spec()
    assert spec["kv"] == {"layers": 1, "heads": 2, "head_dim": 16}
    assert spec["state_layers"] == 2 and set(spec["state"]) == {"ssm", "conv"}
    eng = _engine(tiny, max_batch=3, num_blocks=12)
    assert eng._k.shape == (1, 12, 8, 2, 16) and eng.decode_impl == "fallback"
    assert eng._state["ssm"].shape == (2, 3, 8, 16, 16)
    assert eng._state["ssm"].dtype == jnp.float32
    assert eng._state["conv"].shape == (2, 3, 3, 8 * 16 + 2 * 2 * 16)
    assert eng._state["conv"].dtype == jnp.bfloat16 and eng.stateful


def test_weights_are_held_in_the_models_stated_dtype():
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = HybridLM({"pattern": "ME*", "dim": 32, "vocab": 50, "seq_len": 16})
    params, _ = model.init_params(jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype("float32")}
    eng = InferenceEngine(model, params, block_size=8, max_batch=2)
    assert {x.dtype for x in jax.tree.leaves(eng.params)} == {jnp.dtype("bfloat16")}
    eng.swap_params(params)
    assert {x.dtype for x in jax.tree.leaves(eng.params)} == {jnp.dtype("bfloat16")}
    with pytest.raises(NotImplementedError, match="serving-only"):
        model.loss_fn(params, {}, None, None, True)


def test_prefill_then_decode_through_the_cache_is_the_references_forward(tiny):
    """Logits, not tokens: a prompt padded to its bucket, then 9 decode
    steps, against the reference's one full forward over the same tokens."""
    cfg = tiny[0]
    eng = _engine(tiny, max_batch=2)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg["vocab_rows_held"], size=13).tolist()
    table = np.zeros((2, eng.max_blocks_per_seq), np.int32)
    table[1, :4] = [3, 1, 4, 2]                    # slot 1; slot 0 inactive
    tok, last = eng.prefill([3, 1], prompt, rid=7, slot=1)
    rows, toks = [last], list(prompt) + [tok]
    for _ in range(9):
        lengths = np.array([0, len(toks) - 1], np.int32)
        nxt, logits = eng.decode(table, lengths, np.array([0, toks[-1]]),
                                 np.zeros(2, np.float32), np.array([0, 7]))
        rows.append(logits[1])
        toks.append(int(nxt[1]))
    padded = np.zeros((1, cfg["run"]["max_context"]), np.int32)
    padded[0, :len(toks) - 1] = toks[:-1]
    want = ref.logits(cfg, SEED, padded)[0, len(prompt) - 1:len(toks) - 1]
    np.testing.assert_allclose(np.stack(rows), want, atol=3e-3, rtol=0)
    assert toks[len(prompt):] == np.argmax(want, axis=-1).tolist()


def _serve(eng_or_sched, requests):
    from theanompi_tpu.serving.scheduler import Request, Scheduler

    sched = (eng_or_sched if isinstance(eng_or_sched, Scheduler)
             else Scheduler(eng_or_sched))
    reqs = [Request(rid=rid, prompt=list(prompt), max_new_tokens=n,
                    temperature=0.0) for rid, prompt, n in requests]
    for r in reqs:
        sched.submit(r)
    while not sched.idle:
        sched.step()
    return {r.rid: list(r.generated) for r in reqs}, sched


def test_a_reused_slot_and_a_recomputed_request_match_a_fresh_run(tiny):
    cfg = tiny[0]
    rng = np.random.RandomState(11)
    work = [(i, rng.randint(0, cfg["vocab_rows_held"], size=n).tolist(), m)
            for i, (n, m) in enumerate([(9, 12), (21, 7), (5, 16), (14, 10),
                                        (30, 9)])]
    fresh = {}
    for w in work:  # each alone, in slot 0 of a new engine
        fresh.update(_serve(_engine(tiny, max_batch=1), [w])[0])
    # two slots for five requests: every later one lands in a used slot
    got, _ = _serve(_engine(tiny, max_batch=2), work)
    assert got == fresh
    # a pool too small for the active contexts: the longest is preempted,
    # queued again and recomputed over prompt + generated
    got, sched = _serve(_engine(tiny, max_batch=3, num_blocks=8), work)
    assert sched.n_preemptions > 0
    assert got == fresh


def test_the_prefix_cache_refuses_a_model_with_per_slot_state(tiny):
    from theanompi_tpu.serving.scheduler import Scheduler

    eng = _engine(tiny, max_batch=2)
    with pytest.raises(ValueError, match="per-slot recurrent state"):
        Scheduler(eng, prefix_cache=True)
    with pytest.raises(ValueError, match="no recurrent state"):
        eng.prefill([1, 2], list(range(12)), prefix_len=8)


def test_decode_tags_count_held_experts_on_the_device(tiny):
    from theanompi_tpu.telemetry import spans

    cfg = tiny[0]
    work = [(i, list(range(3 + i, 9 + 2 * i)), 4) for i in range(2)]
    _serve(_engine(tiny, max_batch=3), work)
    tags = [r.tags for r in spans.snapshot() if r.name == "serve.decode"][-3:]
    n_e = cfg["hybrid_override_pattern"].count("E")
    # the counters reach the host with the tokens, one step after the
    # launch: the first of the three launches had no step to read
    assert "moe_local_hits" not in tags[0]
    for t in tags[1:]:
        assert 0 <= t["moe_local_hits"] <= t["batch"] * n_e * cfg["num_experts_per_tok"]
        assert 0 <= t["moe_load_peak"] <= t["batch"]
        assert (t["moe_local_hits"] > 0) == (t["moe_load_peak"] > 0)


def test_the_grouped_product_kernel_decodes_the_tokens_ragged_dot_does(tiny):
    """``decode_kernel`` resolves the expert products where it resolves the
    decode attention: "on" runs the kernel (the interpreter here), "off"
    and, off the TPU, "auto" run ``lax.ragged_dot``; the tokens are the
    same and ``serve.decode`` / ``serve.prefill`` say which ran."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.telemetry import spans

    cfg, _, params = tiny
    n_e = cfg["hybrid_override_pattern"].count("E")
    rng = np.random.RandomState(28)
    work = [(i, rng.randint(0, cfg["vocab_rows_held"], size=n).tolist(), m)
            for i, (n, m) in enumerate([(9, 6), (21, 5), (5, 7)])]
    got = {}
    for variant, impl, ran in [("on", "kernel_interpret", 2 * n_e),
                               ("off", "ragged_dot", 0),
                               ("auto", "ragged_dot", 0)]:
        model = HybridLM(arch.model_config(cfg))  # an engine sets its model
        eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                              decode_kernel=variant)
        assert eng.expert_impl == impl == model.expert_layer.products
        assert eng.resolved_paths()["expert_products"] == impl
        assert model.resolved_paths()["experts"] == impl
        seq0 = max((r.seq for r in spans.snapshot()), default=-1)
        got[variant] = _serve(eng, work)[0]
        mine = [r for r in spans.snapshot() if r.seq > seq0
                and r.name in ("serve.decode", "serve.prefill")]
        assert {r.name for r in mine} == {"serve.decode", "serve.prefill"}
        for r in mine:
            assert r.tags["moe_products"] == 2 * n_e
            assert r.tags["moe_kernel_products"] == ran
    assert got["on"] == got["off"] == got["auto"]
    assert all(len(toks) == m for (_, _, m), toks
               in zip(work, got["on"].values()))


def test_a_model_without_an_expert_layer_gains_no_tag():
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = HybridLM({"pattern": "M*", "dim": 32, "vocab": 50, "seq_len": 16})
    assert model.expert_layer is None and model.expert_products == 0
    eng = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2, decode_kernel="on")
    assert eng.expert_impl is None and eng._moe_tags == {}
    assert "expert_products" not in eng.resolved_paths()


def test_device_scopes_name_the_new_layers(tiny):
    eng = _engine(tiny, max_batch=2)
    b, i32 = 2, jnp.int32
    text = eng._decode_fn.lower(
        eng.params, eng._k, eng._v, jnp.zeros((b, eng.max_blocks_per_seq), i32),
        jnp.zeros((b,), i32), jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key, eng._state).as_text(debug_info=True)
    for scope in ("embed", "mamba", "moe.route", "moe.experts", "moe.shared",
                  "attn", "head", "sample"):
        assert re.search(rf'loc\("[^"]*\b{re.escape(scope)}[/"]', text), scope
    assert "module @jit__decode_impl" in text


# -- the other model keeps its programs ------------------------------------------------

#: sha256 (first 16 hex) of the StableHLO text TransformerLM's serving steps
#: lowered to at the parent of PR 27 (commit 7a40a8e, jax 0.9.0), at
#: ``hlo_audit``'s two serving configurations; the paged-decode call's
#: serialized kernel body is left out of the text (it embeds source lines).
#: A PR that means to change these programs replaces the hashes.
#: Replaced since commit c10db4e: ``fallback/prefill`` (0c1dad5a85c7e652
#: there), whose pool holds 2 K/V heads, which ``write_prefill`` now writes
#: a token row at a time (``prefill_write_form``) so that XLA does not re-lay
#: the pools out around the scatter; ``kernel/prefill``'s 8 heads keep the
#: whole-block write, and no decode program moved.
GOLDEN = {"fallback/decode": "b105afd5f576134c",
          "fallback/prefill": "7ad3557c24905450",
          "kernel/decode": "319206e897e6ae95",
          "kernel/prefill": "f9bae97553af55ad"}


@pytest.mark.parametrize("variant", ["fallback", "kernel"])
def test_transformer_lm_serving_lowers_to_the_programs_it_had(variant):
    """The cache built from ``cache_spec()``, the trailing state argument
    and the cast to a stated weight dtype change nothing for a model that
    has neither state nor a stated dtype."""
    from theanompi_tpu.analysis import hlo_audit
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine

    kernel = variant == "kernel"
    model = TransformerLM(dict(hlo_audit.SERVE_KERNEL_CFG if kernel
                               else hlo_audit.SERVE_MODEL_CFG))
    assert model.cache_spec() == {
        "kv": {"layers": 2, "heads": model.config["heads"],
               "head_dim": model.config["dim"] // model.config["heads"]},
        "state": {}, "state_layers": 0}
    params, _ = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                          decode_kernel="on" if kernel else "off")
    assert eng.params is params and not eng.stateful
    if kernel:
        eng.decode_impl = "kernel"  # the compiled call, lowered for the TPU
    b, i32 = eng.max_batch, jnp.int32
    steps = {
        "decode": (eng._decode_impl, (
            eng.params, eng._k, eng._v, jnp.zeros((b, eng.max_blocks_per_seq), i32),
            jnp.zeros((b,), i32), jnp.zeros((b,), i32),
            jnp.zeros((b,), jnp.float32), jnp.zeros((b,), i32), eng._base_key)),
        "prefill": (eng._prefill_impl, (
            eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
            jnp.zeros((16,), i32), jnp.asarray(5, i32),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32), eng._base_key)),
    }
    for name, (fn, args) in steps.items():
        traced = jax.jit(fn, donate_argnums=(1, 2)).trace(*args)
        # only the decode step holds the kernel: it alone lowers for the TPU
        text = (traced.lower(lowering_platforms=("tpu",))
                if kernel and name == "decode" else traced.lower()).as_text()
        text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                      'backend_config = ""', text)
        got = hashlib.sha256(text.encode()).hexdigest()[:16]
        assert got == GOLDEN[f"{variant}/{name}"], (variant, name)
