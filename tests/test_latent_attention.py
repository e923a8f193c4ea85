"""``HybridLM``'s latent-attention layer (``L``: one cached row ``[c_kv |
k_pe]`` a token and layer, absorbed decode, decompressed prefill) and its
bias-corrected sigmoid experts against the plain reference
(``benchmarks/arch/joyai_llm_flash_reference.py``) on the CPU at small
widths, with seeded weights: the ops alone, the paged latent kernel under
the interpreter against the gather, the model through the engine's cache
and the scheduler's slots, the expert share, and the cache's spec."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))

from joyflash_tiny import tiny_config  # noqa: E402

from benchmarks.arch import joyai_llm_flash as arch  # noqa: E402
from benchmarks.arch import joyai_llm_flash_reference as ref  # noqa: E402

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration (dim 256, 4 heads, q_lora 96, kv_lora 64,
    nope 32, rope 16, v 32, 16 router experts top 4 with 8 held, a shared
    expert, route scale 2.5, a dense first layer, vocabulary 512: pattern
    ``L-LELE``) computed in float32 (the weights are bf16 values either
    way), its program model and that model's weights."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = tiny_config()
    cfg["run"].update(precision="fp32", weights="fp32")
    model = HybridLM(arch.model_config(cfg))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          arch.seeded_params(model, cfg, SEED))
    return cfg, model, params


def _tokens(cfg, shape, seed=0):
    return np.random.RandomState(seed).randint(0, cfg["vocab_size"], size=shape)


# -- the ops alone ---------------------------------------------------------------

def _hf_interleaved(x, positions, theta):
    """The ``transformers`` DeepSeek-V3 rule, written out in numpy: ``view(d
    / 2, 2).transpose`` (evens, then odds), then rotate-half with ``cos =
    [cos f ; cos f]``, ``f_i = theta ** (-2 i / d)``."""
    *lead, d = x.shape
    x = x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)
    inv = (1.0 / theta ** (np.arange(0, d, 2) / d)).astype(np.float32)
    ang = positions[:, None, None] * inv           # float32 angles, as served
    cos, sin = np.cos(np.concatenate([ang] * 2, -1)), np.sin(
        np.concatenate([ang] * 2, -1))
    half = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def test_interleaved_rotary_is_the_hf_rule_and_turns_adjacent_pairs():
    from theanompi_tpu.ops.attention import rotary

    rng = np.random.RandomState(0)
    pos = np.array([0, 1, 5, 300, 9999], np.float32)
    q = rng.randn(5, 3, 16).astype(np.float32)
    k = rng.randn(5, 1, 16).astype(np.float32)
    got_q, got_k = rotary(jnp.asarray(q), jnp.asarray(k), jnp.asarray(pos),
                          3.2e7, interleave=True)
    np.testing.assert_allclose(got_q, _hf_interleaved(q, pos, 3.2e7),
                               atol=2e-5)
    np.testing.assert_allclose(got_k, _hf_interleaved(k, pos, 3.2e7),
                               atol=2e-5)
    # pair (2i, 2i + 1) turns as one complex number at frequency i
    z = (q[..., 0::2] + 1j * q[..., 1::2]) * np.exp(
        1j * pos[:, None, None] * (3.2e7 ** (-np.arange(8) / 8)).astype(
            np.float32))
    np.testing.assert_allclose(got_q[..., :8], z.real, atol=2e-5)
    np.testing.assert_allclose(got_q[..., 8:], z.imag, atol=2e-5)
    # the scores a query and a key turned alike meet are the pairs' own
    assert np.allclose(np.einsum("thd,tgd->thg", got_q, got_k),
                       np.einsum("thd,tgd->thg", _hf_interleaved(q, pos, 3.2e7),
                                 _hf_interleaved(k, pos, 3.2e7)), atol=1e-4)


def _mla(**kw):
    from theanompi_tpu.ops.attention import LatentAttention

    args = dict(dim=64, heads=4, q_lora=24, kv_lora=32, qk_nope=16,
                qk_rope=8, v_head=16, rope_theta=1e4, rope_interleave=True)
    args.update(kw)
    return LatentAttention(**args)


def test_the_absorbed_decode_is_the_decompressed_attention():
    """In float32: ``q_nope W_UK^T`` against the cached rows and ``o_lat
    W_UV`` give every position the context the decompressed keys and
    values give it, through the paged pool by the gather and by the
    interpreted kernel."""
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    mla = _mla()
    p = mla.init(jax.random.PRNGKey(0), (64,))[0]
    t = 24
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 64))
    positions = jnp.arange(t)[None]
    row, q_nope, q_pe = mla.project_latent(p, x, positions)
    assert row.shape == (1, t, 40) and q_pe.shape == (1, t, 4, 8)
    want = mla.attend(p, row, q_nope, q_pe)[0]              # [T, H, v]
    cache = PagedKVCache.create(1, 4, 8, None, None, 1, 32, jnp.float32,
                                latent=mla.latent)
    cache = cache.write_prefill(0, row, None, jnp.array([1, 2, 3]))
    cache = cache.with_tables([[1, 2, 3, 0]])
    q = mla.absorb(p, q_nope[0], q_pe[0])                  # [T, H, latent]
    for impl in ("fallback", "kernel_interpret"):
        c = PagedKVCache(cache.k, None, cache.block_tables, 8,
                         decode_impl=impl)
        got = jnp.stack([
            mla.expand(p, c.attend_latent(0, q[i:i + 1], jnp.array([i]),
                                          values=32, scale=mla.scale))[0]
            for i in range(t)])
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_w_uk_and_w_uv_are_views_of_the_one_leaf():
    mla = _mla()
    p = mla.init(jax.random.PRNGKey(0), (64,))[0]
    assert set(p) == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o"}
    w = np.asarray(p["kv_b"]["w"]).reshape(32, 4, 32)
    q_nope = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 16))
    q_pe = jnp.zeros((3, 4, 8))
    np.testing.assert_allclose(
        mla.absorb(p, q_nope, q_pe)[..., :32],
        np.einsum("bhn,chn->bhc", q_nope, w[..., :16]), atol=1e-5)
    o = jax.random.normal(jax.random.PRNGKey(3), (3, 4, 32))
    np.testing.assert_allclose(mla.expand(p, o),
                               np.einsum("bhc,chv->bhv", o, w[..., 16:]),
                               atol=1e-5)


# -- the paged latent kernel -------------------------------------------------------

BS, NB, V, H = 4, 8, 32, 8


@pytest.mark.parametrize("per", [1, 2, 8])
def test_the_latent_kernel_is_the_gather_at_ragged_lengths(per):
    """Under the interpreter, over a two-layer pool at layer 1: slots at the
    first token, a block's last and first position, a step boundary and the
    table's end; an inactive slot on the null block.  The two sum in
    another order: a few float32 rounding steps."""
    from theanompi_tpu.ops.pallas_paged_attention import (
        paged_attend_decode_latent,
    )
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    rng = np.random.RandomState(per)
    positions = np.array([1, 3, 4, 15, 31, 0], np.int32)
    num = 6 * NB + 1
    pool = jnp.asarray(rng.randn(2, num, BS, 128), jnp.float32)
    tables = np.zeros((6, NB), np.int32)
    free = iter(rng.permutation(np.arange(1, num)))
    for i, p in enumerate(positions):
        if p:
            tables[i, :p // BS + 1] = [next(free) for _ in range(p // BS + 1)]
    q = jnp.asarray(rng.randn(6, H, 128), jnp.float32)
    got = paged_attend_decode_latent(pool, 1, jnp.asarray(tables),
                                     jnp.asarray(positions), q, block_size=BS,
                                     values=V, scale=0.3, interpret=True,
                                     blocks_per_step=per)
    ref_ = PagedKVCache(pool, None, jnp.asarray(tables), BS)._attend_decode_latent(
        1, q, jnp.asarray(positions), V, 0.3)
    assert got.shape == (6, H, V)
    np.testing.assert_allclose(got, ref_, atol=1e-5, rtol=1e-5)
    # by hand for one slot: softmax of scaled scores over its keys
    rows = np.asarray(pool[1])[tables[3]].reshape(-1, 128)[:16]
    s = 0.3 * np.asarray(q[3]) @ rows.T
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    np.testing.assert_allclose(got[3], pr @ rows[:, :V], atol=1e-5)


def test_the_latent_kernels_gate():
    from theanompi_tpu.ops.pallas_paged_attention import (
        paged_decode_latent_supported,
    )

    assert paged_decode_latent_supported(32, 640, 512, 16, jnp.bfloat16)
    assert paged_decode_latent_supported(32, 640, 512, 8, jnp.float32)
    assert not paged_decode_latent_supported(4, 640, 512, 16)     # sublanes
    assert not paged_decode_latent_supported(32, 640, 500, 16)    # lanes
    assert not paged_decode_latent_supported(32, 512, 512, 16)    # no rope
    assert not paged_decode_latent_supported(32, 640, 512, 8)     # bf16 block
    assert not paged_decode_latent_supported(32, 640, 512, 16, jnp.int8)


# -- the cache's spec -------------------------------------------------------------

def test_the_cache_spec_and_the_pool_of_a_latent_model(tiny):
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    cfg, model, _ = tiny
    spec = model.cache_spec()
    assert spec == {"kv": {"layers": 3, "latent": 80, "values": 64},
                    "state": {}, "state_layers": 0}
    cache = PagedKVCache.from_spec(spec, num_blocks=5, block_size=8,
                                   max_batch=2, max_context=64,
                                   dtype=jnp.bfloat16)
    # one pool, rows padded to whole lanes, no V pool
    assert cache.latent and cache.v is None
    assert cache.k.shape == (3, 5, 8, 128) and cache.k.dtype == jnp.bfloat16
    assert ref.cache_bytes_per_token(cfg) == 3 * 80 * 2
    # every pattern without an L keeps the spec it had
    for kw, want in [
            ({"pattern": "MEM*E"}, {"layers": 1, "heads": 2, "head_dim": 32}),
            ({"pattern": "*-*-", "loops": 3},
             {"layers": 6, "heads": 2, "head_dim": 32}),
            ({"pattern": "ME"}, {"layers": 1, "heads": 2, "head_dim": 32}),
            ({"pattern": "*-wE", "kv_heads": 4, "head_dim": 8},
             {"layers": 1, "heads": 4, "head_dim": 8})]:
        assert HybridLM(kw).cache_spec()["kv"] == want, kw


@pytest.mark.parametrize("kw", [{"pattern": "L*"}, {"pattern": "LwE"},
                                {"pattern": "L-", "loops": 2},
                                {"pattern": "LE", "block_len": 4,
                                 "mask_id": 3}])
def test_latent_attention_is_the_models_one_paged_pool(kw):
    from theanompi_tpu.models.hybrid_lm import HybridLM

    with pytest.raises(ValueError, match="latent attention"):
        HybridLM(kw)


# -- the expert layer's share --------------------------------------------------------

def test_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """16 router experts top 4, the bias-corrected sigmoid router, route
    scale 2.5, a shared expert: four chips' routed parts over the same
    weights, each holding 4, plus the shared expert counted once, add up to
    the uncut layer."""
    from theanompi_tpu.ops.moe import DroplessMoE

    def layer(held):
        return DroplessMoE(64, 16, 4, None, 32, 32, 2.5, held,
                           activation="silu_gated", router="sigmoid")

    whole = layer(None)
    p = whole.init(jax.random.PRNGKey(0), (64,))[0]
    p["router"]["b_corr"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4),
                                                     (16,))
    u = jax.random.normal(jax.random.PRNGKey(1), (37, 64))
    want, _ = whole.apply_tokens(p, u)
    total = whole.apply_tokens(p, u, routed=False)[0]
    for lo in range(0, 16, 4):
        share = dict(p, w1=p["w1"][lo:lo + 4], w2=p["w2"][lo:lo + 4])
        total = total + layer((lo, lo + 4)).apply_tokens(share, u,
                                                         shared=False)[0]
    np.testing.assert_allclose(total, want, atol=1e-5)
    # the correction bias selects; the weights are the unbiased scores
    idx, w = whole.route(p, u)
    s = jax.nn.sigmoid(u @ p["router"]["w"])
    np.testing.assert_array_equal(
        np.sort(idx, -1), np.sort(jax.lax.top_k(s + p["router"]["b_corr"],
                                                4)[1], -1))
    np.testing.assert_allclose(
        w, 2.5 * np.take_along_axis(s, idx, -1)
        / np.take_along_axis(s, idx, -1).sum(-1, keepdims=True), rtol=1e-5)


# -- the model through the engine and the scheduler ---------------------------------

def _engine(tiny, **kw):
    from theanompi_tpu.serving.engine import InferenceEngine

    _, model, params = tiny
    return InferenceEngine(model, params, block_size=8, **kw)


def test_apply_logits_is_the_references_forward(tiny):
    cfg, model, params = tiny
    toks = _tokens(cfg, (2, 32), seed=1)
    want = ref.logits(cfg, SEED, toks)
    got = model.apply_logits(params, {}, jnp.asarray(toks))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("decode_kernel", ["off", "on"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        tiny, decode_kernel):
    """Logits, not tokens: a prompt padded to its bucket, then 9 decode
    steps through the latent pool, against the reference's one full
    forward over the same tokens; by the gather and by the interpreted
    kernel."""
    cfg = tiny[0]
    eng = _engine(tiny, max_batch=2, decode_kernel=decode_kernel)
    assert eng.decode_impl == {"off": "fallback",
                               "on": "kernel_interpret"}[decode_kernel]
    assert eng._k.shape == (3, 2 * 8 + 1, 8, 128) and eng._v is None
    prompt = _tokens(cfg, 13, seed=3).tolist()
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
    padded = np.zeros((1, 32), np.int32)
    padded[0, :len(toks) - 1] = toks[:-1]
    want = ref.logits(cfg, SEED, padded)[0, len(prompt) - 1:len(toks) - 1]
    np.testing.assert_allclose(np.stack(rows), want, atol=2e-5, rtol=0)
    assert toks[len(prompt):] == np.argmax(want, axis=-1).tolist()


def _serve(eng, requests):
    from theanompi_tpu.serving.scheduler import Request, Scheduler

    sched = Scheduler(eng)
    reqs = [Request(rid=rid, prompt=list(prompt), max_new_tokens=n,
                    temperature=0.0) for rid, prompt, n in requests]
    for r in reqs:
        sched.submit(r)
    while not sched.idle:
        sched.step()
    return {r.rid: list(r.generated) for r in reqs}, sched


def test_the_scheduler_serves_the_references_greedy_tokens(tiny):
    """Five requests through two slots (every later one in a used slot) and
    through a pool too small for three (preemption and recompute): the
    served tokens are the reference's greedy continuation, by logits."""
    from theanompi_tpu.telemetry import spans

    cfg = tiny[0]
    rng = np.random.RandomState(11)
    work = [(i, rng.randint(0, 512, size=n).tolist(), m)
            for i, (n, m) in enumerate([(9, 12), (21, 7), (5, 16), (14, 10)])]
    spans.RING.clear()
    got, sched = _serve(_engine(tiny, max_batch=2, decode_kernel="on"), work)
    tight, sched2 = _serve(_engine(tiny, max_batch=3, num_blocks=6), work)
    assert sched2.n_preemptions > 0 and tight == got
    sample = [(prompt, got[rid]) for rid, prompt, _ in work]
    gaps = arch.served_gaps(dict(cfg, check=dict(cfg["check"],
                                                 routing_margin=0.0)),
                            SEED, [(p, g) for p, g in sample])
    assert gaps["tokens_compared"] == 12 + 7 + 16 + 10
    assert gaps["widest_logit_gap"] < 1e-4
    # the tags the decode and prefill spans carry
    decodes = [r for r in spans.snapshot() if r.name == "serve.decode"]
    prefills = [r for r in spans.snapshot() if r.name == "serve.prefill"]
    assert decodes and prefills
    kernel = [r.tags for r in decodes if r.tags.get("latent_kernel_layers")]
    gather = [r.tags for r in decodes if "latent_kernel_layers" in r.tags
              and not r.tags["latent_kernel_layers"]]
    assert kernel and gather
    assert all(t["latent_layers"] == 3 and t["latent_kernel_layers"] == 3
               and t["paged_layers"] == 0 for t in kernel)
    assert all(r.tags["latent_layers"] == 3 and "latent_kernel_layers"
               not in r.tags and r.tags["pool_writes"] == 3 for r in prefills)


def test_the_engine_names_the_latent_paths(tiny):
    eng = _engine(tiny, max_batch=2, decode_kernel="on")
    paths = eng.resolved_paths()
    assert paths["decode_attention_call"] == "paged_decode_latent"
    assert paths["prefill_kv_write"] == "whole_blocks"
    assert paths["latent_attention"].startswith("one paged row of 80")
    assert eng._latent_tags == {"latent_layers": 3, "latent_kernel_layers": 3}
    off = _engine(tiny, max_batch=2, decode_kernel="off")
    assert off.decode_call is None
    assert off._latent_tags == {"latent_layers": 3, "latent_kernel_layers": 0}
