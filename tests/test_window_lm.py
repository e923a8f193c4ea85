"""``HybridLM`` with window (``w``) and full (``*``) attention layers of
different head counts over one cache, a gate a head, partial YaRN rotary
and gated experts at model width, against the plain reference
(``benchmarks/arch/laguna_reference.py``) on the CPU at small widths with
seeded weights: the ops alone, the model through ring and pool, the
scheduler's slots."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))

from laguna_tiny import tiny_config  # noqa: E402

from benchmarks.arch import laguna as arch  # noqa: E402
from benchmarks.arch import laguna_reference as ref  # noqa: E402

SEED = 2**31 + 5
WINDOW = 16  # the tiny configuration's sliding_window


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration computed in float32 (the weights are bf16
    values either way), its program model and that model's weights."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = tiny_config()
    cfg["run"].update(precision="fp32", weights="fp32")
    assert cfg["sliding_window"] == WINDOW
    model = HybridLM(arch.model_config(cfg))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          arch.seeded_params(model, cfg, SEED))
    return cfg, model, params


def _tokens(cfg, n, seed=0):
    return np.random.RandomState(seed).randint(0, cfg["vocab_size"], size=n)


# -- the ops alone ---------------------------------------------------------------

def _dense_band(q, k, v, window):
    """Masked softmax over the whole score matrix, float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    t = q.shape[1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(t, h=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (1, t, h, d), jnp.float32) for k in ks]


@pytest.mark.parametrize("t,window", [(24, 8), (24, 1), (24, 24), (24, 40),
                                      (64, 16)])
def test_the_band_in_blockwise_attention(t, window):
    from theanompi_tpu.parallel.ring_attention import blockwise_attention

    q, k, v = _qkv(t)
    for block in (None, 8):
        got = blockwise_attention(q, k, v, causal=True, block_size=block,
                                  window=window)
        np.testing.assert_allclose(got, _dense_band(q, k, v, window),
                                   atol=2e-6, rtol=2e-5)
    with pytest.raises(ValueError, match="causal"):
        blockwise_attention(q, k, v, causal=False, window=window)


@pytest.mark.parametrize("t,window,blocks", [
    (256, 64, (128, 128)),     # tiles wholly behind the band are skipped
    (256, 128, (128, 128)),    # the band one tile wide
    (256, 100, (128, 128)),    # and not a whole number of tiles
    (512, 130, (128, 256)),    # a key tile wider than the query tile
    (256, 300, (128, 128)),    # a band wider than the sequence: causal
    (256, 1, (128, 128)),      # the query alone
])
def test_the_band_in_the_flash_forward_kernel(t, window, blocks):
    """Under the interpreter, against a dense masked softmax: skipped
    tiles, straddling tiles and the clamped index maps."""
    from theanompi_tpu.ops.pallas_attention import flash_attention

    q, k, v = _qkv(t, seed=t + window)
    got = flash_attention(q, k, v, causal=True, block_q=blocks[0],
                          block_k=blocks[1], interpret=True, window=window)
    np.testing.assert_allclose(got, _dense_band(q, k, v, window),
                               atol=2e-5, rtol=2e-4)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, interpret=True, window=window)


def test_the_kernel_without_a_window_is_the_kernel_as_it_was():
    """``window=None`` takes the differentiable path the kernel had, and a
    band as wide as the sequence is causal attention."""
    from theanompi_tpu.ops.pallas_attention import flash_attention

    q, k, v = _qkv(256)
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b = flash_attention(q, k, v, causal=True, interpret=True, window=256)
    np.testing.assert_allclose(a, _dense_band(q, k, v, None), atol=2e-5)
    np.testing.assert_allclose(a, b, atol=1e-6)
    g = jax.grad(lambda q: flash_attention(q, k, v, causal=True,
                                           interpret=True).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def _rotary64(x, positions, theta, share, yarn):
    """The direct formula in float64 numpy: ``x`` ``[T, H, Dh]``."""
    x = np.asarray(x, np.float64)
    hd = x.shape[-1]
    rot = int(hd * share)
    i = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rot)
    scale = 1.0
    if yarn is not None:
        orig, factor = yarn["original_max_position"], yarn["factor"]

        def dim_of(turns):
            return rot * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
        high = min(math.ceil(dim_of(yarn["beta_slow"])), rot - 1)
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        f = f / factor * ramp + f * (1.0 - ramp)
        scale = yarn.get("attention_factor", 0.1 * math.log(factor) + 1.0)
    ang = np.asarray(positions, np.float64)[:, None] * f[None, :]
    cos, sin = scale * np.cos(ang)[:, None, :], scale * np.sin(ang)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]],
                          axis=-1)


@pytest.mark.parametrize("share,yarn", [
    (1.0, None),
    (0.5, None),
    (0.5, dict(factor=64, original_max_position=4096, beta_fast=64,
               beta_slow=1, attention_factor=1.4158883083359672)),
    (0.5, dict(factor=8, original_max_position=32, beta_fast=4, beta_slow=1)),
    (1.0, dict(factor=4, original_max_position=64, beta_fast=8, beta_slow=2)),
])
def test_partial_and_yarn_rotary_against_the_direct_formula(share, yarn):
    from theanompi_tpu.ops.attention import rotary, yarn_inv_freq

    theta = 5e5
    q = jax.random.normal(jax.random.PRNGKey(0), (9, 3, 128), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (9, 1, 128), jnp.float32)
    positions = np.array([0, 1, 2, 7, 40, 511, 512, 4095, 16000])
    gq, gk = rotary(q, k, positions, theta, share, yarn)
    # float32 angles of up to 16 000 radians: 1e-3 of a turn
    np.testing.assert_allclose(gq, _rotary64(q, positions, theta, share, yarn),
                               atol=5e-3)
    np.testing.assert_allclose(gk, _rotary64(k, positions, theta, share, yarn),
                               atol=5e-3)
    if share < 1.0:  # the other dims pass unrotated, to the bit
        rot = int(128 * share)
        np.testing.assert_array_equal(np.asarray(gq)[..., rot:],
                                      np.asarray(q)[..., rot:])
    if yarn is not None and yarn["factor"] == 64:
        # the published rule: 32 frequencies, the ramp from dim 5 to 16
        f = np.asarray(yarn_inv_freq(theta, 64, 64, 4096, 64, 1), np.float64)
        plain = theta ** (-np.arange(32) / 32.0)
        np.testing.assert_allclose(f[:6], plain[:6], rtol=1e-6)
        np.testing.assert_allclose(f[16:], plain[16:] / 64, rtol=1e-6)
        assert (f[6:16] < plain[6:16]).all() and (
            f[6:16] > plain[6:16] / 64).all()
        assert yarn["attention_factor"] == pytest.approx(
            0.1 * math.log(64) + 1.0)


def _per_token_gated(layer, p, u):
    """``DroplessMoE(latent=None, activation="silu_gated")`` a token and an
    expert at a time, float64."""
    p = jax.tree.map(lambda x: np.asarray(x, np.float64), p)
    u = np.asarray(u, np.float64)
    def ffn(w_in, w_out, x):
        gate, up = np.split(x @ w_in, 2)
        return (gate / (1 + np.exp(-gate)) * up) @ w_out

    out = np.zeros_like(u)
    for n, x in enumerate(u):
        s = 1.0 / (1.0 + np.exp(-(x @ p["router"]["w"])))
        top = np.argsort(-(s + p["router"]["b_corr"]), kind="stable")[:layer.top_k]
        for e in top:
            out[n] += (layer.route_scale * s[e] / s[top].sum()
                       * ffn(p["w1"][e], p["w2"][e], x))
        out[n] += ffn(p["shared"]["v1"], p["shared"]["v2"], x)
    return out


@pytest.mark.parametrize("products", ["ragged_dot", "kernel_interpret"])
def test_gated_experts_at_model_width_against_a_per_token_loop(products):
    from theanompi_tpu.ops.moe import DroplessMoE

    layer = DroplessMoE(32, 8, 3, None, 16, 24, route_scale=2.5,
                        products=products, activation="silu_gated")
    p = layer.init(jax.random.PRNGKey(3), (32,))[0]
    assert "down" not in p and "up" not in p
    assert p["w1"].shape == (8, 32, 32) and p["w2"].shape == (8, 16, 32)
    assert p["shared"]["v1"].shape == (32, 48)
    assert layer.product_shapes == ((32, 32), (16, 32))
    # a router that tells the experts apart, and a bias on the selection
    p["router"]["w"] = 10.0 * p["router"]["w"]
    p["router"]["b_corr"] = jnp.linspace(0.5, 0.0, 8)
    u = jax.random.normal(jax.random.PRNGKey(4), (21, 32), jnp.float32)
    got, stats = layer.apply_tokens(p, u)
    np.testing.assert_allclose(got, _per_token_gated(layer, p, u),
                               atol=2e-6, rtol=2e-5)
    assert int(stats["local_hits"]) == 21 * 3


def _latent_relu2_as_it_was(layer, params, u):
    """``DroplessMoE.apply_tokens`` of the latent relu² layer, the lines it
    had before it learnt another width and activation (PR 32)."""
    from jax import lax

    n = u.shape[0]
    lo, hi = layer.held
    e_held = hi - lo
    idx, w = layer.route(params, u)
    lat = u @ params["down"]["w"].astype(u.dtype)
    local = idx - lo
    is_held = (local >= 0) & (local < e_held)
    eid = jnp.where(is_held, local, e_held).reshape(-1)
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.bincount(eid, length=e_held + 1)[:e_held].astype(jnp.int32)
    rows = jnp.take(lat, order // layer.top_k, axis=0)
    h = lax.ragged_dot(rows, params["w1"].astype(u.dtype), sizes,
                       preferred_element_type=u.dtype)
    h = jnp.square(jax.nn.relu(h.astype(jnp.float32)))
    y = lax.ragged_dot(h.astype(u.dtype), params["w2"].astype(u.dtype), sizes,
                       preferred_element_type=jnp.float32)
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(n, layer.top_k,
                                                        layer.latent)
    r = jnp.sum(jnp.where(is_held[..., None], y * w[..., None], 0.0), axis=1)
    out = r.astype(u.dtype) @ params["up"]["w"].astype(u.dtype)
    hs = jnp.square(jax.nn.relu(u @ params["shared"]["v1"].astype(u.dtype)))
    return out + hs @ params["shared"]["v2"].astype(u.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_latent_relu2_arguments_give_the_layer_as_it_was(dtype):
    """The other architecture's arguments: the same parameters from the same
    key and, to the bit, the same output."""
    from theanompi_tpu.ops.moe import DroplessMoE

    layer = DroplessMoE(32, 16, 4, 8, 24, 40, route_scale=5.0,
                        experts_held=(4, 12))
    assert layer.activation == "relu2" and layer.width == 8
    assert layer.product_shapes == ((8, 24), (24, 8))
    p = layer.init(jax.random.PRNGKey(5), (32,))[0]
    assert {k: v.shape for k, v in p.items() if k in ("w1", "w2")} == {
        "w1": (8, 8, 24), "w2": (8, 24, 8)}
    assert p["down"]["w"].shape == (32, 8) and p["up"]["w"].shape == (8, 32)
    assert p["shared"]["v1"].shape == (32, 40)
    ks = jax.random.split(jax.random.PRNGKey(5), 7)  # the draws it had
    np.testing.assert_array_equal(
        p["down"]["w"], 0.02 * jax.random.normal(ks[1], (32, 8), jnp.float32))
    np.testing.assert_array_equal(
        p["up"]["w"], 0.02 * jax.random.normal(ks[4], (8, 32), jnp.float32))
    u = jax.random.normal(jax.random.PRNGKey(6), (19, 32), jnp.float32)
    u, p = u.astype(dtype), jax.tree.map(lambda x: x.astype(dtype), p)
    got, _ = layer.apply_tokens(p, u)
    want = _latent_relu2_as_it_was(layer, p, u)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    with pytest.raises(ValueError, match="activation"):
        DroplessMoE(32, 16, 4, 8, 24, 40, activation="gelu")


# -- the model against the reference ------------------------------------------------

def test_apply_logits_is_the_reference(tiny):
    """Float32 on both sides: what is left is the order of the sums (1e-6
    on logits of 0.8), the tolerance ten times that."""
    cfg, model, params = tiny
    toks = np.stack([_tokens(cfg, 64, s) for s in (1, 2)])
    old, ref.QUERY_BLOCK = ref.QUERY_BLOCK, 32
    try:
        want = np.asarray(ref.logits(cfg, SEED, toks))
        # each term of the mathematics moves the reference by more than that
        for drop in ("gate", "window", "shared", "route_scale", "yarn_factor"):
            moved = np.abs(np.asarray(ref.logits(cfg, SEED, toks, drop=drop))
                           - want).max()
            assert moved > (1e-4 if drop == "yarn_factor" else 1e-2), drop
    finally:
        ref.QUERY_BLOCK = old
    got = np.asarray(model.apply_logits(params, {}, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_program_counts_the_models_parameters(tiny):
    cfg, model, params = tiny
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    routers = cfg["mlp_layer_types"].count("sparse") * cfg["num_experts"]
    assert n == ref.parameter_count(cfg) + routers  # + b_corr, zero
    assert model.config["pattern"] == "*-wE*E"
    assert [k for _, k in model.layers] == ["attn", "mlp", "attn_w", "moe",
                                            "attn", "moe"]
    gates = {name: params[name]["mixer"]["gate"]["w"].shape
             for name, k in model.layers if k.startswith("attn")}
    assert gates == {"00_attn": (64, 4), "02_attn_w": (64, 8),
                     "04_attn": (64, 4)}


def test_the_cache_keeps_a_window_layers_kv_apart(tiny):
    """``cache_spec()`` and the cache's shapes: a window layer costs its
    window a slot (at most 528 tokens at the served 512), the pool holds
    the full layers only."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    cfg, model, _ = tiny
    spec = model.cache_spec()
    assert spec["kv"] == {"layers": 2, "heads": 2, "head_dim": 16}
    assert spec["window"] == {"layers": 1, "size": WINDOW, "heads": 2,
                              "head_dim": 16}
    cache = PagedKVCache.from_spec(spec, num_blocks=9, block_size=8,
                                   max_batch=3, max_context=64)
    assert cache.k.shape == (2, 9, 8, 2, 16)
    assert cache.state["window_k"].shape == (1, 3, WINDOW, 2, 16) \
        == cache.state["window_v"].shape
    served = HybridLM(dict(pattern="*-wEwEwE*E", window=512, kv_heads=8,
                           head_dim=128, seq_len=16384)).cache_spec()
    assert served["window"]["size"] <= 512 + 16 and served["window"]["layers"] == 3
    assert served["kv"]["layers"] == 2
    # a model without a ``w`` asks for what it asked for
    assert "window" not in HybridLM(dict(pattern="ME*")).cache_spec()
    with pytest.raises(ValueError, match="loops"):
        HybridLM(dict(pattern="w-", loops=2))


def _engine(tiny, **kw):
    from theanompi_tpu.serving.engine import InferenceEngine

    cfg, model, params = tiny
    kw = {"block_size": 8, "max_batch": 3, "num_blocks": 40, **kw}
    return InferenceEngine(model, params, **kw)


def _serve(eng, toks, prompt_len, slot, row, upto=None):
    """Prefill ``toks[:prompt_len]`` into ``slot`` over blocks ``row`` and
    decode the rest, teacher-forced; -> logits at positions ``prompt_len -
    1 ..``."""
    upto = len(toks) if upto is None else upto
    _, last = eng.prefill(row[:-(-prompt_len // eng.block_size)],
                          toks[:prompt_len].tolist(), slot=slot)
    out = [last]
    b = eng.max_batch
    tables = np.zeros((b, eng.max_blocks_per_seq), np.int32)
    tables[slot, :len(row)] = row
    for p in range(prompt_len, upto):
        lengths, feed = np.zeros(b, np.int32), np.zeros(b, np.int32)
        lengths[slot], feed[slot] = p, toks[p]
        _, logits = eng.decode(tables, lengths, feed, np.zeros(b, np.float32),
                               np.zeros(b, np.int32))
        out.append(logits[slot])
    return np.stack(out)


@pytest.mark.parametrize("prompt_len", [WINDOW - 1, WINDOW, WINDOW + 1,
                                        3 * WINDOW, 5])
def test_prefill_then_decode_through_ring_and_pool_is_the_forward_pass(
        tiny, prompt_len):
    """Contexts below, at and beyond the window, decoding on until the
    context is 64: the ring wraps and the pool fills."""
    cfg, model, params = tiny
    toks = _tokens(cfg, 64, seed=prompt_len)
    want = np.asarray(model.apply_logits(params, {}, jnp.asarray(toks)[None]))[0]
    eng = _engine(tiny)
    got = _serve(eng, toks, prompt_len, slot=1, row=list(range(3, 11)))
    np.testing.assert_allclose(got, want[prompt_len - 1:], atol=1e-5)
    assert eng.resolved_paths()["window_attention"].startswith(
        f"slot ring of {WINDOW} tokens")


def test_a_slot_reused_by_a_shorter_request_reads_nothing_of_the_longer(tiny):
    cfg, model, params = tiny
    eng = _engine(tiny)
    long = _tokens(cfg, 64, seed=11)
    _serve(eng, long, 40, slot=2, row=list(range(1, 9)))  # wraps the ring
    short = _tokens(cfg, 30, seed=12)
    want = np.asarray(model.apply_logits(params, {}, jnp.asarray(short)[None]))[0]
    got = _serve(eng, short, 6, slot=2, row=list(range(20, 24)))
    np.testing.assert_allclose(got, want[5:], atol=1e-5)


def test_a_prefilled_slot_survives_a_step_it_sits_out(tiny):
    """A decode step in which a slot is inactive (position 0) leaves that
    slot's ring as its prefill wrote it."""
    cfg, model, params = tiny
    eng = _engine(tiny)
    toks = _tokens(cfg, 40, seed=21)
    eng.prefill(list(range(1, 6)), toks[:33].tolist(), slot=0)
    other = _tokens(cfg, 20, seed=22)
    _serve(eng, other, 9, slot=1, row=list(range(10, 14)))  # slot 0 sits out
    tables = np.zeros((3, eng.max_blocks_per_seq), np.int32)
    tables[0, :5] = range(1, 6)
    lengths, feed = np.array([33, 0, 0], np.int32), np.zeros(3, np.int32)
    feed[0] = toks[33]
    _, logits = eng.decode(tables, lengths, feed, np.zeros(3, np.float32),
                           np.zeros(3, np.int32))
    want = np.asarray(model.apply_logits(params, {}, jnp.asarray(toks)[None]))[0]
    np.testing.assert_allclose(logits[0], want[33], atol=1e-5)


def test_the_grouped_fallback_in_pieces_is_the_fallback_whole(tiny,
                                                              monkeypatch):
    """A table wider than the piece goes through piece by piece, joined by
    the online softmax: the same context to float32 rounding."""
    from theanompi_tpu.serving import kv_cache

    cfg, model, params = tiny
    toks = _tokens(cfg, 64, seed=31)
    whole = _serve(_engine(tiny), toks, 50, slot=0, row=list(range(1, 9)))
    monkeypatch.setattr(kv_cache, "_GROUPED_CHUNK_TOKENS", 16)
    pieces = _serve(_engine(tiny), toks, 50, slot=0, row=list(range(1, 9)))
    np.testing.assert_allclose(pieces, whole, atol=1e-5)
    want = np.asarray(model.apply_logits(params, {}, jnp.asarray(toks)[None]))[0]
    np.testing.assert_allclose(pieces, want[49:], atol=1e-5)


def test_preemption_and_re_prefill_leave_the_tokens_as_they_were(tiny):
    """A pool too small for every request: the scheduler preempts, the
    victim's prompt and tokens are prefilled again into whatever slot is
    free, ring and all, and every request's greedy tokens are those of the
    full forward pass."""
    from theanompi_tpu.serving.scheduler import Request, Scheduler

    cfg, model, params = tiny
    eng = _engine(tiny, num_blocks=14, max_batch=3)
    sched = Scheduler(eng)
    reqs = [Request(rid=i, prompt=_tokens(cfg, 10 + 7 * i, seed=40 + i).tolist(),
                    max_new_tokens=30) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    for _ in range(400):
        sched.step()
        if all(r.state == "done" for r in reqs):
            break
    assert all(r.state == "done" for r in reqs)
    assert sched.n_preemptions > 0
    # admission and preemption count the full layers' blocks only
    assert sched.pool.num_blocks == 14 and sched.pool.free_blocks == 13
    forward = jax.jit(lambda t: model.apply_logits(params, {}, t))
    for r in reqs:  # one padded shape: causal attention ignores the padding
        toks = list(r.prompt)
        for _ in range(r.max_new_tokens):
            padded = np.zeros((1, 64), np.int32)
            padded[0, :len(toks)] = toks
            toks.append(int(jnp.argmax(forward(padded)[0, len(toks) - 1])))
        assert toks[len(r.prompt):] == list(r.generated), r.rid


def test_prefill_reads_its_head_at_one_position(tiny):
    """The engine's prefill program holds no ``[bucket, vocab]`` logits;
    ``apply_logits`` still returns every position."""
    cfg, model, params = tiny
    eng = _engine(tiny)
    assert model.prefill_head_at and eng._head_at
    i32 = jnp.int32
    text = jax.jit(eng._prefill_impl).lower(
        eng.params, eng._k, eng._v, jnp.zeros((4,), i32),
        jnp.zeros((32,), i32), jnp.asarray(5, i32), jnp.asarray(0.0),
        jnp.asarray(1, i32), eng._base_key, eng._state,
        jnp.asarray(1, i32)).as_text()
    v = cfg["vocab_size"]
    assert f"tensor<1x{v}xf32>" in text and f"tensor<32x{v}x" not in text
    logits, _ = model.apply_prefill(params, {}, None, None,
                                    jnp.zeros((1, 32), i32))
    assert logits.shape == (1, 32, v)
    one, _ = model.apply_prefill(params, {}, None, None,
                                 jnp.zeros((1, 32), i32), head_at=jnp.int32(7))
    np.testing.assert_allclose(one[0, 0], logits[0, 7], atol=1e-6)
