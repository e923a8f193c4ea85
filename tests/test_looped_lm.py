"""``HybridLM`` as a looped stack (the pattern applied ``loops`` times with
shared weights, K/V per (loop step, layer), an exit gate, rotary positions,
sandwich RMSNorm, a gated FFN) against the plain reference
(``benchmarks/arch/ouro_reference.py``) on the CPU at small widths, with
seeded weights: the ops alone, the model through the engine's cache and the
scheduler's slots, and the programs the default configuration must keep."""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))

from ouro_tiny import tiny_cell  # noqa: E402

from benchmarks.arch import ouro as arch  # noqa: E402
from benchmarks.arch import ouro_reference as ref  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration (dim 64, 4 heads of 16, FFN 96, pattern
    ``*-*-``, 3 loops) computed in float32 (the weights are bf16 values
    either way), its program model and that model's weights."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = tiny_cell()["cfg"]
    cfg["run"].update(precision="fp32", weights="fp32")
    model = HybridLM(arch.model_config(cfg))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          arch.seeded_params(model, cfg, SEED))
    return cfg, model, params


def _tokens(cfg, shape, seed=0):
    return np.random.RandomState(seed).randint(0, cfg["vocab_size"], size=shape)


# -- the ops alone ---------------------------------------------------------------

def test_rotary_is_the_complex_rotation():
    """Pair ``(i, i + Dh/2)`` as one complex number, turned by ``position *
    theta ** (-2 i / Dh)``; position 0 is the identity; q.k depends on the
    distance alone."""
    from theanompi_tpu.ops.attention import rotary

    theta, hd = 1e4, 16
    rng = np.random.RandomState(0)
    q = rng.randn(2, 5, 3, hd).astype(np.float32)
    k = rng.randn(2, 5, 3, hd).astype(np.float32)
    pos = np.array([[0, 1, 2, 7, 300], [5, 6, 7, 8, 9]])
    gq, gk = rotary(jnp.asarray(q), jnp.asarray(k), jnp.asarray(pos), theta)
    turn = np.exp(1j * pos[..., None, None]
                  * theta ** (-2.0 * np.arange(hd // 2) / hd))
    for got, x in ((gq, q), (gk, k)):
        want = (x[..., :hd // 2] + 1j * x[..., hd // 2:]) * turn
        np.testing.assert_allclose(got[..., :hd // 2], want.real, atol=2e-5)
        np.testing.assert_allclose(got[..., hd // 2:], want.imag, atol=2e-5)
    np.testing.assert_array_equal(gq[0, 0], q[0, 0])
    a = np.einsum("hd,hd->h", gq[1, 0], gk[1, 3])      # positions 5 and 8
    fq, fk = rotary(jnp.asarray(q[1, :1]), jnp.asarray(k[1, 3:4]),
                    jnp.asarray([100]), theta)
    _, fk = rotary(fq, jnp.asarray(k[1, 3:4]), jnp.asarray([103]), theta)
    np.testing.assert_allclose(a, np.einsum("hd,hd->h", fq[0], fk[0]),
                               atol=1e-4)
    assert gq.dtype == jnp.float32
    assert rotary(jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
                  jnp.asarray(pos), theta)[1].dtype == jnp.bfloat16


def test_gated_ffn_and_the_sandwich_layer_are_the_references(tiny):
    cfg, model, params = tiny
    leaves = {n: x.astype(jnp.float32) for n, x in
              ref.leaves(cfg, ref.seed_key(SEED), "layer", 1).items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (9, cfg["hidden_size"]))
    got = model._mlp(params["03_mlp"]["mixer"], u)
    np.testing.assert_allclose(got, ref.ffn(ref._ein("fp32"), leaves, u),
                               atol=2e-6, rtol=2e-5)
    assert set(params["03_mlp"]["mixer"]) == {"gate", "up", "down"}
    assert set(params["03_mlp"]) == {"norm", "mixer", "post_norm"}
    # x + RMSNorm(mixer(RMSNorm(x))): the FFN half of the reference's block
    eps = cfg["rms_norm_eps"]
    want = u + ref.rms(ref.ffn(ref._ein("fp32"), leaves,
                               ref.rms(u, leaves["g3"], eps)), leaves["g4"], eps)
    p = params["03_mlp"]
    got = model._residual(p, u, model._mlp(p["mixer"], model._normed(p, u)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_letters_loops_and_the_state_pool():
    from theanompi_tpu.models.hybrid_lm import HybridLM

    with pytest.raises(ValueError, match="gated FFN"):
        HybridLM({"pattern": "*x"})
    for pattern in ("M*-", "w-"):  # slot-owned pools: one entry a layer
        with pytest.raises(ValueError, match="no M or w layer"):
            HybridLM({"pattern": pattern, "loops": 2})
    with pytest.raises(ValueError, match="loops >= 1"):
        HybridLM({"pattern": "*-", "loops": 0})
    model = HybridLM({"pattern": "*-*-*", "loops": 4, "kv_heads": 2,
                      "head_dim": 16})
    assert model.cache_spec() == {
        "kv": {"layers": 12, "heads": 2, "head_dim": 16},
        "state": {}, "state_layers": 0}
    params, _ = model.init_params(jax.random.PRNGKey(0))
    assert params["exit_gate"]["w"].shape == (256,)
    assert params["exit_gate"]["b"].shape == ()
    assert "post_norm" not in params["00_attn"]
    assert "exit_gate" not in HybridLM({"pattern": "*-"}).init_params(
        jax.random.PRNGKey(0))[0]
    with pytest.raises(NotImplementedError, match="multi-exit loss"):
        model.loss_fn(params, {}, None, None, True)


# -- the whole forward -------------------------------------------------------------

def test_apply_logits_is_the_references_forward_at_every_position(tiny):
    cfg, model, params = tiny
    toks = _tokens(cfg, (2, 23))
    got, t_star = model.apply_logits(params, {}, jnp.asarray(toks),
                                     exit_steps=True)
    np.testing.assert_allclose(got, ref.logits(cfg, SEED, toks),
                               atol=2e-5, rtol=0)
    # the published threshold: every token reads the last step's state
    assert np.asarray(t_star).tolist() == [[cfg["total_ut_steps"]] * 23] * 2
    assert (np.asarray(ref.forward(cfg, SEED, toks)["t_star"]) == 3).all()


@pytest.mark.parametrize("tau", [0.51, 0.6, 0.77])
def test_the_exit_rule_below_the_published_threshold(tiny, tau):
    """``t*`` and the state read out, against the reference, wherever the
    reference's cumulated exit probability keeps a margin from ``tau``."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg, _, params = tiny
    toks = _tokens(cfg, (3, 19), seed=4)
    out = ref.forward(cfg, SEED, toks, tau=tau)
    lam = np.asarray(out["lam"])
    cdf = np.cumsum(lam * np.cumprod(np.concatenate(
        [np.ones_like(lam[:1]), 1.0 - lam[:-1]]), axis=0), axis=0)[:-1]
    clear = (np.abs(cdf - tau) > 1e-4).all(axis=0)
    assert clear.mean() > 0.8
    model = HybridLM(dict(arch.model_config(cfg), exit_threshold=tau))
    got, t_star = model.apply_logits(params, {}, jnp.asarray(toks),
                                     exit_steps=True)
    want_t = np.asarray(out["t_star"])
    assert (np.asarray(t_star) == want_t)[clear].all()
    want = np.asarray(ref.logits(cfg, SEED, toks, tau=tau))
    np.testing.assert_allclose(np.asarray(got)[clear], want[clear],
                               atol=2e-5, rtol=0)
    # the three thresholds between them read every step out somewhere
    assert set(np.unique(want_t)) <= {1, 2, 3}
    assert set(np.unique(want_t[clear])) == {0.51: {1, 2}, 0.6: {2},
                                             0.77: {2, 3}}[tau]


# -- the model through the engine's cache and the scheduler's slots ----------------

def _engine(tiny, **kw):
    from theanompi_tpu.serving.engine import InferenceEngine

    _, model, params = tiny
    return InferenceEngine(model, params, block_size=8, **kw)


@pytest.mark.parametrize("decode_kernel", ["off", "on"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        tiny, decode_kernel):
    """Logits, not tokens: a prompt padded to its bucket, then 9 decode
    steps, against the reference's one full forward over the same tokens;
    by the gather and by the interpreted kernel under a traced entry."""
    cfg = tiny[0]
    eng = _engine(tiny, max_batch=2, decode_kernel=decode_kernel)
    assert eng.decode_impl == {"off": "fallback",
                               "on": "kernel_interpret"}[decode_kernel]
    assert eng._k.shape == (6, 2 * 8 + 1, 8, 4, 16)   # 3 loops x 2 * layers
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
    padded = np.zeros((1, cfg["run"]["max_context"]), np.int32)
    padded[0, :len(toks) - 1] = toks[:-1]
    want = ref.logits(cfg, SEED, padded)[0, len(prompt) - 1:len(toks) - 1]
    np.testing.assert_allclose(np.stack(rows), want, atol=2e-5, rtol=0)
    assert toks[len(prompt):] == np.argmax(want, axis=-1).tolist()


def test_each_loop_step_and_layer_writes_its_own_entry(tiny):
    """A prefill of 13 tokens into blocks 3 and 1 fills those blocks of all
    six entries with six different K's and touches no other block; a decode
    step then changes one token's row of each entry and nothing else."""
    cfg = tiny[0]
    eng = _engine(tiny, max_batch=2)
    prompt = _tokens(cfg, 13, seed=3).tolist()
    tok, _ = eng.prefill([3, 1], prompt, rid=7, slot=1)
    k = np.asarray(eng._k)
    assert k.shape[0] == 6
    written = np.abs(k).sum(axis=(2, 3, 4)) > 0          # [entry, block]
    assert written[:, [1, 3]].all() and not written[:, [2, 4, 5, 6]].any()
    firsts = [k[e, 3, 0].ravel() for e in range(6)]      # position 0's key
    for a in range(6):
        for b in range(a):
            assert np.abs(firsts[a] - firsts[b]).max() > 1e-3, (a, b)
    # position 0 is unrotated: entry 0 holds layer 0's own W_k row of step 1
    leaves = ref.leaves(cfg, ref.seed_key(SEED), "layer", 0)
    top = ref.leaves(cfg, ref.seed_key(SEED), "top")
    a = ref.rms(top["embed"][prompt[0]].astype(jnp.float32),
                leaves["g1"].astype(jnp.float32), cfg["rms_norm_eps"])
    np.testing.assert_allclose(firsts[0], a @ leaves["wk"].astype(jnp.float32),
                               atol=2e-6)
    table = np.zeros((2, eng.max_blocks_per_seq), np.int32)
    table[1, :2] = [3, 1]
    eng.decode(table, np.array([0, 13], np.int32), np.array([0, tok]),
               np.zeros(2, np.float32), np.array([0, 7]))
    changed = np.abs(np.asarray(eng._k) - k).sum(axis=(3, 4)) > 0
    want = np.zeros_like(changed)
    want[:, 1, 13 - 8] = True        # position 13: block 1, row 5, every entry
    want[:, 0, 0] = True             # the inactive slot's write: the null block
    np.testing.assert_array_equal(changed, want)


@pytest.mark.parametrize("entry", [0, 2, 5])
def test_a_traced_entry_writes_and_attends_at_that_entry_alone(entry):
    """The cache's writes and both decode attentions under a traced entry
    (a loop's): the entry's blocks change and no other's; the interpreted
    kernel is bit-equal to the gather and to the Python-int call."""
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    rng = np.random.RandomState(entry)
    shape = (6, 9, 8, 4, 16)
    k0, v0 = (jnp.asarray(rng.randn(*shape), jnp.float32) for _ in range(2))
    tables = jnp.asarray([[3, 1, 0, 0], [5, 0, 0, 0], [0, 0, 0, 0]], jnp.int32)
    positions = jnp.asarray([9, 4, 0], jnp.int32)
    new_k, new_v, q = (jnp.asarray(rng.randn(3, 4, 16), jnp.float32)
                       for _ in range(3))

    def step(impl, e, k, v):
        cache = PagedKVCache(k, v, tables, 8, decode_impl=impl)
        cache = cache.write_decode(e, new_k, new_v, positions)
        return cache.k, cache.v, cache.attend_decode(e, q, positions)

    outs = {}
    for impl in ("fallback", "kernel_interpret"):
        outs[impl] = jax.jit(step, static_argnums=0)(
            impl, jnp.int32(entry), k0, v0)
    fixed = jax.jit(step, static_argnums=(0, 1))("fallback", entry, k0, v0)
    for a, b, c in zip(outs["fallback"], outs["kernel_interpret"], fixed):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    changed = np.abs(np.asarray(outs["fallback"][0]) - np.asarray(k0)
                     ).sum(axis=(1, 2, 3, 4)) > 0
    assert changed.tolist() == [e == entry for e in range(6)]
    # a whole prompt's blocks under a traced entry too
    blocks = jnp.asarray(rng.randn(1, 16, 4, 16), jnp.float32)
    wrote = jax.jit(lambda e, k, v: PagedKVCache(k, v, tables, 8).write_prefill(
        e, blocks, blocks, jnp.asarray([7, 2])).k)(jnp.int32(entry), k0, v0)
    np.testing.assert_array_equal(wrote[entry, 7], blocks[0, :8])
    np.testing.assert_array_equal(wrote[entry, 2], blocks[0, 8:])
    assert float(jnp.abs(wrote - k0).sum(axis=(1, 2, 3, 4))[entry - 1]) == 0.0


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


@pytest.fixture(scope="module")
def work(tiny):
    cfg = tiny[0]
    rng = np.random.RandomState(11)
    work = [(i, rng.randint(0, cfg["vocab_size"], size=n).tolist(), m)
            for i, (n, m) in enumerate([(9, 12), (21, 7), (5, 16), (14, 10),
                                        (30, 9)])]
    fresh = {}
    for w in work:  # each alone, in slot 0 of a new engine
        fresh.update(_serve(_engine(tiny, max_batch=1), [w])[0])
    return work, fresh


def test_a_reused_slot_matches_a_fresh_run(tiny, work):
    # two slots for five requests: every later one lands in a used slot,
    # over blocks whose six entries an earlier request filled
    got, sched = _serve(_engine(tiny, max_batch=2), work[0])
    assert got == work[1] and sched.n_preemptions == 0


def test_a_pool_too_small_preempts_and_finishes_every_request_whole(tiny, work):
    """8 blocks under 3 slots: the longest is preempted, queued again and
    recomputed over prompt + generated, through every entry of the pool."""
    requests, fresh = work
    got, sched = _serve(_engine(tiny, max_batch=3, num_blocks=8), requests)
    assert sched.n_preemptions > 0
    assert got == fresh
    assert [len(got[i]) for i, _, _ in requests] == [m for _, _, m in requests]


def test_the_prefix_cache_refuses_a_model_without_partial_prefill(tiny):
    from theanompi_tpu.serving.scheduler import Scheduler

    eng = _engine(tiny, max_batch=2)
    assert not eng.stateful and not eng.partial_prefill
    with pytest.raises(ValueError, match="no partial prefill"):
        Scheduler(eng, prefix_cache=True)
    with pytest.raises(ValueError, match="no partial prefill"):
        eng.prefill([1, 2], list(range(12)), prefix_len=8)
    Scheduler(eng)  # without the prefix cache it is served


def test_decode_tags_count_the_context_and_the_exit_steps(tiny):
    from theanompi_tpu.telemetry import spans

    work = [(i, list(range(3 + i, 9 + 2 * i)), 4) for i in range(2)]
    _serve(_engine(tiny, max_batch=3), work)   # prompts of 6 and 7 tokens
    tags = [r.tags for r in spans.snapshot() if r.name == "serve.decode"][-3:]
    assert [t["batch"] for t in tags] == [2, 2, 2]
    # each slot's context with the token the step writes: 7 + 8, 8 + 9, ...
    assert [t["kv_tokens"] for t in tags] == [15, 17, 19]
    # 2 slots x 3, read with the tokens one step after the launch: the
    # first of the three launches had no step to read
    assert [t.get("loop_exit_steps") for t in tags] == [None, 6, 6]


# -- programs ------------------------------------------------------------------------

def _decode_text(eng, debug_info=False):
    b, i32 = eng.max_batch, jnp.int32
    return eng._decode_fn.lower(
        eng.params, eng._k, eng._v, jnp.zeros((b, eng.max_blocks_per_seq), i32),
        jnp.zeros((b,), i32), jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key).as_text(debug_info=debug_info)


def test_the_loop_is_a_loop_of_the_program(tiny):
    """The decode program holds the pattern once whatever ``loops`` is: as
    many weight products at 3 loops as at 2, inside one ``while``."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    cfg, _, params = tiny
    texts = {}
    for loops in (2, 3):
        model = HybridLM(dict(arch.model_config(cfg), loops=loops))
        texts[loops] = _decode_text(InferenceEngine(
            model, params, block_size=8, max_batch=2), debug_info=True)
    n = {k: t.count("stablehlo.dot_general") for k, t in texts.items()}
    # two layers of 7 products (q, k, v, o, gate, up, down) + the head
    assert n[2] == n[3] == 2 * 7 + 1
    assert all(t.count("stablehlo.while") >= 1 for t in texts.values())
    for scope in ("embed", "attn", "mlp", "loop.exit", "head", "sample"):
        assert re.search(rf'loc\("[^"]*\b{re.escape(scope)}[/"]', texts[3]), scope


#: sha256 (first 16 hex) of the StableHLO text a default-configured
#: ``HybridLM`` (``loops`` 1, ``post_norm`` False, ``rope_theta`` None, no
#: ``-``) lowered its serving steps to at the parent of PR 31 (commit
#: 4bffab6, jax 0.9.0).  A PR that means to change these programs replaces
#: the hashes.
#: PR 33 replaced ``prefill``: the head reads the one position that is
#: sampled (``head_at``), not the bucket's every row; ``decode`` is PR 31's.
#: Replaced since commit c10db4e: ``prefill`` (474deaf959a5d613 there): the
#: prompt's K/V goes into its 2-K/V-head pool a token row at a time
#: (``prefill_write_form``), which keeps the pool's layout where the
#: whole-block scatter had XLA re-lay both pools out and back.
GOLDEN_HYBRID = {"decode": "09e39c4d17dbe50b", "prefill": "6afe2e9b3f12736f"}


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_the_default_spine_lowers_to_the_programs_it_had(name):
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = HybridLM({
        "pattern": "MEM*E", "dim": 64, "vocab": 211, "seq_len": 64,
        "heads": 4, "kv_heads": 2, "head_dim": 16, "mamba_heads": 8,
        "mamba_head_dim": 16, "state_size": 16, "n_groups": 2,
        "chunk_size": 8, "n_experts": 32, "experts_held": (8, 16),
        "top_k": 8, "latent": 32, "expert_dim": 48, "shared_dim": 64})
    params, _ = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                          decode_kernel="off")
    b, i32 = eng.max_batch, jnp.int32
    fn, args = {
        "decode": (eng._decode_impl, (
            eng.params, eng._k, eng._v,
            jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
            jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), i32), eng._base_key, eng._state)),
        "prefill": (eng._prefill_impl, (
            eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
            jnp.zeros((16,), i32), jnp.asarray(5, i32),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32), eng._base_key,
            eng._state, jnp.asarray(1, i32))),
    }[name]
    text = jax.jit(fn, donate_argnums=(1, 2, 9)).trace(*args).lower().as_text()
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = ""',
                  text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == GOLDEN_HYBRID[name]


# -- compiled for the chip without the chip -----------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _described_engine(model, one_chip, max_batch=1):
    """An engine over ``model``'s shapes, its weights as the engine's own
    rule holds them (``_held``, evaluated abstractly: bf16 for these models)
    on a described chip -> (engine, ``shape(s, dtype)`` of an argument on
    that chip)."""
    from theanompi_tpu.serving.engine import InferenceEngine

    def shape(s, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    class Engine(InferenceEngine):
        def _held(self, params):
            return jax.tree.map(lambda x: shape(x.shape, x.dtype),
                                jax.eval_shape(super()._held, params))

    eng = Engine(model, jax.eval_shape(model.init_params,
                                       jax.random.PRNGKey(0))[0],
                 block_size=16, num_blocks=2, max_batch=max_batch)
    return eng, shape


def _compiled(jitted, *args):
    """``jitted`` compiled for the described chip, the persistent cache off
    (it cannot read such an entry back and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jitted.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("program", ["decode", "prefill16", "prefill128"])
def test_the_served_widths_compile_for_a_v5e_around_one_pool(
        one_chip, monkeypatch, program):
    """What the interpreter cannot show, at the served widths, 16 slots and
    pool geometry with 4 of the 48 layers (16 entries of 321 blocks): the
    pools go through the program's loop as ONE buffer each — aliased in and
    out, and no temporary the size of a pool, which is what a loop-carried
    pool re-laid out for the prefill's scatter cost (two copies of both
    pools) — and nothing loop-invariant is hoisted out for every layer at
    once (a second layout of each of q, k, v's weights: 8 MB a weight)."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "ouro-2.6b.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the chip's gates
    eng, shape = _described_engine(HybridLM(arch.model_config(cfg)), one_chip,
                                   max_batch=16)
    assert eng.decode_impl == "kernel"
    pool = shape((16, 321, 16, 16, 128), jnp.bfloat16)
    key, b = shape((2,), jnp.uint32), eng.max_batch
    if program == "decode":
        fn, args = eng._decode_impl, (
            shape((b, eng.max_blocks_per_seq)), shape((b,)), shape((b,)),
            shape((b,), jnp.float32), shape((b,)), key)
    else:
        p = int(program[len("prefill"):])
        fn, args = eng._prefill_impl, (
            shape((p // 16,)), shape((p,)), shape(()), shape((), jnp.float32),
            shape(()), key)
    compiled = _compiled(jax.jit(fn, donate_argnums=(1, 2)),
                         eng.params, pool, pool, *args)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    pool_bytes = 16 * 321 * 16 * 16 * 128 * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < 64 * 2**20 < pool_bytes, mem
    calls = text.count("tpu_custom_call")
    # 4 layers inside one loop: the paged-decode kernel, the flash prefill
    # kernel from 128 tokens on, the plain blockwise attention below
    assert calls == {"decode": 4, "prefill16": 0, "prefill128": 4}[program]
    assert len(re.findall(r"\bwhile\(", text)) >= 1
    _no_pool_relayout(text, pool.shape)


@pytest.mark.parametrize("program", ["decode", "prefill16384"])
def test_the_window_models_served_widths_compile_for_a_v5e_and_fit(
        one_chip, monkeypatch, program):
    """The other architecture of this spine at ITS served widths
    (``benchmarks/configs/laguna-xs2-pp8.json``: five layers, 32 slots,
    contexts to 16 384): the programs compile, the pool and the window
    rings are aliased in and out, the grouped products, the full layers'
    paged-decode kernel of grouped pools (ISSUE 34) and (prefill) the flash
    kernel with its band are custom calls, and arguments + temporaries
    leave the chip room — the decode step gathers no context (the fallback
    held a 4096-token piece and its copy, 1.4 GB; whole: 4.3 GB), the
    longest bucket's head is one row (6.6 GB of float32 logits whole)."""
    from benchmarks.arch import laguna
    from theanompi_tpu.models.hybrid_lm import HybridLM

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "laguna-xs2-pp8.json")) as f:
        cfg = json.load(f)
    run = cfg["run"]
    b = run["max_batch"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the chip's gates
    eng, shape = _described_engine(HybridLM(laguna.model_config(cfg)), one_chip)
    assert (eng.decode_impl, eng.decode_call, eng.expert_impl) == (
        "kernel", "paged_decode_grouped", "kernel")
    pool = shape((2, run["num_blocks"], 16, 8, 128), jnp.bfloat16)
    ring = shape((3, b, 512, 8, 128), jnp.bfloat16)
    state = {"window_k": ring, "window_v": ring}
    key = shape((2,), jnp.uint32)
    if program == "decode":
        fn, args = eng._decode_impl, (
            shape((b, eng.max_blocks_per_seq)), shape((b,)), shape((b,)),
            shape((b,), jnp.float32), shape((b,)), key, state, shape((b,)))
    else:
        p = int(program[len("prefill"):])
        fn, args = eng._prefill_impl, (
            shape((p // 16,)), shape((p,)), shape(()), shape((), jnp.float32),
            shape(()), key, state, shape(()))
    compiled = _compiled(jax.jit(fn, donate_argnums=(1, 2, 9)),
                         eng.params, pool, pool, *args)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    held = 2 * (2 * run["num_blocks"] * 16 + 3 * b * 512) * 8 * 128 * 2
    assert mem.alias_size_in_bytes >= held
    assert 9.6e9 < mem.argument_size_in_bytes < 9.8e9   # 7.74 GB of weights
    # decode 11 MB (no gathered context); prefill 2.5 GB (131 072 expert
    # rows at model width)
    assert mem.temp_size_in_bytes < {"decode": 64e6, "prefill16384": 2.8e9}[
        program], mem
    # eight grouped products; in decode also the two full layers' paged
    # kernel, in prefill five flash kernels, three of them over the band
    assert text.count("tpu_custom_call") == {"decode": 10,
                                             "prefill16384": 13}[program]
    _no_pool_relayout(text, pool.shape)
    if program == "decode":
        _no_gathered_context(text, pool.shape, b, table_tokens=16384)


def _no_pool_relayout(text, pool_shape):
    """No ``copy`` (nor ``copy-start``) in a compiled program has the pool's
    shape in its result, in any layout: the program writes the pools where
    they lie.  A prompt's whole-block scatter into a pool of fewer K/V heads
    than a sublane tile holds had XLA copy both pools to another layout and
    back around every prefill (four copies of 1.61 GB in ``sdar30``'s)."""
    dims = ",".join(str(d) for d in pool_shape)
    for line in text.splitlines():
        inst = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w\-]+)\(", line)
        if inst and inst.group(2) in ("copy", "copy-start"):
            assert f"[{dims}]" not in inst.group(1), line[:200]


def _no_gathered_context(text, pool_shape, slots, table_tokens):
    """A compiled decode program reads its grouped pool through the kernel:
    the ``paged_decode_grouped`` custom call takes the pools whole, nothing
    else is as large as a layer of the pool but the decode scatter (in
    place) — no copy or re-layout ahead of the call — and no gather is an
    eighth of the slots' tables wide (the fallback's pieces are a quarter)."""
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "paged_decode_grouped" in line]
    dims = ",".join(str(d) for d in pool_shape)
    assert calls and all(line.count(f"bf16[{dims}]") >= 2 for line in calls)
    layer = ",".join(str(d) for d in pool_shape[1:])
    for line in text.splitlines():
        inst = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(",
                        line)
        if not inst:
            continue
        dtype, shape, op = inst.groups()
        if dtype == "bf16" and shape in (dims, layer):
            assert op in ("parameter", "scatter", "fusion", "bitcast",
                          "get-tuple-element"), line[:200]
            assert op != "fusion" or "/scatter" in line, line[:200]
        if op == "gather":  # the fallback's: [slots, tokens, Hkv, Dh], or a piece
            size = int(np.prod([int(d) for d in shape.split(",") if d]))
            assert size < slots * table_tokens * np.prod(pool_shape[3:]) // 8, \
                line[:200]


def test_the_hybrids_served_widths_decode_through_the_grouped_kernel(
        one_chip, monkeypatch):
    """``benchmarks/configs/nemotron3-super-ep4.json`` as it is served (11
    layers, 128 slots, contexts to 4096, a pool of 2 K/V heads under 32
    query heads): the decode program holds ten grouped products, five state
    updates and ONE ``paged_decode_grouped`` call, and gathers no slot's
    4096-token context."""
    from benchmarks.arch import nemotron_h
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.ops import mamba2

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "nemotron3-super-ep4.json")) as f:
        cfg = json.load(f)
    run = cfg["run"]
    b = run["max_batch"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the chip's gates
    model = HybridLM(nemotron_h.model_config(cfg))
    with mamba2.pin_state_update("kernel"):
        eng, shape = _described_engine(model, one_chip)
        assert (eng.decode_impl, eng.decode_call) == ("kernel",
                                                      "paged_decode_grouped")
        assert eng._paged_tags == {"paged_layers": 1, "paged_kernel_layers": 1}
        spec = model.cache_spec()
        pool = shape((1, run["num_blocks"], 16, 2, 128), jnp.bfloat16)
        state = {name: shape((spec["state_layers"], b, *s), dt)
                 for name, (s, dt) in spec["state"].items()}
        compiled = _compiled(
            jax.jit(eng._decode_impl, donate_argnums=(1, 2, 9)),
            eng.params, pool, pool, shape((b, eng.max_blocks_per_seq)),
            shape((b,)), shape((b,)), shape((b,), jnp.float32), shape((b,)),
            shape((2,), jnp.uint32), state, shape((b,)))
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("tpu_custom_call") == 10 + 5 + 1
    assert mem.temp_size_in_bytes < 64e6, mem
    _no_gathered_context(text, pool.shape, b, table_tokens=4096)


def test_the_hybrids_served_prefill_writes_its_two_head_pool_where_it_lies(
        one_chip, monkeypatch):
    """The same configuration's 512 bucket, its mean prompt's: the prompt's
    K/V goes into the ``bf16[1, 32769, 16, 2, 128]`` pool a token row at a
    time, so no copy of either pool re-lays it out (the whole-block scatter
    made four, and 0.31 GB of temporaries)."""
    from benchmarks.arch import nemotron_h
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.ops import mamba2

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "nemotron3-super-ep4.json")) as f:
        cfg = json.load(f)
    run = cfg["run"]
    b = run["max_batch"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the chip's gates
    model = HybridLM(nemotron_h.model_config(cfg))
    with mamba2.pin_state_update("kernel"):
        eng, shape = _described_engine(model, one_chip, max_batch=b)
        assert eng._write_tags == {"pool_writes": 1, "pool_writes_in_place": 1}
        spec = model.cache_spec()
        pool = shape((1, run["num_blocks"], 16, 2, 128), jnp.bfloat16)
        state = {name: shape((spec["state_layers"], b, *s), dt)
                 for name, (s, dt) in spec["state"].items()}
        compiled = _compiled(
            jax.jit(eng._prefill_impl, donate_argnums=(1, 2, 9)),
            eng.params, pool, pool, shape((32,)), shape((512,)), shape(()),
            shape((), jnp.float32), shape(()), shape((2,), jnp.uint32), state,
            shape(()))
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= 2 * np.prod(pool.shape) * 2
    assert mem.temp_size_in_bytes < 0.2e9, mem
    _no_pool_relayout(text, pool.shape)


@pytest.mark.parametrize("program", ["block", "prefill1024", "prefill4096"])
def test_the_block_diffusion_stage_compiles_for_a_v5e_and_fits(
        one_chip, monkeypatch, program):
    """``benchmarks/configs/sdar-30b-a3b-pp8.json`` as it is served (six
    layers of 128 experts, 64 slots, contexts to 4096): the pass program
    holds the twelve grouped products and six ``paged_decode_grouped``
    calls — a slot's 4 x 32 queries as the query heads of one slot — and
    gathers no context; the block prefill (the 1024 bucket holds the mean
    prompt) runs the flash kernel with its block-causal mask (the last
    layer's attention and experts feed nothing and are gone) and writes the
    4-K/V-head pools where they lie, no copy re-laying them out; arguments
    and temporaries leave the chip room."""
    from benchmarks.arch import sdar_moe
    from theanompi_tpu.models.hybrid_lm import HybridLM

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "sdar-30b-a3b-pp8.json")) as f:
        cfg = json.load(f)
    run = cfg["run"]
    b = run["max_batch"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the chip's gates
    eng, shape = _described_engine(HybridLM(sdar_moe.model_config(cfg)),
                                   one_chip, max_batch=b)
    assert (eng.decode_impl, eng.decode_call, eng.expert_impl) == (
        "kernel", "paged_decode_grouped", "kernel")
    pool = shape((6, run["num_blocks"], 16, 4, 128), jnp.bfloat16)
    if program == "block":
        fn, args = eng._block_impl, (
            shape((b, eng.max_blocks_per_seq)), shape((b,)), shape((b, 6)),
            shape((b,), jnp.float32), shape((b,)), shape((2,), jnp.uint32),
            {}, shape((b, 14)))
    else:
        p = int(program[len("prefill"):])
        fn, args = eng._prefill_blocks_impl, (shape((p // 16,)), shape((p,)))
    compiled = _compiled(jax.jit(fn, donate_argnums=(1, 2)),
                         eng.params, pool, pool, *args)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= 2 * np.prod(pool.shape) * 2
    # 8.72 GB of weights + the pool; a prefill leaves out the last layer's
    # experts (0.94 GB), which feed nothing
    low, high = (11.9e9, 12.0e9) if program == "block" else (10.0e9, 10.2e9)
    assert low < mem.argument_size_in_bytes < high
    # 0.10 / 0.62 GB for the prefills: 1.64 / 1.68 GB while a pool-sized
    # copy stood in for each pool's scatter
    assert mem.temp_size_in_bytes < {"block": 0.3e9, "prefill1024": 0.15e9,
                                     "prefill4096": 0.7e9}[program], mem
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    grouped = [line for line in calls if "paged_decode_grouped" in line]
    assert (len(calls), len(grouped)) == {"block": (18, 6),
                                          "prefill1024": (15, 0),
                                          "prefill4096": (15, 0)}[program]
    _no_pool_relayout(text, pool.shape)
    if program == "block":
        _no_gathered_context(text, pool.shape, b, table_tokens=4096)


@pytest.mark.parametrize("heads,kv_heads,dh,bs,dtype", [
    (48, 8, 128, 16, jnp.bfloat16), (32, 2, 128, 16, jnp.bfloat16),
    (4 * 32, 4, 128, 16, jnp.bfloat16),
    (48, 8, 128, 16, jnp.float32), (16, 4, 128, 16, jnp.bfloat16),
    (8, 2, 256, 16, jnp.bfloat16), (8, 1, 128, 8, jnp.float32)])
def test_the_grouped_kernels_gate_is_what_compiles_for_a_v5e(
        one_chip, heads, kv_heads, dh, bs, dtype):
    """Every shape the gate's test accepts compiles alone, the pool its
    only operand of that size and nothing copied."""
    from theanompi_tpu.ops.pallas_paged_attention import (
        paged_attend_decode_grouped,
        paged_decode_grouped_supported,
    )

    assert paged_decode_grouped_supported(heads, kv_heads, dh, bs, dtype)

    def shape(s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    pool = shape((2, 1025, bs, kv_heads, dh), dtype)
    compiled = _compiled(
        jax.jit(lambda k, v, t, q, p: paged_attend_decode_grouped(
            k, v, 1, t, bs, q, p, interpret=False)),
        pool, pool, shape((8, 64)), shape((8, heads, dh), dtype), shape((8,)))
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes == 0


#: sha256 (first 16 hex) of the StableHLO text the decode step of the two
#: one-K/V-head-a-query-head configurations lowered to for the TPU at the
#: parent of PR 34 (commit bb31a71, jax 0.9.0), at their served shapes (a
#: pool of 64 blocks; the kernel's serialized body left out): PR 34 gave
#: grouped pools a kernel of their own and must leave these programs alone.
#: PR 36 replaced ``cgpt-1.3b`` (296f8dc2efcb3a57 until then): ``TransformerLM``
#: states a ``weight_dtype`` now, so the engine holds its tree in bf16 and the
#: program takes every weight as bf16 — the 390 ``convert`` ops of its
#: ``recast`` scope are gone from the text and nothing else of it moved;
#: ``ouro-2.6b`` (``HybridLM``, which always stated one) is PR 34's parent's.
GOLDEN_SERVED_DECODE = {"cgpt-1.3b": "5c03fe830de1e048",
                        "ouro-2.6b": "98ac4963790912eb"}


@pytest.mark.parametrize("name", GOLDEN_SERVED_DECODE)
def test_the_one_head_a_query_decode_programs_lower_to_the_text_they_had(name):
    from benchmarks.common import model_config
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    if name == "cgpt-1.3b":
        model, b, state = TransformerLM(model_config(cfg)), 32, ()
    else:
        model, b, state = HybridLM(arch.model_config(cfg)), 16, ({},)
    sds = jax.ShapeDtypeStruct

    class Engine(InferenceEngine):
        def _held(self, params):
            dtype = getattr(self.model, "weight_dtype", None) or jnp.float32
            return jax.tree.map(lambda x: sds(x.shape, dtype), params)

    eng = Engine(model, jax.eval_shape(model.init_params,
                                       jax.random.PRNGKey(0))[0],
                 block_size=16, num_blocks=2, max_batch=b, decode_kernel="on")
    assert eng.decode_call == "paged_decode"
    eng.decode_impl = "kernel"      # the compiled call, lowered for the TPU
    kv = model.cache_spec()["kv"]
    pool = sds((kv["layers"], 64, 16, kv["heads"], kv["head_dim"]),
               jnp.bfloat16)
    i32 = jnp.int32
    text = jax.jit(eng._decode_impl, donate_argnums=(1, 2)).trace(
        eng.params, pool, pool, sds((b, eng.max_blocks_per_seq), i32),
        sds((b,), i32), sds((b,), i32), sds((b,), jnp.float32), sds((b,), i32),
        sds((2,), jnp.uint32), *state).lower(
            lowering_platforms=("tpu",)).as_text()
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = ""',
                  text)
    assert text.count("tpu_custom_call") == kv["layers"] // cfg.get(
        "total_ut_steps", 1)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == GOLDEN_SERVED_DECODE[name]


def test_cgpt13s_decode_program_compiled_for_a_v5e_holds_no_weight_sized_temporary(
        one_chip, monkeypatch):
    """PR 36, what the lowered text (``test_engine_held_weights.py``) cannot
    show: after XLA's passes, at the served widths and pool with 2 of the 24
    layers, the program's arguments are the bf16 tree and its temporaries
    hold nothing the size of the embedding — the parent re-cast the whole
    ``f32[50257,2048]`` every step, 206 MB of bf16, for a gather of 32 rows."""
    from benchmarks.common import model_config
    from theanompi_tpu.models.transformer_lm import TransformerLM

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "cgpt-1.3b.json")) as f:
        cfg = dict(json.load(f), n_layer=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the chip's gates
    eng, shape = _described_engine(TransformerLM(model_config(cfg)), one_chip,
                                   max_batch=32)
    assert eng.decode_impl == "kernel"
    assert eng.resolved_paths()["weights_held"] == "bfloat16"
    pool, b = shape((2, 2048, 16, 16, 128), jnp.bfloat16), eng.max_batch
    compiled = _compiled(
        jax.jit(eng._decode_impl, donate_argnums=(1, 2)), eng.params, pool,
        pool, shape((b, eng.max_blocks_per_seq)), shape((b,)), shape((b,)),
        shape((b,), jnp.float32), shape((b,)), shape((2,), jnp.uint32))
    mem, text = compiled.memory_analysis(), compiled.as_text()
    weights = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(eng.params))
    pools = 2 * 2 * 2048 * 16 * 16 * 128 * 2
    assert abs(mem.argument_size_in_bytes - (2 * weights + pools)) < 1 << 20
    assert mem.temp_size_in_bytes < 64e6, mem  # the embedding alone: 206 MB
    assert "f32[50257,2048]" not in text and "f32[2048,8192]" not in text
    assert text.count("tpu_custom_call") == 2
