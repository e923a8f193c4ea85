"""The serving engine holds a model's weights once, in the dtype the model's
serving programs read them in (``weight_dtype``): ``TransformerLM`` states
its precision policy's compute dtype, as ``HybridLM`` states its own, so a
bf16 model's fp32 checkpoint is rounded at load and at a rollout — never
again inside a decode step or a prefill — and serves the same bits (here,
on the CPU; on the chip another rounding of the same sums: PERF.md §6)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SERVING_TINY

from theanompi_tpu.serving.kv_cache import PagedKVCache
from theanompi_tpu.serving.quant import QuantizedTensor

BF16 = jnp.dtype("bfloat16")
PROMPT = [3, 17, 42, 9, 27, 5, 11]
BLOCK, ROW = 4, [1, 2, 3]  # 7 prompt tokens + 3 decoded: three blocks of 4


def _float_dtypes(tree):
    return {x.dtype for x in jax.tree.leaves(tree)
            if jnp.issubdtype(x.dtype, jnp.floating)}


def _quantized(tree):
    return [x for x in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(x, QuantizedTensor)]


@pytest.fixture(scope="module", autouse=True)
def _the_ring_left_empty():
    """Tracing two 24-layer programs leaves ~15 000 ``jit.build`` instants in
    the process's span ring (65 536 records), and tests of other files on
    this xdist worker refuse a ring that has wrapped (ROADMAP D16): nothing
    here reads the ring, so it is cleared behind the module."""
    from theanompi_tpu.telemetry import spans

    yield
    spans.RING.clear()


@pytest.fixture(scope="module")
def bf16_model(dense_model):
    """The session's lightly trained tiny model, read under the bf16 policy:
    the same fp32 tree, a model whose serving programs compute in bf16."""
    from theanompi_tpu.models.transformer_lm import TransformerLM

    _, params, _ = dense_model
    return TransformerLM(dict(SERVING_TINY, precision="bf16")), params


@pytest.fixture(scope="module")
def bf16_engine(bf16_model):
    from theanompi_tpu.serving.engine import InferenceEngine

    model, params = bf16_model
    return InferenceEngine(model, params, block_size=BLOCK, max_batch=2)


def _serve(engine, steps=3):
    """``PROMPT`` prefilled into slot 0 and ``steps`` greedy decode steps
    through the engine's host API -> (tokens, the logits each was read off)."""
    tok, last = engine.prefill(ROW[:engine.pad_len(len(PROMPT)) // BLOCK],
                               PROMPT)
    toks, logits = [tok], [last]
    tables = np.zeros((engine.max_batch, engine.max_blocks_per_seq), np.int32)
    tables[0, :len(ROW)] = ROW
    n = len(PROMPT)
    for _ in range(steps):
        nxt, lg = engine.decode(tables, [n, 0], [toks[-1], 0], [0.0, 0.0],
                                [0, 0])
        toks.append(int(nxt[0]))
        logits.append(lg[0])
        n += 1
    return toks, logits


def test_a_bf16_models_engine_holds_bf16_and_says_so(bf16_model, bf16_engine):
    model, params = bf16_model
    assert model.weight_dtype == BF16
    assert _float_dtypes(params) == {jnp.dtype("float32")}  # as handed in
    assert _float_dtypes(bf16_engine.params) == {BF16}
    assert jax.tree.structure(bf16_engine.params) == jax.tree.structure(params)
    # rounded once, to nearest: the bits the programs' own cast would read
    np.testing.assert_array_equal(
        np.asarray(bf16_engine.params["head"]["w"]),
        np.asarray(params["head"]["w"].astype(BF16)))
    assert bf16_engine.resolved_paths()["weights_held"] == "bfloat16"


def test_an_fp32_models_engine_keeps_the_tree_it_was_handed(
        dense_model, serving_engine):
    model, params, _ = dense_model
    assert model.weight_dtype == jnp.float32
    assert serving_engine.params is params
    assert serving_engine.resolved_paths()["weights_held"] == "float32"


def test_training_never_reads_the_attribute(bf16_model):
    """The loss and the full-sequence forward take the fp32 tree as ever
    (a trainer keeps fp32 masters; ``weight_dtype`` is the engine's)."""
    model, params = bf16_model
    toks = jnp.asarray([PROMPT], jnp.int32)
    logits = jax.jit(model.apply_logits)(params, {}, toks)
    held = jax.tree.map(lambda x: x.astype(BF16), params)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(jax.jit(model.apply_logits)(held, {}, toks)))


def test_rounded_once_serves_the_bits_rounded_every_step_did(
        bf16_model, bf16_engine):
    """Tokens AND logits of the engine (a bf16 tree, its ``recast`` a no-op)
    equal those of the model's entry points called directly with the fp32
    tree (the cast inside every program), bit for bit on the CPU."""
    model, params = bf16_model
    toks, logits = _serve(bf16_engine)

    eng = bf16_engine
    k, v = jnp.zeros_like(eng._k), jnp.zeros_like(eng._v)
    p_pad = eng.pad_len(len(PROMPT))
    row = jnp.asarray(ROW[:p_pad // BLOCK], jnp.int32)
    padded = np.zeros((1, p_pad), np.int32)
    padded[0, :len(PROMPT)] = PROMPT

    @jax.jit
    def prefill(params, k, v):
        cache = PagedKVCache(
            k, v, jnp.zeros((1, eng.max_blocks_per_seq), jnp.int32), BLOCK)
        lg, cache = model.apply_prefill(params, {}, cache, row,
                                        jnp.asarray(padded))
        return lg[0, len(PROMPT) - 1], cache.k, cache.v

    @jax.jit
    def decode(params, k, v, tables, positions, tokens):
        cache = PagedKVCache(k, v, tables, BLOCK, decode_impl=eng.decode_impl)
        lg, cache = model.apply_decode(params, {}, cache, positions, tokens)
        return lg, cache.k, cache.v

    last, k, v = prefill(params, k, v)
    want_toks, want_logits = [int(jnp.argmax(last))], [np.asarray(last)]
    tables = np.zeros((eng.max_batch, eng.max_blocks_per_seq), np.int32)
    tables[0, :len(ROW)] = ROW
    for i in range(3):
        lg, k, v = decode(params, k, v, jnp.asarray(tables),
                          jnp.asarray([len(PROMPT) + i, 0], jnp.int32),
                          jnp.asarray([want_toks[-1], 0], jnp.int32))
        want_toks.append(int(jnp.argmax(lg[0])))
        want_logits.append(np.asarray(lg[0]))
    assert toks == want_toks
    for got, want in zip(logits, want_logits):
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want)


#: what the parent of PR 36 (commit 47482df: the tree kept in fp32, every
#: non-int8 leaf rounded inside each program) served for ``PROMPT`` under
#: ``quantize_int8=True`` on the CPU, recorded there
INT8_TOKENS_AT_THE_PARENT = [7, 7, 26, 7]


def test_int8_leaves_stay_whole_and_serve_the_parents_tokens(bf16_model):
    from theanompi_tpu.serving.engine import InferenceEngine

    model, params = bf16_model
    engine = InferenceEngine(model, params, block_size=BLOCK, max_batch=2,
                             quantize_int8=True, quant_chunk=64)
    leaves = _quantized(engine.params)
    assert leaves and engine.quantized
    for leaf in leaves:
        assert leaf.q.dtype == jnp.int8
        assert leaf.scales.dtype == jnp.float32  # not rounded with the rest
    assert engine.resolved_paths()["weights_held"] == "bfloat16"
    assert _serve(engine)[0] == INT8_TOKENS_AT_THE_PARENT


def test_held_fences_a_quantized_leaf_of_any_model(bf16_engine):
    """``_held`` itself, over a tree with an int8 leaf beside fp32 and
    integer ones: only the plain floating leaf is cast."""
    q = QuantizedTensor(jnp.ones((2, 8), jnp.int8),
                        jnp.asarray([0.5, 1e-3], jnp.float32), (4, 4),
                        jnp.float32)
    tree = {"w": jnp.ones((4, 4), jnp.float32), "q": q,
            "n": jnp.arange(3, dtype=jnp.int32)}
    held = bf16_engine._held(tree)
    assert held["w"].dtype == BF16 and held["n"].dtype == jnp.int32
    assert isinstance(held["q"], QuantizedTensor)
    assert held["q"].q.dtype == jnp.int8
    assert held["q"].scales.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(held["q"].scales),
                                  np.asarray(q.scales))


def test_a_rollouts_fp32_tree_lands_bf16_on_the_programs_it_had(bf16_model):
    from theanompi_tpu.serving.engine import InferenceEngine

    model, params = bf16_model
    engine = InferenceEngine(model, params, block_size=BLOCK, max_batch=2)
    before, _ = _serve(engine)
    sizes = (engine._decode_fn._cache_size(),
             {b: fn._cache_size() for b, fn in engine._prefill_fns.items()})

    incoming = jax.tree.map(lambda x: x * 1.5 + 0.01, params)  # fp32, as a
    prev = engine.swap_params(incoming)                        # trainer's
    assert prev is not engine.params and _float_dtypes(prev) == {BF16}
    assert _float_dtypes(engine.params) == {BF16}
    assert engine.params_version == 1
    np.testing.assert_array_equal(
        np.asarray(engine.params["head"]["w"]),
        np.asarray(incoming["head"]["w"].astype(BF16)))
    during, _ = _serve(engine)
    assert during != before  # other weights are being served

    engine.restore_params(prev)
    assert engine.params is prev and engine.params_version == 2
    assert _serve(engine)[0] == before
    # identical shapes AND dtypes: nothing was traced or compiled again
    assert sizes == (engine._decode_fn._cache_size(),
                     {b: fn._cache_size()
                      for b, fn in engine._prefill_fns.items()})


# -- the served widths, lowered for the chip without the chip (compiled for
# a v5e: ``test_looped_lm.py``, beside the other described-chip compiles) ----

def _cgpt13():
    from benchmarks.common import model_config
    from theanompi_tpu.models.transformer_lm import TransformerLM

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "cgpt-1.3b.json")) as f:
        return TransformerLM(model_config(json.load(f)))


def _abstract_engine(model, **kw):
    """An engine over ``model``'s SHAPES whose ``_held`` is the real one,
    evaluated abstractly: what it holds is what the rule gives."""
    from theanompi_tpu.serving.engine import InferenceEngine

    class Engine(InferenceEngine):
        def _held(self, params):
            return jax.eval_shape(super()._held, params)

    return Engine(model, jax.eval_shape(model.init_params,
                                        jax.random.PRNGKey(0))[0],
                  block_size=16, num_blocks=2, max_batch=32, **kw)


#: shapes of cgpt-1.3b's large weights: embedding, head, the FFN's two, an
#: attention projection and the three of q, k, v side by side
_WEIGHT_SHAPES = ("50257x2048", "2048x50257", "2048x8192", "8192x2048",
                  "2048x2048", "2048x6144")
#: those no op of a program may PRODUCE (``project_qkv`` joins q, k and v's
#: weights where it applies them, as in training: 2048x6144 is its own)
_NEVER_MADE = _WEIGHT_SHAPES[:4]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_served_programs_read_no_fp32_weight_and_recast_nothing(program):
    """``cgpt-1.3b``'s programs lowered for the TPU at the served widths (all
    24 layers, 32 slots; the harness of ``test_looped_lm``'s golden decode
    text): every large weight enters as bf16, none as f32, and no op
    PRODUCES a tensor of a weight's shape — no bf16 copy of the embedding
    (206 MB, the parent's largest temporary), no convert of a block's."""
    model = _cgpt13()
    eng = _abstract_engine(model, decode_kernel="on")
    eng.decode_impl = "kernel"      # the compiled call, lowered for the TPU
    assert eng.resolved_paths()["weights_held"] == "bfloat16"
    sds, i32, b = jax.ShapeDtypeStruct, jnp.int32, eng.max_batch
    pool = sds((24, 64, 16, 16, 128), jnp.bfloat16)
    if program == "decode":
        fn, args = eng._decode_impl, (
            sds((b, eng.max_blocks_per_seq), i32), sds((b,), i32),
            sds((b,), i32), sds((b,), jnp.float32), sds((b,), i32),
            sds((2,), jnp.uint32))
    else:
        fn, args = eng._prefill_impl, (
            sds((256 // 16,), i32), sds((256,), i32), sds((), i32),
            sds((), jnp.float32), sds((), i32), sds((2,), jnp.uint32))
    text = jax.jit(fn, donate_argnums=(1, 2)).trace(
        eng.params, pool, pool, *args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tensor<50257x2048xbf16>" in text
    for shape in _WEIGHT_SHAPES:
        assert f"tensor<{shape}xf32>" not in text, shape
    for shape in _NEVER_MADE:
        made = re.findall(rf"-> tensor<{shape}x\w+>\s*$", text, re.M)
        assert not made, (shape, made[:2])
