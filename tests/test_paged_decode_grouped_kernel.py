"""The paged-decode kernel of grouped K/V pools (ISSUE 34,
``ops/pallas_paged_attention.py::paged_attend_decode_grouped``) under the
Pallas interpreter: against one masked softmax over the gathered context
(``serving/kv_cache.py::_grouped_attend``) over the table cases a serving
cache produces, at every layer of a several-layer pool, its shape gate, its
dispatch from ``PagedKVCache.attend_decode`` and an engine that serves the
tokens the gather serves.  The two paths sum in another order, so the lock
is a tolerance: a few float32 rounding steps over float32 pools, a few
rounding steps of the bfloat16 output over bfloat16 pools.  The compiled
kernel is checked where it can be: for a v5e without the chip in
``tests/test_looped_lm.py``, on the chip by ``chip_smoke.py``."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops.pallas_paged_attention import (
    paged_attend_decode_grouped,
    paged_decode_grouped_supported,
)
from theanompi_tpu.serving.kv_cache import PagedKVCache, _grouped_attend

BS, NB, PER, DH, LAYERS = 4, 8, 2, 16, 3     # a step is PER x BS = 8 tokens
NUM_BLOCKS = 48
GEOMETRIES = [(48, 8), (32, 2), (4, 2)]


def _pools(hkv, dtype, seed=0):
    rng = np.random.RandomState(seed)
    shape = (LAYERS, NUM_BLOCKS, BS, hkv, DH)
    return (jnp.asarray(rng.randn(*shape), dtype),
            jnp.asarray(rng.randn(*shape), dtype))


def _tables(positions, rng, ids=None):
    """Each slot's blocks up to its position, distinct unless ``ids`` names
    them; a slot at position 0 is inactive: an all-null table."""
    tables = np.zeros((len(positions), NB), np.int32)
    free = iter(rng.permutation(np.arange(1, NUM_BLOCKS)))
    for i, p in enumerate(positions):
        if p:
            n = p // BS + 1
            tables[i, :n] = (ids[i][:n] if ids is not None
                             else [next(free) for _ in range(n)])
    return tables


def _case(name):
    """(positions, tables) of one batch of four slots."""
    rng = np.random.RandomState(7)
    step = PER * BS
    if name == "shared_prefix":       # two blocks held by three slots at once
        positions = [13, 21, 9, 30]
        own = rng.permutation(np.arange(3, NUM_BLOCKS))
        ids = [[1, 2, *own[8 * i:8 * i + 6]] for i in range(4)]
        return positions, _tables(positions, rng, ids)
    if name == "scattered":           # ids high, low and out of order
        positions = [31, 18, 27, 6]
        ids = [[47, 3, 46, 5, 44, 9, 40, 2], [1, 45, 7, 43, 11, 41, 13, 39],
               [38, 36, 34, 32, 30, 28, 26, 24], [23, 25, 27, 29, 31, 33, 35, 37]]
        return positions, _tables(positions, rng, ids)
    positions = {
        "ragged": [5, 17, 30, 11],
        "inactive": [0, 9, 0, 0],
        "block_boundary": [BS - 1, BS, 2 * BS - 1, 2 * BS],
        "step_boundary": [step - 1, step, 2 * step - 1, 2 * step],
        "table_end": [NB * BS - 1, NB * BS - 2, NB * BS - BS, NB * BS - BS - 1],
    }[name]
    return positions, _tables(positions, rng)


CASES = ["ragged", "inactive", "block_boundary", "step_boundary", "table_end",
         "shared_prefix", "scattered"]


@functools.lru_cache(maxsize=None)
def _kernel(traced):
    def run(k, v, layer, tables, q, positions):
        return paged_attend_decode_grouped(
            k, v, layer if traced else int(layer), tables, BS, q, positions,
            interpret=True, blocks_per_step=PER)
    return jax.jit(run) if traced else jax.jit(run, static_argnums=2)


def _reference(k, v, layer, tables, q, positions):
    hkv = k.shape[3]
    b = tables.shape[0]
    kb = jnp.take(k[layer], tables, axis=0).reshape(b, NB * BS, hkv, DH)
    vb = jnp.take(v[layer], tables, axis=0).reshape(b, NB * BS, hkv, DH)
    return np.asarray(_grouped_attend(q, kb, vb, positions), np.float32)


def _tolerance(dtype, ref):
    """float32: rounding of sums in another order; bfloat16: four rounding
    steps of the output at its magnitude."""
    eps = 1e-6 if dtype == jnp.float32 else 4 * float(jnp.finfo(dtype).eps)
    return eps * max(1.0, float(np.abs(ref).max()))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("heads,kv_heads", GEOMETRIES)
def test_the_kernel_is_the_masked_softmax_over_the_gathered_context(
        heads, kv_heads, case):
    positions, tables = _case(case)
    rng = np.random.RandomState(3)
    for dtype in (jnp.float32, jnp.bfloat16):
        k, v = _pools(kv_heads, dtype)
        q = jnp.asarray(rng.randn(len(positions), heads, DH), dtype)
        args = (jnp.asarray(tables), q, jnp.asarray(positions, jnp.int32))
        got = np.asarray(_kernel(False)(k, v, 1, *args), np.float32)
        ref = _reference(k, v, 1, *args)
        assert got.shape == (len(positions), heads, DH)
        assert np.isfinite(got).all()          # an inactive slot: finite garbage
        assert np.abs(got - ref).max() <= _tolerance(dtype, ref), (case, dtype)


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("layer", range(LAYERS))
def test_the_kernel_reads_the_layer_it_is_told_of_a_several_layer_pool(
        layer, traced):
    positions, tables = _case("ragged")
    k, v = _pools(2, jnp.float32, seed=5)
    q = jnp.asarray(np.random.RandomState(9).randn(4, 4, DH), jnp.float32)
    args = (jnp.asarray(tables), q, jnp.asarray(positions, jnp.int32))
    at = jnp.int32(layer) if traced else layer
    got = np.asarray(_kernel(traced)(k, v, at, *args))
    refs = [_reference(k, v, l, *args) for l in range(LAYERS)]
    assert np.abs(got - refs[layer]).max() <= _tolerance(jnp.float32, refs[layer])
    for other in set(range(LAYERS)) - {layer}:
        assert np.abs(got - refs[other]).max() > 0.1


def test_a_step_wider_than_the_table_and_a_table_no_multiple_of_it():
    """``blocks_per_step`` is capped at the table's width, and a table of 8
    blocks read 3 a step ends on a step that is part past the table: its
    entries re-read the last block and the mask drops them."""
    positions, tables = _case("table_end")
    k, v = _pools(2, jnp.float32)
    q = jnp.asarray(np.random.RandomState(1).randn(4, 4, DH), jnp.float32)
    args = (jnp.asarray(tables), q, jnp.asarray(positions, jnp.int32))
    ref = _reference(k, v, 0, *args)
    for per in (3, 5, 64, None):
        got = np.asarray(paged_attend_decode_grouped(
            k, v, 0, tables, BS, q, args[2], interpret=True,
            blocks_per_step=per))
        assert np.abs(got - ref).max() <= _tolerance(jnp.float32, ref), per


@pytest.mark.parametrize("shape,ok", [
    # the two served geometries, and what else a v5e compile has shown
    ((48, 8, 128, 16, jnp.bfloat16), True),
    ((32, 2, 128, 16, jnp.bfloat16), True),
    ((48, 8, 128, 16, jnp.float32), True),
    ((16, 4, 128, 16, jnp.bfloat16), True),
    ((8, 2, 256, 16, jnp.bfloat16), True),
    ((8, 1, 128, 8, jnp.float32), True),
    # one K/V head a query head is the other kernel's pool
    ((16, 16, 128, 16, jnp.bfloat16), False),
    # one bfloat16 K/V head is half a packed row; 6 K/V heads no tile
    ((8, 1, 128, 16, jnp.bfloat16), False),
    ((24, 6, 128, 16, jnp.bfloat16), False),
    # a block of 4 x 2 rows is no whole bfloat16 tile; 10 heads over 4 no group
    ((8, 2, 128, 4, jnp.bfloat16), False),
    ((10, 4, 128, 16, jnp.float32), False),
    # head dims of part of a lane row (every tiny test shape), other dtypes
    ((4, 2, 16, 4, jnp.float32), False),
    ((48, 8, 128, 16, jnp.float16), False),
])
def test_the_shape_gate(shape, ok):
    assert paged_decode_grouped_supported(*shape) is ok


def test_a_compiled_call_outside_the_gate_and_a_wrong_pool_raise():
    k, v = _pools(2, jnp.float32)
    positions, tables = _case("ragged")
    q = jnp.zeros((4, 4, DH), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    with pytest.raises(ValueError, match="paged_decode_grouped_supported"):
        paged_attend_decode_grouped(k, v, 0, tables, BS, q, pos,
                                    interpret=False)
    with pytest.raises(ValueError, match="no grouped pool"):
        paged_attend_decode_grouped(k, v, 0, tables, BS, q[:, :2], pos,
                                    interpret=True)
    with pytest.raises(ValueError, match="no grouped pool"):
        paged_attend_decode_grouped(k, v, 0, tables, BS,
                                    jnp.zeros((4, 3, DH)), pos, interpret=True)
    with pytest.raises(ValueError, match="outside the pool's 3 layers"):
        paged_attend_decode_grouped(k, v, 3, tables, BS, q, pos,
                                    interpret=True)
    with pytest.raises(ValueError, match="blocks"):
        paged_attend_decode_grouped(k, v, 0, tables, 8, q, pos, interpret=True)


@pytest.mark.parametrize("impl", ["fallback", "kernel_interpret"])
def test_the_cache_dispatches_by_the_heads_it_is_handed(impl):
    """``attend_decode`` tells a grouped pool by ``q``'s heads against the
    pool's: on a kernel tier the grouped kernel, else the grouped gather —
    and queries of another head count over the same pool (a model whose
    layers differ in query heads) take the same branch."""
    positions, tables = _case("ragged")
    k, v = _pools(2, jnp.float32)
    cache = PagedKVCache(k, v, jnp.asarray(tables), BS, decode_impl=impl)
    pos = jnp.asarray(positions, jnp.int32)
    for heads in (4, 8):
        q = jnp.asarray(np.random.RandomState(heads).randn(4, heads, DH),
                        jnp.float32)
        ref = _reference(k, v, 2, jnp.asarray(tables), q, pos)
        text = jax.jit(lambda q: cache.attend_decode(2, q, pos)).lower(
            q).as_text(debug_info=True)
        scoped = re.search(r'loc\("[^"]*\bpaged_decode_grouped[/"]', text)
        assert bool(scoped) == (impl != "fallback")
        got = np.asarray(cache.attend_decode(2, q, pos))
        assert np.abs(got - ref).max() <= _tolerance(jnp.float32, ref)


# -- through the engine ------------------------------------------------------------------

MODELS = {
    # a ``*`` layer of 4 query heads over 2 K/V heads beside state and experts
    "hybrid": {"pattern": "M*E*", "heads": 4, "kv_heads": 2},
    # window layers of 8 query heads and full layers of 4 over one cache of 2
    "windowed": {"pattern": "*-wE*", "heads": 4, "kv_heads": 2, "window": 8,
                 "window_heads": 8, "attn_gate": True, "latent": None,
                 "expert_act": "silu_gated", "rope_theta": 1e4},
    # a looped stack: the pool entry is a traced scalar of the program's loop
    "looped": {"pattern": "*-*-", "loops": 2, "heads": 8, "kv_heads": 2,
               "post_norm": True, "rope_theta": 1e4, "ffn_dim": 48},
}


@pytest.mark.parametrize("name", MODELS)
def test_an_engine_over_a_grouped_pool_serves_the_tokens_the_gather_serves(name):
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.scheduler import Request, Scheduler
    from theanompi_tpu.telemetry import spans

    cfg = dict(MODELS[name], dim=32, vocab=97, seq_len=64, head_dim=8,
               mamba_heads=4, mamba_head_dim=8, state_size=8, n_groups=2,
               chunk_size=8, n_experts=8, top_k=2, expert_dim=16,
               shared_dim=16, weights="fp32")
    cfg.setdefault("latent", 16)
    rng = np.random.RandomState(34)
    work = [(i, rng.randint(0, 97, size=n).tolist(), m)
            for i, (n, m) in enumerate([(9, 7), (21, 4), (5, 9)])]
    got = {}
    for variant in ("on", "off", "auto"):
        model = HybridLM(cfg)       # an engine sets its model's paths
        params, _ = model.init_params(jax.random.PRNGKey(1))
        eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                              decode_kernel=variant)
        n = model.paged_layers
        assert n == cfg.get("loops", 1) * cfg["pattern"].count("*")
        kernel = variant == "on"
        paths = eng.resolved_paths()
        assert paths["decode_attention"] == (
            "kernel_interpret" if kernel else "fallback")
        assert paths.get("decode_attention_call") == (
            "paged_decode_grouped" if kernel else None)
        assert eng._paged_tags == {"paged_layers": n,
                                   "paged_kernel_layers": n if kernel else 0}
        if variant == "auto":       # off the chip: the gather, as "off"
            continue
        seq0 = max((r.seq for r in spans.snapshot()), default=-1)
        sched = Scheduler(eng)
        reqs = [Request(rid=rid, prompt=list(p), max_new_tokens=m,
                        temperature=0.0) for rid, p, m in work]
        for r in reqs:
            sched.submit(r)
        while not sched.idle:
            sched.step()
        got[variant] = {r.rid: list(r.generated) for r in reqs}
        mine = [r for r in spans.snapshot() if r.seq > seq0
                and r.name in ("serve.decode", "serve.prefill")]
        assert {r.name for r in mine} == {"serve.decode", "serve.prefill"}
        for r in mine:
            assert r.tags["paged_layers"] == n
            assert r.tags["paged_kernel_layers"] == (n if kernel else 0)
    assert got["on"] == got["off"]
    assert all(len(got["on"][rid]) == m for rid, _, m in work)


def test_the_other_pools_keep_their_kernel_and_their_tags():
    """One K/V head a query head: the gate asked is the other kernel's, the
    call named is ``paged_decode``, and a model without a ``*`` layer holds
    a pool entry for nobody and counts no paged layer."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = TransformerLM({"dim": 32, "heads": 4, "n_layers": 2, "vocab": 50,
                           "seq_len": 16, "verbose": False})
    params, _ = model.init_params(jax.random.PRNGKey(0))
    for variant, call, ran in [("on", "paged_decode", 2), ("off", None, 0)]:
        eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                              decode_kernel=variant)
        assert eng.resolved_paths().get("decode_attention_call") == call
        assert eng._paged_tags == {"paged_layers": 2,
                                   "paged_kernel_layers": ran}
    model = HybridLM({"pattern": "ME", "dim": 32, "vocab": 50, "seq_len": 16})
    eng = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2, decode_kernel="on")
    assert eng._k.shape[0] == 1
    assert eng._paged_tags == {"paged_layers": 0, "paged_kernel_layers": 0}


def test_int8_weights_under_a_grouped_pool_are_dequantized_on_either_tier():
    """The fused int8 matmul lives in ``TransformerLM``'s layers: a model
    with a grouped pool has every int8 leaf dequantized in its decode step
    on the kernel tier as on the fallback, and decodes the same tokens."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    got = {}
    for variant in ("on", "off"):
        model = HybridLM({
            "pattern": "*E", "heads": 4, "kv_heads": 2, "dim": 128,
            "vocab": 256, "seq_len": 64, "head_dim": 32, "n_experts": 8,
            "top_k": 2, "expert_dim": 128, "shared_dim": 128, "latent": 128,
            "weights": "fp32"})
        params, _ = model.init_params(jax.random.PRNGKey(1))
        eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                              decode_kernel=variant, quantize_int8=True)
        paths = eng.resolved_paths()
        assert paths["int8_matmul"]["decode_fused"] == 0
        assert paths["int8_matmul"]["decode_dequantized"] > 0
        tok, _ = eng.prefill([1, 2], list(range(9)), slot=0)
        tables = np.zeros((2, 8), np.int32)
        tables[0, :2] = [1, 2]
        nxt, _ = eng.decode(tables, np.array([9, 0]), np.array([tok, 0]),
                            np.zeros(2, np.float32), np.array([0, 0]))
        got[variant] = (tok, int(nxt[0]))
    assert got["on"] == got["off"]
