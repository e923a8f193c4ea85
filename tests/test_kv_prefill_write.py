"""A prompt's K/V into the paged pool (``PagedKVCache.write_prefill``): the
form the pool's K/V heads pick (``prefill_write_form``: a token row at a
time below a tile's 8 sublanes, a whole block at a time from there) lands
the same bytes in the same blocks as the other, over every head count a
served pool has, and the engine says so — ``serve.prefill``'s
``pool_writes`` / ``pool_writes_in_place`` tags,
``resolved_paths()["prefill_kv_write"]`` and the benchmark's
``kv.prefill_write_in_place_share`` that reads the tags."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.serving import kv_cache
from theanompi_tpu.serving.kv_cache import PagedKVCache, prefill_write_form

BS, DH, NUM_BLOCKS, LAYERS = 4, 8, 24, 3


def _whole_blocks(pool, layer, x, table_row, block_size):
    """The pool with ``x`` ``[1, P_pad, H, Dh]`` written a block at a time:
    ``[P_pad // block_size, block_size, H, Dh]`` at ``pool[layer,
    table_row]``."""
    blocks = x[0].reshape(x.shape[1] // block_size, block_size, *x.shape[2:])
    return pool.at[layer, table_row].set(blocks.astype(pool.dtype))


def _table(n_blocks, real, rng):
    """``n_blocks`` entries: ``real`` distinct live blocks, the rest the null
    block (a bucket's padding), so the null block repeats."""
    row = np.zeros((n_blocks,), np.int32)
    row[:real] = rng.choice(np.arange(1, NUM_BLOCKS), real, replace=False)
    return row


def test_the_pools_heads_pick_the_form():
    """Below a tile's 8 sublanes (``sdar30``'s 4 K/V heads, ``nemotron3s``'
    2) the rows; from 8 (``lagunaxs2``, ``cgpt13``, ``ouro26``) the blocks."""
    assert [prefill_write_form(h) for h in (1, 2, 4, 7, 8, 16, 32)] == \
        ["token_rows"] * 4 + ["whole_blocks"] * 3


@pytest.mark.parametrize("n_blocks", [1, 2, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("heads", [1, 2, 4, 8, 16])
def test_the_token_row_write_lands_what_the_whole_block_scatter_did(
        heads, dtype, n_blocks):
    """The token rows, and ``write_prefill`` whichever form it picks, leave
    every non-null block bit-equal to the whole-block scatter's and every
    block the table does not name as it was, at a layer given as a Python
    int and as a traced one (a looped stack's entry); tables whose padding repeats
    the null block, and one whose live blocks fill half the bucket (a
    prompt shorter than it)."""
    rng = np.random.RandomState(heads * 100 + n_blocks)
    shape = (LAYERS, NUM_BLOCKS, BS, heads, DH)
    k0 = jnp.asarray(rng.standard_normal(shape), dtype)
    v0 = jnp.asarray(rng.standard_normal(shape), dtype)
    tables = jnp.zeros((2, 16), jnp.int32)
    p_pad = n_blocks * BS
    # the prompt in float32, as a projection might hand it over
    k = jnp.asarray(rng.standard_normal((1, p_pad, heads, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, p_pad, heads, DH)), jnp.float32)
    live = np.ones((LAYERS, NUM_BLOCKS), bool)
    live[:, PagedKVCache.NULL_BLOCK] = False    # garbage either way

    @jax.jit
    def forms(k0, v0, k, v, row, layer):
        cache = PagedKVCache(k0, v0, tables, BS).write_prefill(layer, k, v,
                                                                row)
        return {"write_prefill": (cache.k, cache.v)} | {
            name: (write(k0, layer, k, row, BS), write(v0, layer, v, row, BS))
            for name, write in (("token_rows", kv_cache._write_token_rows),
                                ("whole_blocks", _whole_blocks))}

    for real in sorted({n_blocks, max(1, n_blocks // 2)}):
        row = jnp.asarray(_table(n_blocks, real, rng))
        for layer in (1, jnp.int32(2)):
            got = forms(k0, v0, k, v, row, layer)
            want = [np.asarray(x.astype(jnp.float32))[live]
                    for x in got.pop("whole_blocks")]
            for name, pair in got.items():
                for x, w in zip(pair, want):
                    assert x.dtype == dtype, name
                    np.testing.assert_array_equal(
                        np.asarray(x.astype(jnp.float32))[live], w,
                        err_msg=name)
            # the rows' own reading: position t in its block's row t % BS
            rows_k = np.asarray(got["token_rows"][0].astype(jnp.float32))
            at = int(layer)
            for t in range(real * BS):
                np.testing.assert_array_equal(
                    rows_k[at, int(row[t // BS]), t % BS],
                    np.asarray(k[0, t].astype(dtype).astype(jnp.float32)))


def _prefill_spans(eng, work):
    from theanompi_tpu.telemetry import spans

    seq0 = max((r.seq for r in spans.snapshot()), default=-1)
    for table_row, tokens in work:
        eng.prefill(table_row, tokens)
    return [r for r in spans.snapshot()
            if r.seq > seq0 and r.name == "serve.prefill"]


@pytest.mark.parametrize("family", ["transformer", "transformer8", "hybrid",
                                    "block_diffusion", "looped"])
def test_every_prefill_states_its_pool_writes_in_place(family):
    """Each ``serve.prefill`` carries the paged layers its program writes
    (a looped stack's once a loop step) and as many written in place, and
    the engine names the form its pool's K/V heads picked."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine

    hybrid = dict(dim=32, vocab=97, seq_len=64, heads=4, kv_heads=2,
                  head_dim=8, mamba_heads=4, mamba_head_dim=8, state_size=8,
                  n_groups=2, chunk_size=8, n_experts=8, top_k=2,
                  latent=None, expert_dim=16, shared_dim=16, weights="fp32")
    plain = {"dim": 32, "heads": 4, "n_layers": 2, "vocab": 97, "seq_len": 64,
             "verbose": False}
    make, writes, form = {
        "transformer": (lambda: TransformerLM(plain), 2, "token_rows"),
        "transformer8": (lambda: TransformerLM(dict(plain, dim=64, heads=8)),
                         2, "whole_blocks"),
        "hybrid": (lambda: HybridLM(dict(hybrid, pattern="M*E*")), 2,
                   "token_rows"),
        "block_diffusion": (lambda: HybridLM(dict(
            hybrid, pattern="*E*E", shared_dim=0, expert_act="silu_gated",
            router="softmax", block_len=4, mask_id=96)), 2, "token_rows"),
        "looped": (lambda: HybridLM(dict(
            hybrid, pattern="*-", loops=3, ffn_dim=48, post_norm=True,
            rope_theta=1e4)), 3, "token_rows"),
    }[family]
    model = make()
    params, _ = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, block_size=8, max_batch=2)
    assert eng.resolved_paths()["prefill_kv_write"] == form
    rng = np.random.RandomState(39)
    got = _prefill_spans(eng, [([1, 2], rng.randint(0, 90, 13).tolist()),
                               ([3], rng.randint(0, 90, 8).tolist())])
    assert len(got) == 2
    for r in got:
        assert (r.tags["pool_writes"], r.tags["pool_writes_in_place"]) \
            == (writes, writes)


def test_the_in_place_share_reads_the_prefill_spans_two_tags(monkeypatch):
    from benchmarks.common import load_json
    from benchmarks.readers import span_tags
    from theanompi_tpu.telemetry import spans

    decl = load_json("metrics", "kv.prefill_write_in_place_share.json")
    assert decl["reader"] == "span_tags"
    assert decl["workloads"] == ["nemotron3s.serve.backlog",
                                 "sdar30.serve.backlog"]

    def span(name, sid, parent, t0, **tags):
        return types.SimpleNamespace(name=name, id=sid, parent=parent, t0=t0,
                                     t1=t0 + 0.05, instant=False, tags=tags)

    records = [span("serve.step", 1, None, 0.0),
               span("serve.admit", 2, 1, 0.01),
               span("serve.prefill", 3, 2, 0.02, pool_writes=6,
                    pool_writes_in_place=6),
               span("serve.step", 4, None, 2.0),
               span("serve.admit", 5, 4, 2.01),
               span("serve.prefill", 6, 5, 2.02, pool_writes=6,
                    pool_writes_in_place=6)]
    monkeypatch.setattr(spans, "snapshot", lambda: records)
    monkeypatch.setattr(spans, "dropped", lambda: 0)
    run = {"counters": {"steps": 2}}
    assert span_tags.read(run, **decl["args"]) == 1.0
    records[5].tags["pool_writes_in_place"] = 0   # a pool re-laid out
    assert span_tags.read(run, **decl["args"]) == 0.5
    for r in records:  # a program that tags neither (the parent) reads nothing
        r.tags.pop("pool_writes", None)
        r.tags.pop("pool_writes_in_place", None)
    assert span_tags.read(run, **decl["args"]) is None
