"""Block diffusion through the serving path (``HybridLM`` with a
``block_len`` -> ``InferenceEngine``'s block pass -> ``Scheduler``'s static
schedule) at tiny sizes on the CPU: the passes the scheduler ran agree with
the plain reference (``benchmarks/arch/sdar_moe_reference.py``) by logits
at the states it recorded; the schedule commits in position order, to
exactly ``max_new_tokens``, with ``L`` advancing by the block after each
write pass and one launch unread; and the ops it brought — the grouped
paged kernel over ``B x H`` query rows, the block-causal flash forward, the
softmax-routed expert layer without a shared expert — against plain
computations of the same thing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.hybrid_lm import HybridLM
from theanompi_tpu.serving.engine import InferenceEngine
from theanompi_tpu.serving.kv_cache import PagedKVCache
from theanompi_tpu.serving.scheduler import Request, Scheduler

MASK = 96
TINY = dict(pattern="*E*E", dim=64, vocab=97, seq_len=64, heads=4,
            kv_heads=2, head_dim=16, rope_theta=1e6, qk_norm=True,
            norm_eps=1e-6, n_experts=8, top_k=2, latent=None, expert_dim=32,
            shared_dim=0, expert_act="silu_gated", router="softmax",
            block_len=4, mask_id=MASK, weights="fp32",
            precision="fp32")


@pytest.fixture(scope="module")
def tiny():
    model = HybridLM(TINY)
    return model, model.init_params(jax.random.PRNGKey(0))[0]


def _serve(model, params, requests, **kw):
    """Serve ``requests`` to the end; -> (engine, scheduler, the ``lengths``
    row of every decode call)."""
    eng = InferenceEngine(model, params, block_size=8,
                          max_batch=kw.pop("max_batch", 3), **kw)
    sched = Scheduler(eng)
    calls = []
    real = eng.decode

    def spy(tables, lengths, tokens, temps, rids):
        calls.append((np.array(lengths), np.array(tokens)))
        return real(tables, lengths, tokens, temps, rids)

    eng.decode = spy
    for r in requests:
        sched.submit(r)
    while not sched.idle:
        sched.step()
    return eng, sched, calls


def _requests(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(0, MASK, size=p).tolist(),
                    max_new_tokens=n) for i, (p, n) in enumerate(lengths)]


# -- the schedule ---------------------------------------------------------------

def test_a_request_gets_its_tokens_in_position_order_to_the_exact_length(tiny):
    """Prompts of every tail (0..3 tokens past a block) and lengths that
    leave the last block part-dropped: each request is ``done`` with
    exactly ``max_new_tokens`` tokens, those its passes committed at its
    positions in order; the last block's positions past the limit are
    committed and kept too."""
    reqs = _requests([(5, 7), (8, 6), (11, 9), (14, 10), (3, 5), (17, 1)])
    _, sched, _ = _serve(*tiny, reqs)
    for r in reqs:
        assert r.state == "done" and len(r.generated) == r.max_new_tokens
        p = len(r.prompt)
        assert [r.committed[i][0] for i in range(p, p + len(r.generated))] \
            == r.generated
        end = -(-(p + r.max_new_tokens) // 4) * 4
        assert sorted(r.committed) == list(range(p, end))
        # a block opened with m masked positions takes ceil(m / 2) passes
        for s in range(p - p % 4, end, 4):
            opened = min(max(p - s, 0), 4)
            passes = [r.committed[i][1] for i in range(max(s, p), s + 4)]
            assert sorted(set(passes)) == list(range(-(-(4 - opened) // 2)))
    assert sched.n_overrun_slots == 0


def test_L_advances_by_the_block_after_its_write_pass_one_launch_unread(tiny):
    """A slot's ``lengths`` entry stays at its block's first position for
    the block's denoising passes and its write pass, then moves by 4; each
    row carries the pass index and the positions still masked; every launch
    but the first of a run of them goes out with the one before unread."""
    from theanompi_tpu.telemetry import spans

    spans.RING.clear()
    reqs = _requests([(9, 12)])
    _, _, calls = _serve(*tiny, reqs, max_batch=1)
    seen = [(int(lengths[0]), int(tokens[0, 0]), int(tokens[0, 1]))
            for lengths, tokens in calls]
    # prompt 9: blocks from 8 (tail 1, 3 masked: 2 passes + write), 12, 16
    # (2 + write each), 20 (its last: 2 passes, no write)
    assert seen == [(8, 0, 3), (8, 1, 1), (8, 2, 0),
                    (12, 0, 4), (12, 1, 2), (12, 2, 0),
                    (16, 0, 4), (16, 1, 2), (16, 2, 0),
                    (20, 0, 4), (20, 1, 2)]
    # the first pass of a request brings its block, later ones take it
    # from the device (-1), a new block opens all masked
    assert calls[0][1][0, 2:].tolist() == [reqs[0].prompt[8], MASK, MASK, MASK]
    assert (calls[1][1][0, 2:] == -1).all()
    assert calls[3][1][0, 2:].tolist() == [MASK] * 4
    decodes = [r for r in spans.snapshot() if r.name == "serve.decode"]
    assert [r.tags["ran_ahead"] for r in decodes] == [0] + [1] * 10
    assert [r.tags["kv_tokens"] for r in decodes] == [s + 4 for s, _, _ in seen]
    assert [r.tags["masked_rows"] for r in decodes] == [m for _, _, m in seen]
    assert sum(r.tags["store_slots"] for r in decodes) == 3
    # ``committed`` is the READ pass's, one behind: 2 + 1 + 0 + (2 + 2 + 0)
    # x 2 + 2 (the last pass is read by a collect)
    assert [r.tags.get("committed") for r in decodes[1:]] == [
        2, 1, 0, 2, 2, 0, 2, 2, 0, 2]


def test_the_kernel_and_the_gather_serve_the_same_tokens(tiny):
    """The grouped paged kernel (interpreted) and the grouped gather commit
    the same tokens at the same passes; a pool too small for the batch
    preempts and recomputes without losing a token or a record."""
    lengths = [(5, 9), (10, 11), (14, 6), (2, 13), (7, 8)]
    out = {}
    for impl in ("on", "off"):
        reqs = _requests(lengths, seed=3)
        eng, sched, _ = _serve(*tiny, reqs, decode_kernel=impl)
        assert eng.resolved_paths()["decode_attention"] == (
            "kernel_interpret" if impl == "on" else "fallback")
        out[impl] = [(r.generated, {p: c[:2] for p, c in r.committed.items()})
                     for r in reqs]
    assert out["on"] == out["off"]
    reqs = _requests(lengths, seed=3)
    eng, sched, _ = _serve(*tiny, reqs, num_blocks=6)
    assert sched.n_preemptions > 0
    for r in reqs:
        assert r.state == "done" and len(r.generated) == r.max_new_tokens
        p = len(r.prompt)
        assert [r.committed[i][0] for i in range(p, p + len(r.generated))] \
            == r.generated


def test_a_stop_token_ends_a_request_and_greedy_is_required(tiny):
    model, params = tiny
    first = _requests([(6, 20)], seed=5)[0]
    _serve(model, params, [first])
    eos = first.generated[5]
    again = _requests([(6, 20)], seed=5)[0]
    eng = InferenceEngine(model, params, block_size=8, max_batch=3)
    sched = Scheduler(eng, eos_token=eos)
    sched.submit(again)
    while not sched.idle:
        sched.step()
    stop = first.generated.index(eos)
    assert again.state == "done" and again.generated == first.generated[:stop + 1]
    with pytest.raises(ValueError, match="greedily"):
        sched.submit(Request(rid=9, prompt=[1, 2, 3], max_new_tokens=4,
                             temperature=0.7))
    with pytest.raises(ValueError, match="mask"):
        sched.submit(Request(rid=9, prompt=[1, MASK, 3], max_new_tokens=4))


# -- against the plain reference --------------------------------------------------

def test_the_served_passes_agree_with_the_reference_by_logits():
    """The benchmark's tiny configuration of the cell's model, its weights
    made from the seed as the cell's are, in float32: requests served
    through ``Scheduler`` / ``InferenceEngine``; every pass each recorded
    (the block as it stood) replayed through the model's own prefill and
    block pass gives the reference's logits (``sdar_moe_reference``, which
    shares nothing with the program), and commits what the served run
    committed."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmarks"))
    from sdar_tiny import tiny_config

    from benchmarks.arch import sdar_moe as arch
    from benchmarks.arch import sdar_moe_reference as ref

    cfg = tiny_config()
    cfg["run"].update(precision="fp32", weights="fp32")
    seed = 2**31 + 5
    model, eng, sched = arch.build(cfg, seed)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=rng.randint(0, arch.vocab(cfg), size=p).tolist(),
                    max_new_tokens=n)
            for i, (p, n) in enumerate([(9, 10), (12, 7), (6, 13)])]
    for r in reqs:
        sched.submit(r)
    while not sched.idle:
        sched.step()
    sample = [(r.prompt, r.generated, arch.served_record(r)) for r in reqs]
    want = ref.pass_logits(cfg, seed, sample)
    params = eng.params
    for r, (prompt, generated, record), lg in zip(reqs, sample, want):
        rows = ref.passes_of(cfg, prompt, generated, record)
        got, chosen = _replay(model, params, rows, len(prompt))
        np.testing.assert_allclose(got, np.asarray(lg), atol=2e-4, rtol=1e-4)
        # the served run committed what its own logits choose
        masked = rows["chosen"] | rows["open"]
        toks, states, conf = model.commit_block(
            jnp.asarray(got), jnp.asarray(rows["inputs"]), jnp.asarray(masked))
        assert (np.asarray(states) == 2).tolist() == rows["chosen"].tolist()
        served = rows["tokens"][rows["starts"][:, None] + np.arange(4)]
        assert (np.asarray(toks)[rows["chosen"]] == served[rows["chosen"]]).all()
        # and stated the confidence it ranked them by
        np.testing.assert_allclose(np.asarray(conf)[rows["chosen"]],
                                   rows["conf"][rows["chosen"]], atol=2e-4)
    gaps = ref.served_gap(cfg, seed, sample)
    assert gaps["logprob_gap"] < 1e-3 and gaps["order_gap"] < 1e-3, gaps


def _replay(model, params, rows, prompt_len):
    """The recorded passes of one request through the model's own programs
    over a fresh cache: its prompt's whole blocks prefilled, then each pass
    in order, each block's K/V written by a pass over its final tokens
    before the next block's first.  -> (logits ``[N, B, V]``, None)."""
    b = model.block_len
    cache = PagedKVCache.create(2, 9, 8, 2, 64, 1, 64)
    cache = PagedKVCache(cache.k, cache.v, jnp.arange(1, 9)[None], 8)
    n = prompt_len - prompt_len % b
    if n:
        toks = np.zeros((1, 64), np.int32)
        toks[0, :n] = rows["tokens"][:n]
        cache = model.apply_prefill_blocks(params, {}, cache, jnp.arange(1, 9),
                                           jnp.asarray(toks))
    out, written = [], n
    for i, s in enumerate(rows["starts"]):
        if s != written:  # the block before is whole: its K/V pass
            final = jnp.asarray(rows["tokens"][written:written + b])[None]
            _, cache, _ = model.apply_block(params, {}, cache,
                                            jnp.asarray([written]), final)
            written = int(s)
        lg, cache, _ = model.apply_block(params, {}, cache, jnp.asarray([s]),
                                         jnp.asarray(rows["inputs"][i])[None])
        out.append(np.asarray(lg[0]))
    return np.stack(out), None


# -- the ops it brought ---------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel_interpret", "fallback"])
def test_a_blocks_queries_attend_the_context_and_the_whole_block(impl):
    """``PagedKVCache.attend_block``: each slot's ``n x H`` queries as the
    query heads of one slot (K/V-head-major) through the grouped kernel or
    the grouped gather, against a plain masked softmax over keys ``[0, L +
    n)`` — every key of the block, none past it."""
    rng = np.random.RandomState(0)
    b, n, h, hkv, d, bs = 3, 4, 8, 2, 16, 8
    k = jnp.asarray(rng.randn(1, 13, bs, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, 13, bs, hkv, d), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 13))[:12].reshape(3, 4))
    lengths = jnp.asarray([0, 12, 24], jnp.int32)
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    got = PagedKVCache(k, v, tables, bs, decode_impl=impl).attend_block(
        0, q, lengths)
    keys = np.take(np.asarray(k[0]), np.asarray(tables), axis=0).reshape(b, 4 * bs, hkv, d)
    vals = np.take(np.asarray(v[0]), np.asarray(tables), axis=0).reshape(b, 4 * bs, hkv, d)
    for s in range(b):
        t = int(lengths[s]) + n
        kk = np.repeat(keys[s, :t], h // hkv, axis=1)
        vv = np.repeat(vals[s, :t], h // hkv, axis=1)
        sc = np.einsum("ihd,thd->iht", np.asarray(q[s]), kk) / np.sqrt(d)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("iht,thd->ihd", p / p.sum(-1, keepdims=True), vv)
        np.testing.assert_allclose(np.asarray(got[s]), want, atol=2e-5)


def test_the_flash_forward_takes_a_block_causal_mask():
    """Query ``i`` sees key ``j`` iff ``j // B <= i // B``: the flash forward
    (interpreted, tiles of 128 so that some lie above the block diagonal
    and are skipped) and ``blockwise_attention`` against a plain masked
    softmax; causal attention differs from it."""
    from theanompi_tpu.ops.pallas_attention import flash_attention
    from theanompi_tpu.parallel.ring_attention import blockwise_attention

    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 384, 2, 64), jnp.float32)
               for _ in range(3))
    i = np.arange(384)
    seen = i[None, :] // 4 <= i[:, None] // 4
    s = np.einsum("thd,shd->hts", np.asarray(q[0]), np.asarray(k[0])) / 8.0
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hts,shd->thd", p / p.sum(-1, keepdims=True),
                     np.asarray(v[0]))
    flash = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True, block=4)
    plain = blockwise_attention(q, k, v, causal=True, block_size=100, block=4)
    np.testing.assert_allclose(np.asarray(flash[0]), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(plain[0]), want, atol=2e-5)
    causal = blockwise_attention(q, k, v, causal=True)
    assert np.abs(np.asarray(causal[0]) - want).max() > 1e-2


def test_the_softmax_router_without_a_shared_expert_is_a_dense_loop():
    """``DroplessMoE(router="softmax", shared_dim=0)``: no ``b_corr`` and no
    ``shared`` leaves; the grouped products give ``sum_i w_i E_i(u)`` over
    the top-k of a float32 softmax, renormalised, as a loop over every
    expert does."""
    from theanompi_tpu.ops.moe import DroplessMoE

    moe = DroplessMoE(32, 8, 3, None, 16, 0, activation="silu_gated",
                      router="softmax")
    params = moe.init(jax.random.PRNGKey(2), (32,))[0]
    assert set(params) == {"router", "w1", "w2"}
    assert set(params["router"]) == {"w"}
    u = jax.random.normal(jax.random.PRNGKey(3), (10, 32), jnp.float32)
    got, stats = moe.apply_tokens(params, u)
    probs = jax.nn.softmax(u @ params["router"]["w"], axis=-1)
    top = np.argsort(-np.asarray(probs), axis=-1)[:, :3]
    want = np.zeros((10, 32), np.float32)
    for t in range(10):
        w = np.asarray(probs)[t, top[t]]
        for e, we in zip(top[t], w / w.sum()):
            hid = np.asarray(u[t] @ params["w1"][e])
            y = (hid[:16] / (1 + np.exp(-hid[:16]))) * hid[16:]
            want[t] += we * (y @ np.asarray(params["w2"][e]))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    assert int(stats["local_hits"]) == 30
