"""Device work is named by scope (ISSUE 25): in the lowered tiny train
step, decode step and prefill program every ``dot_general``, every custom
call and every all-reduce lies under one of the scope names, and each
scope name occurs.  Metadata only: the lowering is read, nothing runs."""

import re

import jax
import jax.numpy as jnp
import pytest

TINY = {"batch_size": 2, "n_train": 16, "n_val": 8, "seq_len": 32,
        "vocab": 61, "dim": 32, "heads": 2, "n_layers": 2, "dropout": 0.0,
        "n_epochs": 1, "precision": "bf16", "grad_clip": 1.0}

MODEL = ("embed", "block", "attn", "mlp")
HYBRID = ("embed", "mamba", "moe.route", "moe.experts", "moe.shared", "attn",
          "head")
LOOPED = ("embed", "attn", "mlp", "loop.exit", "head", "sample")
WINDOWED = ("embed", "attn", "attn.window", "mlp", "moe.route", "moe.experts",
            "moe.shared", "head", "sample")
BLOCKDIFF = ("embed", "attn", "moe.route", "moe.experts", "head",
             "diffusion.select")
LATENT = ("embed", "attn.latent", "mlp", "moe.route", "moe.experts",
          "moe.shared", "head", "sample")
#: program -> the scopes it must show
EXPECTED = {
    "train": (*MODEL, "loss", "clip", "exchange", "optimizer"),
    "train_plain_loss": (*MODEL, "head", "loss", "clip", "exchange",
                         "optimizer"),
    # the engine's own programs take the tree it holds, already in the
    # dtype they read (PR 36): their ``recast`` scope lowers to nothing ...
    "decode": (*MODEL, "head", "sample", "paged_decode"),
    "prefill": (*MODEL, "head", "sample"),
    # ... and is there for a caller that hands the fp32 tree in directly
    "decode_fp32_tree": (*MODEL, "head", "recast", "sample", "paged_decode"),
    "prefill_fp32_tree": (*MODEL, "head", "recast", "sample"),
    # HybridLM (ISSUE 27): one mixer a layer, each under its own scope
    "hybrid_decode": (*HYBRID, "sample"),
    "hybrid_prefill": (*HYBRID, "sample"),
    # a looped stack (ISSUE 31): the ``-`` mixer and the end of a loop step
    "looped_decode": LOOPED,
    "looped_prefill": LOOPED,
    # window and full attention layers in one model (ISSUE 33)
    "window_decode": WINDOWED,
    "window_prefill": WINDOWED,
    # block diffusion: a pass's selection in its own scope, a prefill that
    # reads out nothing
    "blockdiff_decode": BLOCKDIFF,
    "blockdiff_prefill": ("embed", "attn", "moe.route", "moe.experts"),
    # latent attention: the decode step absorbs W_kvb, through the latent
    # kernel; the prefill decompresses it
    "latent_decode": (*LATENT, "attn.latent.absorb", "paged_decode_latent"),
    "latent_prefill": (*LATENT, "attn.latent.decompress"),
}
SCOPES = sorted({s for names in EXPECTED.values() for s in names}
                | {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"})
_WORD = re.compile(r"(?<![A-Za-z0-9_])(" + "|".join(map(re.escape, SCOPES))
                   + r")(?![A-Za-z0-9_.])")
_OPS = ("stablehlo.dot_general", "stablehlo.custom_call",
        "stablehlo.all_reduce")
_LOC = re.compile(r"loc\((#loc\d+)\)\s*$")


def named_ops(text: str) -> tuple[list, set]:
    """-> ([(op, its whole name stack)], every scope name in any stack).

    ``as_text(debug_info=True)`` names an op by a ``#locN`` alias whose
    string is the name stack relative to the function the op is in; the
    stack of a private function (a scan body, a ``closed_call``) is that of
    its ``call`` sites, prepended here."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    lines = text.splitlines()
    callers: dict = {}
    found, fn = [], None
    for i, line in enumerate(lines):
        m = re.search(r"func\.func (?:public |private )?@([\w.]+)\(", line)
        if m:
            fn = m.group(1)
            continue
        loc = _LOC.search(line)
        op = next((o for o in _OPS if o in line), None)
        if op and not loc:  # an op with a region: the name is on its last line
            indent = " " * (len(line) - len(line.lstrip()))
            loc = _LOC.search(next(x for x in lines[i + 1:]
                                   if x.startswith(indent + "})")))
        name = names.get(loc.group(1), "") if loc else ""
        call = re.search(r"call @([\w.]+)\(", line)
        if call:
            callers.setdefault(call.group(1), []).append((fn, name))
        if op:
            found.append((op, fn, name))

    def stacks(f, seen=()):
        if f not in callers or f in seen:
            return [""]
        return [p + "/" + n for cf, n in callers[f]
                for p in stacks(cf, seen + (f,))]

    ops = [(op, p + "/" + name) for op, f, name in found for p in stacks(f)]
    # argument names (params['head']...) are no name stacks: ops' names only
    seen = {w for n in names.values() if "[" not in n for w in _WORD.findall(n)}
    return ops, seen


@pytest.fixture(scope="module")
def lowered():
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.parallel.bsp import BSPTrainer
    from theanompi_tpu.parallel.mesh import make_mesh
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.utils.helper_funcs import shard_batch
    from theanompi_tpu.utils.recorder import Recorder

    out = {}
    for key, fused in (("train", True), ("train_plain_loss", False)):
        model = TransformerLM(dict(TINY, fused_loss=fused))
        t = BSPTrainer(model, mesh=make_mesh(n_data=2, devices=jax.devices()[:2]),
                       recorder=Recorder(verbose=False))
        t.compile_iter_fns()
        t.init_state()
        batch = shard_batch(t.mesh, next(iter(model.data.train_batches(
            t.global_batch, 0, seed=0))), spec=t.batch_spec)
        out[key] = t._step_fn.lower(
            t.params, t.state, t.opt_state, batch, jnp.float32(0.01),
            jnp.int32(0)).as_text(debug_info=True)
    params, _ = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                          decode_kernel="on")
    b, i32 = eng.max_batch, jnp.int32
    for key, tree in (("", eng.params), ("_fp32_tree", params)):
        out["decode" + key] = eng._decode_fn.lower(
            tree, eng._k, eng._v,
            jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
            jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), i32), eng._base_key).as_text(debug_info=True)
        out["prefill" + key] = jax.jit(
            eng._prefill_impl, donate_argnums=(1, 2)).lower(
                tree, eng._k, eng._v, jnp.zeros((2,), i32),
                jnp.zeros((16,), i32), jnp.asarray(5, i32),
                jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32),
                eng._base_key).as_text(debug_info=True)

    from theanompi_tpu.models.hybrid_lm import HybridLM

    hybrid = HybridLM({"pattern": "ME*", "dim": 32, "vocab": 61, "seq_len": 32,
                       "heads": 4, "kv_heads": 2, "head_dim": 8,
                       "mamba_heads": 4, "mamba_head_dim": 16, "state_size": 8,
                       "n_groups": 2, "chunk_size": 8, "n_experts": 8,
                       "experts_held": (2, 6), "top_k": 2, "latent": 16,
                       "expert_dim": 24, "shared_dim": 32})
    eng = InferenceEngine(hybrid, hybrid.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2)
    out["hybrid_decode"] = eng._decode_fn.lower(
        eng.params, eng._k, eng._v,
        jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
        jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key,
        eng._state).as_text(debug_info=True)
    out["hybrid_prefill"] = jax.jit(
        eng._prefill_impl, donate_argnums=(1, 2, 9)).lower(
        eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
        jnp.zeros((16,), i32), jnp.asarray(5, i32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32),
        eng._base_key, eng._state,
        jnp.asarray(1, i32)).as_text(debug_info=True)

    looped = HybridLM({"pattern": "*-*-", "dim": 32, "vocab": 61, "seq_len": 32,
                       "heads": 4, "kv_heads": 4, "head_dim": 8, "ffn_dim": 48,
                       "loops": 3, "post_norm": True, "rope_theta": 1e4})
    eng = InferenceEngine(looped, looped.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2)
    out["looped_decode"] = eng._decode_fn.lower(
        eng.params, eng._k, eng._v,
        jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
        jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key).as_text(debug_info=True)
    out["looped_prefill"] = jax.jit(
        eng._prefill_impl, donate_argnums=(1, 2)).lower(
        eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
        jnp.zeros((16,), i32), jnp.asarray(5, i32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32),
        eng._base_key).as_text(debug_info=True)
    window = HybridLM({"pattern": "*-wE", "dim": 32, "vocab": 61, "seq_len": 32,
                       "heads": 4, "window_heads": 8, "kv_heads": 2,
                       "head_dim": 8, "ffn_dim": 48, "window": 8,
                       "attn_gate": True, "rope_theta": 5e5, "rope_share": 0.5,
                       "rope_yarn": {"factor": 8, "original_max_position": 16,
                                     "beta_fast": 4, "beta_slow": 1},
                       "window_rope_theta": 1e4, "n_experts": 8, "top_k": 2,
                       "latent": None, "expert_dim": 16, "shared_dim": 16,
                       "expert_act": "silu_gated"})
    eng = InferenceEngine(window, window.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2)
    out["window_decode"] = eng._decode_fn.lower(
        eng.params, eng._k, eng._v,
        jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
        jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key,
        eng._state).as_text(debug_info=True)
    out["window_prefill"] = jax.jit(
        eng._prefill_impl, donate_argnums=(1, 2, 9)).lower(
        eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
        jnp.zeros((16,), i32), jnp.asarray(5, i32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32),
        eng._base_key, eng._state,
        jnp.asarray(1, i32)).as_text(debug_info=True)
    block = HybridLM({"pattern": "*E*", "dim": 32, "vocab": 61, "seq_len": 32,
                      "heads": 4, "kv_heads": 2, "head_dim": 8,
                      "rope_theta": 1e6, "qk_norm": True, "n_experts": 8,
                      "top_k": 2, "latent": None, "expert_dim": 16,
                      "shared_dim": 0, "expert_act": "silu_gated",
                      "router": "softmax", "block_len": 4, "mask_id": 60})
    eng = InferenceEngine(block, block.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2)
    out["blockdiff_decode"] = eng._decode_fn.lower(
        eng.params, eng._k, eng._v,
        jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
        jnp.zeros((b, 6), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key).as_text(debug_info=True)
    out["blockdiff_prefill"] = jax.jit(
        eng._prefill_blocks_impl, donate_argnums=(1, 2)).lower(
        eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
        jnp.zeros((16,), i32)).as_text(debug_info=True)
    latent = HybridLM({"pattern": "L-LE", "dim": 32, "vocab": 61,
                       "seq_len": 32, "heads": 4, "q_lora": 16, "kv_lora": 16,
                       "qk_nope": 8, "qk_rope": 8, "v_head": 8,
                       "rope_theta": 1e4, "rope_interleave": True,
                       "ffn_dim": 48, "n_experts": 8, "experts_held": (0, 4),
                       "top_k": 2, "latent": None, "expert_dim": 16,
                       "shared_dim": 16, "expert_act": "silu_gated",
                       "route_scale": 2.5})
    eng = InferenceEngine(latent, latent.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2, decode_kernel="on")
    out["latent_decode"] = eng._decode_fn.lower(
        eng.params, eng._k, eng._v,
        jnp.zeros((b, eng.max_blocks_per_seq), i32), jnp.zeros((b,), i32),
        jnp.zeros((b,), i32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), i32), eng._base_key).as_text(debug_info=True)
    out["latent_prefill"] = jax.jit(
        eng._prefill_impl, donate_argnums=(1, 2)).lower(
        eng.params, eng._k, eng._v, jnp.zeros((2,), i32),
        jnp.zeros((16,), i32), jnp.asarray(5, i32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(1, i32),
        eng._base_key).as_text(debug_info=True)
    return out


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_every_matmul_kernel_and_all_reduce_lies_under_a_scope(lowered, program):
    ops, seen = named_ops(lowered[program])
    kinds = {op for op, _ in ops}
    assert "stablehlo.dot_general" in kinds
    if program.startswith("train"):
        assert "stablehlo.all_reduce" in kinds  # two workers: the exchange
    outside = [(op, stack) for op, stack in ops if not _WORD.search(stack)]
    assert not outside, outside[:5]
    missing = set(EXPECTED[program]) - seen
    assert not missing, (program, sorted(seen))


def test_the_exchange_and_the_loss_own_their_ops(lowered):
    """The gradient all-reduce reads ``exchange`` (or the optimizer's
    ``clip`` norm), the fused loss's matmuls read ``loss``, on the way
    forward and on the way back."""
    ops, _ = named_ops(lowered["train"])
    reduces = [s for op, s in ops if op == "stablehlo.all_reduce"]
    assert reduces and all(re.search(r"exchange|clip", s) for s in reduces)
    loss = [s for op, s in ops
            if op == "stablehlo.dot_general" and "loss" in s]
    assert any("transpose" in s for s in loss)
    assert any("transpose" not in s for s in loss)
    blocks = [s for op, s in ops if "block" in s]
    assert all(re.search(r"attn|mlp", s) for s in blocks)


@pytest.mark.parametrize("program", ["window_decode", "window_prefill"])
def test_each_attention_kind_owns_its_products_and_its_gate(lowered, program):
    """A ``*`` layer's products read ``attn``, a ``w`` layer's
    ``attn.window``; each kind's five weight products (q|k|v fused, the
    gate, o, and the two of the attention itself) lie inside its scope, the
    gate's ``[dim, heads]`` among them."""
    ops, _ = named_ops(lowered[program])
    dots = [s for op, s in ops if op == "stablehlo.dot_general"]
    full = [s for s in dots if re.search(r"attn(?![.\w])", s)]
    band = [s for s in dots if "attn.window" in s]
    assert len(full) == len(band) == 5, (len(full), len(band))
    text = lowered[program]
    # the gates: 32 -> 4 heads under attn, 32 -> 8 heads under attn.window
    for heads in (4, 8):
        assert re.search(rf"tensor<32x{heads}xbf16>\) -> tensor<[0-9x]*x{heads}xbf16>",
                         text), heads


def test_the_jitted_programs_keep_their_names(lowered):
    """The benchmark's traffic files find the programs by these names."""
    assert "module @jit_local_step" in lowered["train"]
    assert "module @jit__decode_impl" in lowered["decode"]
    assert "module @jit__decode_impl" in lowered["hybrid_decode"]
    assert "module @jit__decode_impl" in lowered["looped_decode"]
    assert "module @jit__block_impl" in lowered["blockdiff_decode"]


def test_the_documented_scopes_are_the_ones_the_programs_show(lowered):
    from theanompi_tpu.telemetry.metrics import DEVICE_SCOPES

    documented = {s for names in DEVICE_SCOPES.values() for s in names}
    assert set(SCOPES) <= documented
    assert set(DEVICE_SCOPES["HybridLM"]) == (
        set(HYBRID) | set(LOOPED) | set(WINDOWED) | set(BLOCKDIFF)
        | set(LATENT) | {"attn.latent.absorb", "attn.latent.decompress"}) \
        - {"sample"}
