"""Adapter of ``model_type`` ``ouro`` for the serving driver
(:mod:`benchmarks.serve_arch`): how to build the program's ``HybridLM`` as a
looped stack from a configuration file and hand it the seed's weights, how
to run the plain reference over what was served, and what work a step
needs — the layers' weights ``total_ut_steps`` times, K/V of one entry per
(loop step, layer).
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.arch import ouro_reference as ref

#: the program's parameter path below a layer -> the reference's leaf name
_PROGRAM = {
    "attn": {("norm", "scale"): "g1", ("mixer", "q", "w"): "wq",
             ("mixer", "k", "w"): "wk", ("mixer", "v", "w"): "wv",
             ("mixer", "o", "w"): "wo", ("post_norm", "scale"): "g2"},
    "mlp": {("norm", "scale"): "g3", ("mixer", "gate", "w"): "w_gate",
            ("mixer", "up", "w"): "w_up", ("mixer", "down", "w"): "w_down",
            ("post_norm", "scale"): "g4"},
}
_TOP = {("embed", "w"): "embed", ("norm_f", "scale"): "norm_f",
        ("head", "w"): "head", ("exit_gate", "w"): "gate_w",
        ("exit_gate", "b"): "gate_b"}


def model_config(cfg: dict) -> dict:
    """The program's ``HybridLM`` config for a configuration file: each
    published layer is a ``*`` and a ``-`` of the pattern."""
    run = cfg["run"]
    return dict(
        pattern="*-" * cfg["num_hidden_layers"], dim=cfg["hidden_size"],
        vocab=cfg["vocab_size"], seq_len=run["max_context"],
        norm_eps=cfg["rms_norm_eps"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), ffn_dim=cfg["intermediate_size"],
        post_norm=True, loops=cfg["total_ut_steps"],
        exit_threshold=float(cfg["early_exit_threshold"]),
        precision=run["precision"], weights=run["weights"], verbose=False)


def check_sizes(cfg: dict) -> None:
    """What the file says twice must agree, and what the program does not
    have must be off."""
    if cfg["layer_types"] != ["full_attention"] * cfg["num_hidden_layers"]:
        raise ValueError("layer_types: every layer is full_attention")
    if cfg["sliding_window"] is not None or cfg["use_sliding_window"] \
            or cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"]:
        raise ValueError("sliding window, rope scaling and a tied head are "
                         "not in the program")
    if (cfg["n_embd"], cfg["n_head"], cfg["n_inner"], cfg["n_positions"]) != (
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["max_position_embeddings"]):
        raise ValueError("the harness's five keys and the source's disagree")
    if cfg["hidden_act"] != "silu":
        raise ValueError("the gated FFN's activation is silu")


def seeded_params(model, cfg: dict, seed: int):
    """The program's parameter tree for ``--seed``: every leaf the bf16
    array the reference's generator makes for it, made on the device leaf
    by leaf.  Program layers ``2 l`` (``*``) and ``2 l + 1`` (``-``) are the
    reference's layer ``l``."""
    key = ref.seed_key(seed)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    kinds = dict(model.layers)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for path, want in flat:
        path = tuple(k.key for k in path)
        if path in _TOP:
            x = ref.leaf(cfg, key, "top", _TOP[path], 0)
        else:
            x = ref.leaf(cfg, key, "layer", _PROGRAM[kinds[path[0]]][path[1:]],
                         int(path[0].split("_")[0]) // 2)
        if tuple(x.shape) != tuple(want.shape):
            raise ValueError(f"{path}: benchmark leaf has shape {x.shape}, "
                             f"the program expects {want.shape}")
        leaves.append(x)
    return jax.tree_util.tree_unflatten(tree, leaves)


def build(cfg: dict, seed: int):
    """-> (model, engine, scheduler) as ``tmserve`` builds them."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.scheduler import Scheduler

    check_sizes(cfg)
    run = cfg["run"]
    model = HybridLM(model_config(cfg))
    params = seeded_params(model, cfg, seed)
    engine = InferenceEngine(model, params, block_size=run["block_size"],
                             num_blocks=run["num_blocks"],
                             max_batch=run["max_batch"],
                             seed=int(seed) & 0x7FFFFFFF)
    return model, engine, Scheduler(engine)


def vocab(cfg: dict) -> int:
    """The ids traffic may draw: the whole vocabulary."""
    return cfg["vocab_size"]


def served_gaps(cfg: dict, seed: int, sample: list, control: bool = False) -> dict:
    """The reference over each sampled request's prompt and served tokens,
    all of them through each layer-run together; -> the widest gap by which
    a served token's logit lies below the reference's best.  ``control``:
    instead of the served tokens, at each position the token the fp8
    control puts first."""
    n_pos = cfg["run"]["max_context"]
    toks = np.zeros((len(sample), n_pos), np.int32)
    served, mask = np.zeros_like(toks), np.zeros(toks.shape, bool)
    for r, (prompt, generated) in enumerate(sample):
        full = list(prompt) + list(generated)
        toks[r, :len(full) - 1], served[r, :len(full) - 1] = full[:-1], full[1:]
        mask[r, len(prompt) - 1:len(full) - 1] = True  # the served positions
    return {"widest_logit_gap": ref.served_gap(cfg, seed, toks, served, mask,
                                               control),
            "tokens_compared": int(mask.sum())}


# -- the work a step needs, from the shapes alone ---------------------------------

def layer_runs(cfg: dict) -> int:
    """Layers a token passes, and K/V entries it leaves: loops x layers."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def layer_params(cfg: dict) -> int:
    """One layer: four projections, the gated FFN's three, four norm scales."""
    return ref.kind_params(cfg, "layer")


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over every entry of the pool."""
    return layer_runs(cfg) * 2 * ref.sizes(cfg)["hkv"] * itemsize


def matmul_flops_per_token(cfg: dict, head: bool = True) -> float:
    """Forward FLOPs of one token through every weight product (2 a
    multiply-add): loops x layers layer-runs, a gate a loop step, one head."""
    z = ref.sizes(cfg)
    per_run = 2.0 * (z["d"] * (z["hq"] + 2 * z["hkv"]) + z["hq"] * z["d"]
                     + 3 * z["d"] * z["f"])
    body = layer_runs(cfg) * per_run + z["loops"] * 2.0 * z["d"]
    return body + (2.0 * z["d"] * z["rows"] if head else 0.0)


def attn_flops_token(cfg: dict, context: float) -> float:
    """q.K^T and p.V of one query token over ``context`` keys, all heads, in
    every entry."""
    return layer_runs(cfg) * 4.0 * context * ref.sizes(cfg)["hq"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Every prompt token through the layer-runs, causal attention, and the
    head for the last position only."""
    z = ref.sizes(cfg)
    body = prompt_len * matmul_flops_per_token(cfg, head=False)
    attn = attn_flops_token(cfg, 1.0) * prompt_len * (prompt_len + 1) / 2.0
    return body + attn + 2.0 * z["d"] * z["rows"]


def decode_flops(cfg: dict, context: float) -> float:
    """One decode token attending over ``context`` cached tokens."""
    return matmul_flops_per_token(cfg) + attn_flops_token(cfg, context)


def step_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Weight bytes one decode step must read: the stack does not fit the
    chip's fast memory and step ``t + 1`` of layer 1 needs step ``t`` of the
    last layer, so every layer once a LOOP STEP, with the final norm and the
    gate; the head once."""
    z = ref.sizes(cfg)
    looped = ref.parameter_count(cfg) - 2 * z["d"] * z["rows"]  # + norm, gate
    return itemsize * (z["loops"] * looped + z["d"] * z["rows"])


def decode_bytes(cfg: dict, context_tokens: int, n_slots: int) -> float:
    """The LEAST HBM bytes one decode step must move: the weights as
    :func:`step_weight_bytes` counts them and the embedding rows gathered,
    K and V of every token in context over every entry plus the step's own
    K/V written, and the float32 logits written."""
    z = ref.sizes(cfg)
    weights = step_weight_bytes(cfg) + 2 * n_slots * z["d"]
    kv = kv_bytes_per_token(cfg) * (context_tokens + n_slots)
    return float(weights + kv + 4 * n_slots * z["rows"])


def paged_decode_bytes(cfg: dict, context_tokens: float, n_slots: float,
                       itemsize: int = 2) -> float:
    """HBM bytes one decode step's attention must move, whatever implements
    it: K and V of every token in context in every entry, plus q in and the
    context vector out per slot and entry."""
    return (kv_bytes_per_token(cfg, itemsize) * context_tokens
            + layer_runs(cfg) * 2.0 * n_slots * ref.sizes(cfg)["hq"] * itemsize)


def paged_decode_flops(cfg: dict, context_tokens: float) -> float:
    return attn_flops_token(cfg, 1.0) * context_tokens
