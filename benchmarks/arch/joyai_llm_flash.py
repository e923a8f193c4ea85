"""Adapter of ``model_type`` ``joyai_llm_flash`` for the serving harness
(:mod:`benchmarks.serve_arch`): how to build the program's ``HybridLM`` from
a configuration file — each published layer two letters, ``L`` for its
latent attention and ``-`` or ``E`` for its FFN — and hand it the seed's
weights, how to run the plain reference over what was served, and what work
a step needs: a decode step's absorbed attention reads ONE latent row a
token and layer, a prefill's decompressed attention the keys and values of
every head; the held experts that receive a row, and not all of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.arch import joyai_llm_flash_reference as ref

#: fewer decided positions than this in a sample is no comparison
MIN_COMPARED = 8
#: the program's parameter path below a layer -> the reference's leaf name
_PROGRAM = {
    "mla": ("attn", {("norm", "scale"): "norm1",
                     ("mixer", "q_a", "w"): "wq_a",
                     ("mixer", "q_norm", "scale"): "q_norm",
                     ("mixer", "q_b", "w"): "wq_b",
                     ("mixer", "kv_a", "w"): "wkv_a",
                     ("mixer", "kv_norm", "scale"): "kv_norm",
                     ("mixer", "kv_b", "w"): "wkv_b",
                     ("mixer", "o", "w"): "wo"}),
    "mlp": ("dense", {("norm", "scale"): "norm2",
                      ("mixer", "gate", "w"): "w_gate",
                      ("mixer", "up", "w"): "w_up",
                      ("mixer", "down", "w"): "w_down"}),
    "moe": ("sparse", {("norm", "scale"): "norm2",
                       ("mixer", "router", "w"): "router_w",
                       ("mixer", "router", "b_corr"): "b_corr",
                       ("mixer", "w1"): "w1", ("mixer", "w2"): "w2",
                       ("mixer", "shared", "v1"): "v1",
                       ("mixer", "shared", "v2"): "v2"}),
}
_TOP = {("embed", "w"): "embed", ("norm_f", "scale"): "norm_f",
        ("head", "w"): "head"}
_FFN = {"dense": "-", "sparse": "E"}


def pattern(cfg: dict) -> str:
    """Two letters a published layer: its latent attention, then its FFN."""
    return "".join("L" + _FFN[ref.layer_kind(cfg, l)]
                   for l in range(cfg["num_hidden_layers"]))


def model_config(cfg: dict) -> dict:
    """The program's ``HybridLM`` config for a configuration file."""
    run = cfg["run"]
    return dict(
        pattern=pattern(cfg), dim=cfg["hidden_size"], vocab=cfg["vocab_size"],
        seq_len=run["max_context"], norm_eps=cfg["rms_norm_eps"],
        heads=cfg["num_attention_heads"], q_lora=cfg["q_lora_rank"],
        kv_lora=cfg["kv_lora_rank"], qk_nope=cfg["qk_nope_head_dim"],
        qk_rope=cfg["qk_rope_head_dim"], v_head=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_interleave=bool(cfg["rope_interleave"]),
        ffn_dim=cfg["intermediate_size"], n_experts=cfg["router_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"], latent=None,
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        route_scale=float(cfg["routed_scaling_factor"]),
        expert_act="silu_gated", router="sigmoid",
        precision=run["precision"], weights=run["weights"], verbose=False)


def check_sizes(cfg: dict) -> None:
    """What the file says twice must agree, and what the program does not
    have must be off."""
    if cfg["hybrid_override_pattern"] != pattern(cfg):
        raise ValueError("hybrid_override_pattern is not the layers' letters")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"] or not 0 <= lo < hi <= cfg[
            "router_experts"]:
        raise ValueError("experts_held must be n_routed_experts of the "
                         "router's experts")
    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["rope_scaling"] is not None or cfg["hidden_act"] != "silu"
            or cfg["scoring_func"] != "sigmoid"
            or cfg["topk_method"] != "noaux_tc" or not cfg["norm_topk_prob"]
            or (cfg["n_group"], cfg["topk_group"]) != (1, 1)
            or cfg["n_shared_experts"] != 1
            or cfg["num_nextn_predict_layers"] != 0):
        raise ValueError("a bias, a tied head, scaled rotary, another "
                         "router, group-limited routing, another shared "
                         "expert count or a multi-token-prediction layer is "
                         "not in the program")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if (cfg["n_embd"], cfg["n_head"], cfg["n_inner"], cfg["n_positions"]) != (
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["max_position_embeddings"]):
        raise ValueError("the harness's keys and the source's disagree")
    if ref.parameter_count(cfg) != cfg["parameters"] or \
            ref.cache_bytes_per_token(cfg) != cfg["cache_bytes_per_token"]:
        raise ValueError("parameters or cache_bytes_per_token is not what "
                         "the sizes make")


def seeded_params(model, cfg: dict, seed: int):
    """The program's parameter tree for ``--seed``: every leaf the bf16
    array the reference's generator makes for it, made on the device leaf
    by leaf.  Program layers ``2 l`` (attention) and ``2 l + 1`` (FFN) are
    the reference's layer ``l``."""
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
            kind, names = _PROGRAM[kinds[path[0]]]
            x = ref.leaf(cfg, key, kind, names[path[1:]],
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


def served_gaps(cfg: dict, seed: int, sample: list, control: bool = False,
                drop: str = "") -> dict:
    """The reference over each sampled request's prompt and served tokens,
    layer-outer (a layer's weights are made once for all of them), the head
    at the served positions only; -> the widest gap by which a served
    token's logit lies below the reference's best, over the positions whose
    routing the reference decides.  Sequences are padded to a whole number
    of the reference's query blocks, no further.  ``control``: instead of
    the served tokens, at each position the token the fp8 control puts
    first; ``drop``: the reference without that term."""
    longest = max(len(p) + len(g) for p, g in sample) - 1
    n_pos = -(-longest // ref.QUERY_BLOCK) * ref.QUERY_BLOCK
    n_served = max(len(g) for _, g in sample)
    toks = np.zeros((len(sample), n_pos), np.int32)
    lengths = np.zeros((len(sample),), np.int32)
    at = np.zeros((len(sample), n_served), np.int32)
    served, mask = np.zeros_like(at), np.zeros(at.shape, bool)
    for r, (prompt, generated) in enumerate(sample):
        full = list(prompt) + list(generated)
        toks[r, :len(full) - 1] = full[:-1]
        lengths[r] = len(full) - 1
        n = len(generated)
        at[r, :n] = np.arange(len(prompt) - 1, len(full) - 1)
        served[r, :n], mask[r, :n] = generated, True
    widest, n = ref.served_gap(cfg, seed, toks, lengths, at, served, mask,
                               control, drop)
    if n < MIN_COMPARED and not (control or drop):
        raise RuntimeError(f"only {n} of {int(mask.sum())} served positions "
                           f"have their routing decided: too few to compare")
    return {"widest_logit_gap": widest, "tokens_compared": n,
            "tokens_served": int(mask.sum())}


# -- the work a step needs, from the shapes alone ---------------------------------

def _held_share(cfg: dict) -> float:
    """The share of a token's assignments this chip's experts take under
    an even router: held experts over the router's."""
    return cfg["n_routed_experts"] / cfg["router_experts"]


def expected_experts_hit(cfg: dict, n_tokens: float) -> float:
    """Held experts of one layer that receive at least one of ``n_tokens``
    tokens' assignments under an even router: ``E_held (1 - (1 -
    1/E_router) ** (k n))`` (62.9 of 64 for 128 slots x 8)."""
    e = cfg["router_experts"]
    return cfg["n_routed_experts"] * (
        1.0 - (1.0 - 1.0 / e) ** (cfg["num_experts_per_tok"] * n_tokens))


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _ffn_flops_per_token(cfg: dict) -> float:
    """Every FFN half of the stack for one token: the dense layers'
    SwiGLU; an expert layer's router, shared expert and the token's
    expected assignments to the held experts."""
    z = ref.sizes(cfg)
    d, total = z["d"], 0.0
    for l in range(z["layers"]):
        if ref.layer_kind(cfg, l) == "dense":
            total += 6.0 * d * z["f"]
        else:
            total += (2.0 * d * z["router"] + 6.0 * d * z["fs"]
                      + cfg["num_experts_per_tok"] * _held_share(cfg)
                      * 2.0 * expert_params(cfg))
    return total


def _mla_projection_flops(cfg: dict) -> float:
    """One token through one layer's query and latent projections and
    ``W_o``: what both routes share."""
    z = ref.sizes(cfg)
    d, h = z["d"], z["h"]
    return 2.0 * (d * z["q_lora"] + z["q_lora"] * h * (z["nope"] + z["rope"])
                  + d * (z["kv_lora"] + z["rope"]) + h * z["v"] * d)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Every prompt token through the layers — MLA decompressed (``W_kvb``
    a token, causal attention of ``qk_head_dim`` keys and ``v_head_dim``
    values a head over ``p (p + 1) / 2`` pairs) — and the head for the
    last position only."""
    z = ref.sizes(cfg)
    p, h = prompt_len, z["h"]
    mla = (_mla_projection_flops(cfg)
           + 2.0 * z["kv_lora"] * h * (z["nope"] + z["v"]))
    pairs = p * (p + 1) / 2.0
    attn = 2.0 * pairs * h * (z["nope"] + z["rope"] + z["v"])
    return (p * (z["layers"] * mla + _ffn_flops_per_token(cfg))
            + z["layers"] * attn + 2.0 * z["d"] * z["rows"])


def paged_decode_flops(cfg: dict, context_tokens: float) -> float:
    """Absorbed decode attention over ``context_tokens`` keys (summed over
    the slots), every layer: ``2 H (latent + kv_lora)`` a key — scores over
    the whole row, the context over its first ``kv_lora`` columns."""
    z = ref.sizes(cfg)
    width = z["kv_lora"] + z["rope"]
    return z["layers"] * 2.0 * z["h"] * (width + z["kv_lora"]) * context_tokens


def decode_flops(cfg: dict, context: float) -> float:
    """One decode token over ``context`` cached tokens, absorbed: the
    projections, ``q_nope W_UK^T`` and ``o_lat W_UV`` a head, the attention
    over the latent rows, the FFNs and the head."""
    z = ref.sizes(cfg)
    absorb = 2.0 * 2 * z["h"] * z["kv_lora"] * z["nope"]
    return (z["layers"] * (_mla_projection_flops(cfg) + absorb)
            + _ffn_flops_per_token(cfg) + paged_decode_flops(cfg, context)
            + 2.0 * z["d"] * z["rows"])


def step_weight_bytes(cfg: dict, n_slots: float, itemsize: int = 2) -> float:
    """Weight bytes one decode step of ``n_slots`` tokens must read: every
    leaf once but the embedding (the rows gathered) and the held experts,
    of which :func:`expected_experts_hit` an expert layer."""
    z = ref.sizes(cfg)
    sparse = sum(ref.layer_kind(cfg, l) == "sparse" for l in range(z["layers"]))
    held = sparse * z["held"] * expert_params(cfg)
    hit = sparse * expected_experts_hit(cfg, n_slots) * expert_params(cfg)
    fixed = ref.parameter_count(cfg) - held - z["rows"] * z["d"]
    return itemsize * (fixed + hit + n_slots * z["d"])


def paged_decode_bytes(cfg: dict, context_tokens: float, n_slots: float,
                       itemsize: int = 2) -> float:
    """HBM bytes one decode step's attention must move, whatever
    implements it: one latent row of every key in context (summed over the
    slots) in every layer, read ONCE for keys and values alike, plus each
    slot's absorbed queries in and latent contexts out a layer."""
    z = ref.sizes(cfg)
    width = z["kv_lora"] + z["rope"]
    return z["layers"] * itemsize * (
        width * context_tokens + n_slots * z["h"] * (width + z["kv_lora"]))


def decode_bytes(cfg: dict, context_tokens: int, n_slots: int) -> float:
    """The LEAST HBM bytes one decode step must move: the weights as
    :func:`step_weight_bytes` counts them; the latent row of every token in
    context, every layer, once; the step's own rows written; the float32
    logits written."""
    z = ref.sizes(cfg)
    row = ref.cache_bytes_per_token(cfg)
    return float(step_weight_bytes(cfg, n_slots) + row * context_tokens
                 + row * n_slots + 4 * n_slots * z["rows"])


def b_corr_spread(cfg: dict, seed: int = 0, n: int = 4096) -> dict:
    """What ``assumed.b_corr`` is sized by: the spread of the router's
    scores ``s = sigmoid(W_r u)`` over the experts on the seeded weights
    (``u`` unit-RMS rows), and the gap between the 8th and 9th of them."""
    key = ref.seed_key(seed)
    w = ref.leaf(cfg, key, "sparse", "router_w", 1).astype(jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (n, cfg["hidden_size"]))
    s = jax.nn.sigmoid(u @ w)
    top = jax.lax.top_k(s, cfg["num_experts_per_tok"] + 1)[0]
    k = cfg["num_experts_per_tok"]
    return {"score_std": float(jnp.mean(jnp.std(s, axis=-1))),
            "gap_8_9_median": float(jnp.median(top[:, k - 1] - top[:, k]))}
