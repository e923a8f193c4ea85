"""Adapter of ``model_type`` ``laguna`` for the serving driver
(:mod:`benchmarks.serve_arch`): how to build the program's ``HybridLM`` from
a configuration file — each published layer two letters, ``*`` or ``w``
for its attention and ``-`` or ``E`` for its FFN — and hand it the seed's
weights, how to run the plain reference over what was served, and what
work a step needs: a window layer's keys capped at the window, a full
layer's the whole context, the experts that receive a row and not all of
them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.arch import laguna_reference as ref

#: fewer decided positions than this in a sample is no comparison
MIN_COMPARED = 8
_LETTERS = {"full": "*", "window": "w", "dense": "-", "sparse": "E"}
#: the program's parameter path below a layer -> the reference's leaf name
_ATTN = {("norm", "scale"): "norm1", ("mixer", "q", "w"): "wq",
         ("mixer", "k", "w"): "wk", ("mixer", "v", "w"): "wv",
         ("mixer", "gate", "w"): "wg", ("mixer", "o", "w"): "wo"}
_PROGRAM = {
    "attn": ("attn", _ATTN), "attn_w": ("attn", _ATTN),
    "mlp": ("dense", {("norm", "scale"): "norm2",
                      ("mixer", "gate", "w"): "w_gate",
                      ("mixer", "up", "w"): "w_up",
                      ("mixer", "down", "w"): "w_down"}),
    "moe": ("sparse", {("norm", "scale"): "norm2",
                       ("mixer", "router", "w"): "router_w",
                       ("mixer", "w1"): "w1", ("mixer", "w2"): "w2",
                       ("mixer", "shared", "v1"): "v1",
                       ("mixer", "shared", "v2"): "v2"}),
}
_TOP = {("embed", "w"): "embed", ("norm_f", "scale"): "norm_f",
        ("head", "w"): "head"}


def pattern(cfg: dict) -> str:
    """Two letters a published layer: its attention, then its FFN."""
    return "".join(_LETTERS[k] for l in range(cfg["num_hidden_layers"])
                   for k in ref.layer_kinds(cfg, l))


def _heads(cfg: dict, kind: str) -> int:
    """The query heads of every layer of ``kind`` (they must agree)."""
    found = {h for h, t in zip(cfg["num_attention_heads_per_layer"],
                               cfg["layer_types"]) if t == kind}
    if len(found) != 1:
        raise ValueError(f"{kind} layers have query heads {sorted(found)}: "
                         f"the program has one count a kind")
    return found.pop()


def _yarn(rule: dict) -> dict | None:
    if rule["rope_type"] != "yarn":
        return None
    return dict(factor=rule["factor"],
                original_max_position=rule["original_max_position_embeddings"],
                beta_fast=rule["beta_fast"], beta_slow=rule["beta_slow"],
                attention_factor=rule["attention_factor"])


def model_config(cfg: dict) -> dict:
    """The program's ``HybridLM`` config for a configuration file."""
    run = cfg["run"]
    full = cfg["rope_parameters"]["full_attention"]
    band = cfg["rope_parameters"]["sliding_attention"]
    return dict(
        pattern=pattern(cfg), dim=cfg["hidden_size"], vocab=cfg["vocab_size"],
        seq_len=run["max_context"], norm_eps=cfg["rms_norm_eps"],
        heads=_heads(cfg, "full_attention"),
        window_heads=_heads(cfg, "sliding_attention"),
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attn_gate=bool(cfg["gating"]), window=cfg["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        rope_share=full["partial_rotary_factor"], rope_yarn=_yarn(full),
        window_rope_theta=float(band["rope_theta"]),
        window_rope_share=band["partial_rotary_factor"],
        window_rope_yarn=_yarn(band), ffn_dim=cfg["intermediate_size"],
        n_experts=cfg["num_experts"], experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"], latent=None,
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["shared_expert_intermediate_size"],
        route_scale=float(cfg["moe_routed_scaling_factor"]),
        expert_act="silu_gated", precision=run["precision"],
        weights=run["weights"], verbose=False)


def check_sizes(cfg: dict) -> None:
    """What the file says twice must agree, and what the program does not
    have must be off."""
    n = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        if len(cfg[key]) != n:
            raise ValueError(f"{key} has {len(cfg[key])} entries for {n} layers")
    if cfg["hybrid_override_pattern"] != pattern(cfg):
        raise ValueError("hybrid_override_pattern is not the layers' letters")
    if tuple(cfg["experts_held"]) != (0, cfg["num_experts"]):
        raise ValueError("experts_held: every expert is held here")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("a bias, a tied head and router weights on the "
                         "expert's input are not in the program")
    if (cfg["n_embd"], cfg["n_head"], cfg["n_inner"], cfg["n_positions"]) != (
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["max_position_embeddings"]):
        raise ValueError("the harness's keys and the source's disagree")
    if cfg["rope_parameters"]["full_attention"]["partial_rotary_factor"] \
            != cfg["partial_rotary_factor"]:
        raise ValueError("partial_rotary_factor is stated twice and differs")


def seeded_params(model, cfg: dict, seed: int):
    """The program's parameter tree for ``--seed``: every leaf the bf16
    array the reference's generator makes for it, made on the device leaf
    by leaf.  Program layers ``2 l`` (attention) and ``2 l + 1`` (FFN) are
    the reference's layer ``l``; the router's correction bias, which the
    reference does not have, is zero."""
    key = ref.seed_key(seed)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    kinds = dict(model.layers)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for path, want in flat:
        path = tuple(k.key for k in path)
        if path in _TOP:
            x = ref.leaf(cfg, key, "top", _TOP[path], 0)
        elif path[1:] == ("mixer", "router", "b_corr"):
            x = jnp.zeros(want.shape, want.dtype)
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
    token's logit lies below the reference's best.  Sequences are padded
    to a whole number of the reference's query blocks, no further.
    ``control``: instead of the served tokens, at each position the token
    the fp8 control puts first."""
    longest = max(len(p) + len(g) for p, g in sample) - 1
    n_pos = -(-longest // ref.QUERY_BLOCK) * ref.QUERY_BLOCK
    n_served = max(len(g) for _, g in sample)
    toks = np.zeros((len(sample), n_pos), np.int32)
    at = np.zeros((len(sample), n_served), np.int32)
    served, mask = np.zeros_like(at), np.zeros(at.shape, bool)
    for r, (prompt, generated) in enumerate(sample):
        full = list(prompt) + list(generated)
        toks[r, :len(full) - 1] = full[:-1]
        n = len(generated)
        at[r, :n] = np.arange(len(prompt) - 1, len(full) - 1)
        served[r, :n], mask[r, :n] = generated, True
    widest, n = ref.served_gap(cfg, seed, toks, at, served, mask, control, drop)
    if n < MIN_COMPARED:
        raise RuntimeError(f"only {n} of {int(mask.sum())} served positions "
                           f"have their routing decided: too few to compare")
    return {"widest_logit_gap": widest, "tokens_compared": n,
            "tokens_served": int(mask.sum())}


# -- the work a step needs, from the shapes alone ---------------------------------

def _layers(cfg: dict) -> list[tuple[str, str, int]]:
    """-> [(``full`` | ``window``, ``dense`` | ``sparse``, query heads x
    head size)] a layer."""
    return [(*ref.layer_kinds(cfg, l),
             cfg["num_attention_heads_per_layer"][l] * cfg["head_dim"])
            for l in range(cfg["num_hidden_layers"])]


def expected_experts_hit(cfg: dict, n_tokens: float) -> float:
    """Experts of one layer that receive at least one of ``n_tokens``
    tokens' assignments under an even router: ``E (1 - (1 - 1/E) ** (k
    n))`` (162 of 256 for 32 slots x 8)."""
    e = cfg["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (cfg["num_experts_per_tok"] * n_tokens))


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_flops_per_token(cfg: dict, head: bool = True) -> float:
    """Forward FLOPs of one token through every weight product (2 a
    multiply-add): q, k, v, gate and o of each layer at its own head count;
    the dense FFN's three; a sparse layer's router, shared expert and the
    ``num_experts_per_tok`` experts the token selects; the head."""
    z = ref.sizes(cfg)
    d, total = z["d"], 0.0
    for _, ffn, hq in _layers(cfg):
        total += 2.0 * d * (2 * hq + 2 * z["hkv"] + hq // z["hd"])
        if ffn == "dense":
            total += 6.0 * d * z["f"]
        else:
            total += (2.0 * d * z["experts"] + 6.0 * d * z["fs"]
                      + cfg["num_experts_per_tok"] * 2.0 * expert_params(cfg))
    return total + (2.0 * d * z["rows"] if head else 0.0)


def attn_flops_token(cfg: dict, context: float) -> float:
    """q.K^T and p.V of one query token whose context (itself included) is
    ``context`` keys: a window layer reads ``min(context, window)`` of
    them, a full layer all."""
    band = min(context, cfg["sliding_window"])
    return sum(4.0 * (context if kind == "full" else band) * hq
               for kind, _, hq in _layers(cfg))


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Every prompt token through the layers, causal attention (a window
    layer's band: position ``i`` reads ``min(i + 1, window)`` keys), and the
    head for the last position only."""
    z, w = ref.sizes(cfg), cfg["sliding_window"]
    p = prompt_len
    pairs = {"full": p * (p + 1) / 2.0,
             "window": (p * (p + 1) / 2.0 if p <= w
                        else w * (w + 1) / 2.0 + (p - w) * w)}
    attn = sum(4.0 * pairs[kind] * hq for kind, _, hq in _layers(cfg))
    return (p * matmul_flops_per_token(cfg, head=False) + attn
            + 2.0 * z["d"] * z["rows"])


def decode_flops(cfg: dict, context: float) -> float:
    """One decode token attending over ``context`` cached tokens."""
    return matmul_flops_per_token(cfg) + attn_flops_token(cfg, context)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> dict:
    """K and V of one token: over the full layers (paged, for the whole
    context) and over the window layers (a ring of ``sliding_window``)."""
    per = 2 * ref.sizes(cfg)["hkv"] * itemsize
    kinds = [k for k, _, _ in _layers(cfg)]
    return {"full": kinds.count("full") * per,
            "window": kinds.count("window") * per}


def step_weight_bytes(cfg: dict, n_slots: float, itemsize: int = 2) -> float:
    """Weight bytes one decode step of ``n_slots`` tokens must read: every
    leaf once but the embedding (the rows gathered) and the routed experts,
    of which :func:`expected_experts_hit` a sparse layer."""
    z = ref.sizes(cfg)
    sparse = [k for _, k, _ in _layers(cfg)].count("sparse")
    all_experts = sparse * z["experts"] * expert_params(cfg)
    hit = sparse * expected_experts_hit(cfg, n_slots) * expert_params(cfg)
    fixed = ref.parameter_count(cfg) - all_experts - z["rows"] * z["d"]
    return itemsize * (fixed + hit + n_slots * z["d"])


def decode_bytes(cfg: dict, context_tokens: int, n_slots: int) -> float:
    """The LEAST HBM bytes one decode step must move: the weights as
    :func:`step_weight_bytes` counts them; K and V of every token in
    context at the full layers and of ``min(context, window)`` at the
    window layers — from the step's MEAN context, which is all the driver
    hands over: a cap on the mean is at least the mean of the caps, so where
    some slots are still inside the window this counts a little too much (of
    the window layers' 0.2 GB, 3 % of a step) — plus the step's own K/V
    written; the float32 logits written."""
    z, kv = ref.sizes(cfg), kv_bytes_per_token(cfg)
    mean = context_tokens / max(n_slots, 1)
    read = (kv["full"] * context_tokens
            + kv["window"] * n_slots * min(mean, cfg["sliding_window"]))
    written = (kv["full"] + kv["window"]) * n_slots
    return float(step_weight_bytes(cfg, n_slots) + read + written
                 + 4 * n_slots * z["rows"])
