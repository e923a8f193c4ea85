"""Adapter of ``model_type`` ``sdar_moe`` for the serving harness
(:mod:`benchmarks.serve_arch`): how to build the program's ``HybridLM``
from a configuration file — each published layer two letters, ``*`` for its
attention and ``E`` for its experts, block diffusion at the configuration's
``block_length`` — and hand it the seed's weights, what the program kept of
how each served token was produced (the pass that committed it), how to run
the plain reference over that, and what work a pass needs: B rows a slot
through every weight, the keys of the context and of the whole block, the
experts the pass's assignments reach.
"""

from __future__ import annotations

import jax

from benchmarks.arch import sdar_moe_reference as ref

#: fewer compared positions, or pairs of a pass's committed and masked
#: positions, than these in a sample is no comparison
MIN_COMPARED = 8
MIN_PAIRS = 8
#: the program's parameter path below a layer -> the reference's leaf name
_PROGRAM = {
    "attn": ("attn", {("norm", "scale"): "norm1", ("mixer", "q", "w"): "wq",
                      ("mixer", "k", "w"): "wk", ("mixer", "v", "w"): "wv",
                      ("mixer", "q_norm", "scale"): "q_norm",
                      ("mixer", "k_norm", "scale"): "k_norm",
                      ("mixer", "o", "w"): "wo"}),
    "moe": ("moe", {("norm", "scale"): "norm2",
                    ("mixer", "router", "w"): "router_w",
                    ("mixer", "w1"): "w1", ("mixer", "w2"): "w2"}),
}
_TOP = {("embed", "w"): "embed", ("norm_f", "scale"): "norm_f",
        ("head", "w"): "head"}


def pattern(cfg: dict) -> str:
    """Two letters a published layer: its attention, then its experts."""
    return "*E" * cfg["num_hidden_layers"]


def model_config(cfg: dict) -> dict:
    """The program's ``HybridLM`` config for a configuration file."""
    run = cfg["run"]
    return dict(
        pattern=pattern(cfg), dim=cfg["hidden_size"], vocab=cfg["vocab_size"],
        seq_len=run["max_context"], norm_eps=cfg["rms_norm_eps"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        qk_norm=True, n_experts=cfg["num_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"], latent=None,
        expert_dim=cfg["moe_intermediate_size"], shared_dim=0,
        expert_act="silu_gated", router="softmax",
        block_len=cfg["block_length"], mask_id=cfg["mask_token_id"],
        precision=run["precision"],
        weights=run["weights"], verbose=False)


def check_sizes(cfg: dict) -> None:
    """What the file says twice must agree, and what the program does not
    have must be off."""
    if cfg["hybrid_override_pattern"] != pattern(cfg):
        raise ValueError("hybrid_override_pattern is not the layers' letters")
    if tuple(cfg["experts_held"]) != (0, cfg["num_experts"]):
        raise ValueError("experts_held: every expert is held here")
    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["use_sliding_window"] or cfg["rope_scaling"] is not None
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1
            or not cfg["norm_topk_prob"] or cfg["hidden_act"] != "silu"):
        raise ValueError("a bias, a tied head, a window, scaled rotary, a "
                         "dense layer or unnormalised top-k weights are not "
                         "in the program")
    if (cfg["n_embd"], cfg["n_head"], cfg["n_inner"], cfg["n_positions"]) != (
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["max_position_embeddings"]):
        raise ValueError("the harness's keys and the source's disagree")
    if not cfg["block_length"] <= cfg["mask_token_id"] < cfg["vocab_size"]:
        raise ValueError("the mask token is no row of the vocabulary")


def seeded_params(model, cfg: dict, seed: int):
    """The program's parameter tree for ``--seed``: every leaf the bf16
    array the reference's generator makes for it, made on the device leaf
    by leaf.  Program layers ``2 l`` (attention) and ``2 l + 1`` (experts)
    are the reference's layer ``l``."""
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
    if getattr(model, "block_len", None) != cfg["block_length"]:
        raise ValueError("this program's HybridLM has no block diffusion")
    if model.commits_per_pass != -(-cfg["block_length"]
                                   // cfg["denoising_passes"]):
        raise ValueError(f"the program's static schedule commits "
                         f"{model.commits_per_pass} positions a pass")
    params = seeded_params(model, cfg, seed)
    engine = InferenceEngine(model, params, block_size=run["block_size"],
                             num_blocks=run["num_blocks"],
                             max_batch=run["max_batch"],
                             seed=int(seed) & 0x7FFFFFFF)
    return model, engine, Scheduler(engine)


def vocab(cfg: dict) -> int:
    """The ids traffic may draw: the rows below the mask token, so that no
    prompt holds a mask."""
    return cfg["mask_token_id"]


def served_record(request) -> dict:
    """What the program kept of how ``request``'s tokens were produced:
    ``passes`` — the pass of its block that committed each generated
    position and then each position of the last block past
    ``max_new_tokens`` (-1: committed before the block's first pass) —,
    ``confidence`` — the log-probability the program gave each such token
    in that pass (None for -1) — and ``dropped``, those last positions'
    tokens."""
    first = len(request.prompt)
    end = first + len(request.generated)
    last = max(request.committed, default=end - 1) + 1
    kept = [request.committed[p] for p in range(first, last)]
    return {"passes": [k[1] for k in kept],
            "confidence": [k[2] for k in kept],
            "dropped": [request.committed[p][0] for p in range(end, last)]}


def served_gaps(cfg: dict, seed: int, sample: list, control: bool = False,
                drop: str = "") -> dict:
    """The reference over every recorded pass of each sampled request, in
    the state the program ran it in; -> under the harness's one name, the
    wider of two mean gaps in nats: the program's stated log-probability
    of a token it committed against the reference's, and a position left
    masked above one committed in the same pass, where the reference
    orders the two by more than ``check.order_margin``
    (:func:`~benchmarks.arch.sdar_moe_reference.served_gap`).  ``control``:
    the choices and confidences of the fp8 control in the same states."""
    got = ref.served_gap(cfg, seed, sample, control, drop)
    served = sum(len(g) for _, g, _ in sample)
    # the verdict on the program needs enough of both to stand on; a
    # reading of the control or of a reference without a term is what it is
    if not (control or drop) and (got["positions"] < MIN_COMPARED
                                  or got["pairs"] < MIN_PAIRS):
        raise RuntimeError(
            f"only {got['positions']} committed positions of {served} served "
            f"and {got['pairs']} pairs of a pass are decided: too few to "
            f"compare")
    return {"widest_logit_gap": max(got["logprob_gap"], got["order_gap"]),
            "tokens_compared": got["positions"],
            "pairs_compared": got["pairs"], "logprob_gap": got["logprob_gap"],
            "order_gap": got["order_gap"], "tokens_served": served}


# -- the work a pass needs, from the shapes alone ---------------------------------

def expected_experts_hit(cfg: dict, n_tokens: float) -> float:
    """Experts of one layer that receive at least one of ``n_tokens``
    tokens' assignments under an even router: ``E (1 - (1 - 1/E) ** (k
    n))`` (128.0 of 128 for 64 slots x 4 rows x 8)."""
    e = cfg["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (cfg["num_experts_per_tok"] * n_tokens))


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_flops_per_token(cfg: dict, head: bool = True) -> float:
    """Forward FLOPs of one row through every weight product (2 a
    multiply-add): q, k, v and o, the router and the
    ``num_experts_per_tok`` experts of each layer; the head."""
    z = ref.sizes(cfg)
    d = z["d"]
    layer = (2.0 * d * (2 * z["hq"] + 2 * z["hkv"]) + 2.0 * d * z["experts"]
             + cfg["num_experts_per_tok"] * 2.0 * expert_params(cfg))
    return z["layers"] * layer + (2.0 * d * z["rows"] if head else 0.0)


def attn_flops(cfg: dict, queries: float, keys: float) -> float:
    """q.K^T and p.V of ``queries`` queries over ``keys`` keys each, every
    layer."""
    z = ref.sizes(cfg)
    return 4.0 * queries * keys * z["hq"] * z["layers"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The prompt's whole blocks (``n`` of ``B`` positions) through the
    layers, block-causal attention (a position of block ``b`` reads ``(b +
    1) B`` keys); no head: a block prefill samples nothing."""
    b = cfg["block_length"]
    n = prompt_len // b
    return (n * b * matmul_flops_per_token(cfg, head=False)
            + attn_flops(cfg, b, b * b * n * (n + 1) / 2.0))


def decode_flops(cfg: dict, context: float) -> float:
    """One slot's pass, ``context`` = its cached tokens + 1 (the count
    ``serve_arch`` makes for one token a step): B rows through every product and the
    head, each over the cached tokens and the whole block."""
    b = cfg["block_length"]
    return (b * matmul_flops_per_token(cfg)
            + attn_flops(cfg, b, context - 1 + b))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over every layer."""
    z = ref.sizes(cfg)
    return 2 * z["hkv"] * itemsize * z["layers"]


def step_weight_bytes(cfg: dict, n_rows: float, itemsize: int = 2) -> float:
    """Weight bytes one pass of ``n_rows`` rows must read: every leaf once
    but the embedding (the rows gathered) and the experts, of which
    :func:`expected_experts_hit` a layer."""
    z = ref.sizes(cfg)
    experts = z["layers"] * z["experts"] * expert_params(cfg)
    hit = z["layers"] * expected_experts_hit(cfg, n_rows) * expert_params(cfg)
    fixed = ref.parameter_count(cfg) - experts - z["rows"] * z["d"]
    return itemsize * (fixed + hit + n_rows * z["d"])


def decode_bytes(cfg: dict, context_tokens: int, n_slots: int) -> float:
    """The LEAST HBM bytes one pass must move: the weights as
    :func:`step_weight_bytes` counts them for ``B`` rows a slot; K and V of
    every cached token and of the block (``context_tokens`` is ``serve_arch``'s
    cached tokens + 1 a slot), the block's K/V written; the float32 logits
    of every row written."""
    b = cfg["block_length"]
    rows = b * n_slots
    kv = kv_bytes_per_token(cfg)
    read = kv * (context_tokens + (b - 1) * n_slots)
    return float(step_weight_bytes(cfg, rows) + read + kv * rows
                 + 4 * rows * ref.sizes(cfg)["rows"])


def paged_decode_bytes(cfg: dict, context_tokens: float, n_slots: float,
                       itemsize: int = 2) -> float:
    """HBM bytes one pass's attention must move, whatever implements it:
    K and V of every key the pass reads (``context_tokens``: the
    ``kv_tokens`` tag, ``L + B`` a slot) in every layer, plus the block's
    ``B x H`` queries in and their context out per slot and layer."""
    z = ref.sizes(cfg)
    return (kv_bytes_per_token(cfg, itemsize) * context_tokens
            + z["layers"] * 2.0 * n_slots * cfg["block_length"] * z["hq"]
            * itemsize)


def paged_decode_flops(cfg: dict, context_tokens: float) -> float:
    """The same attention's FLOPs: each of a slot's ``B`` queries over all
    its ``L + B`` keys."""
    return attn_flops(cfg, cfg["block_length"], context_tokens)
