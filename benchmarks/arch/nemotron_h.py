"""Adapter of ``model_type`` ``nemotron_h`` for the serving driver
(:mod:`benchmarks.serve_arch`): how to build the program's model from a
configuration file and hand it the seed's weights, how to run the plain
reference over what was served, and what work a step needs.

The next architecture adds a module like this one beside it, named by its
``model_type``, with the same five functions.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.arch import nemotron_h_reference as ref

#: the program's parameter path below a layer -> the reference's leaf name
_PROGRAM = {
    "mamba": {("norm", "scale"): "norm",
              ("mixer", "in_proj", "w"): "in_proj",
              ("mixer", "conv", "w"): "conv_w", ("mixer", "conv", "b"): "conv_b",
              ("mixer", "dt_bias"): "dt_bias", ("mixer", "A_log"): "A_log",
              ("mixer", "D"): "D", ("mixer", "norm", "scale"): "gnorm",
              ("mixer", "out_proj", "w"): "out_proj"},
    "attn": {("norm", "scale"): "norm",
             ("mixer", "q", "w"): "wq", ("mixer", "k", "w"): "wk",
             ("mixer", "v", "w"): "wv", ("mixer", "o", "w"): "wo"},
    "moe": {("norm", "scale"): "norm",
            ("mixer", "router", "w"): "router_w",
            ("mixer", "router", "b_corr"): "b_corr",
            ("mixer", "down", "w"): "down", ("mixer", "w1"): "w1",
            ("mixer", "w2"): "w2", ("mixer", "up", "w"): "up",
            ("mixer", "shared", "v1"): "v1", ("mixer", "shared", "v2"): "v2"},
}
_TOP = {("embed", "w"): "embed", ("norm_f", "scale"): "norm_f",
        ("head", "w"): "head"}


def model_config(cfg: dict) -> dict:
    """The program's ``HybridLM`` config for a configuration file."""
    run = cfg["run"]
    return dict(
        pattern=cfg["hybrid_override_pattern"], dim=cfg["hidden_size"],
        vocab=cfg["vocab_rows_held"], seq_len=run["max_context"],
        norm_eps=cfg["norm_eps"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        n_experts=cfg["router_experts"], experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"], latent=cfg["moe_latent_size"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        route_scale=float(cfg["routed_scaling_factor"]),
        precision=run["precision"], weights=run["weights"], verbose=False)


def check_sizes(cfg: dict) -> None:
    """What the file says twice must agree."""
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"] or hi > cfg["router_experts"]:
        raise ValueError(f"experts_held {lo}..{hi} against n_routed_experts "
                         f"{cfg['n_routed_experts']} of {cfg['router_experts']}")
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers disagree")
    if cfg["mamba_num_heads"] * cfg["mamba_head_dim"] != cfg["expand"] * cfg["hidden_size"]:
        raise ValueError("mamba heads x head size is not expand x hidden_size")


def seeded_params(model, cfg: dict, seed: int):
    """The program's parameter tree for ``--seed``: every leaf the bf16
    array the reference's generator makes for it, made on the device leaf
    by leaf (the float32 draw of one leaf is the largest temporary)."""
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
            kind = kinds[path[0]]
            x = ref.leaf(cfg, key, kind, _PROGRAM[kind][path[1:]],
                         int(path[0].split("_")[0]))
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
    """The ids traffic may draw: the vocabulary rows held."""
    return cfg["vocab_rows_held"]


def served_gaps(cfg: dict, seed: int, sample: list, control: bool = False) -> dict:
    """The reference over each sampled request's prompt and served tokens,
    layer-outer (a layer's weights are made once for all of them); -> the
    widest gap by which a served token's logit lies below the reference's
    best.  ``control``: instead of the served tokens, at each position the
    token the fp8 control puts first."""
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

def _counts(cfg: dict) -> dict:
    pattern = cfg["hybrid_override_pattern"]
    return {k: pattern.count(c) for c, k in ref.KINDS.items()}


def expected_local_hits(cfg: dict) -> float:
    """Selected experts of a token that are held here, under a uniform
    router: ``top_k x held / router width`` (5.5 in the cell)."""
    lo, hi = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * (hi - lo) / cfg["router_experts"]


def matmul_flops_per_token(cfg: dict, head: bool = True) -> float:
    """Forward FLOPs of one token through every weight product (2 a
    multiply-add) and the recurrence: an ``M`` layer's two projections, its
    convolution and 5 operations per state element (decay, outer product,
    accumulate, read-out); a ``*`` layer's four projections; an ``E``
    layer's router, latent projections, shared expert and the EXPECTED
    number of held experts a token selects; the head over the rows held."""
    z, n = ref.sizes(cfg), _counts(cfg)
    d = z["d"]
    h = cfg["mamba_num_heads"]
    mamba = (2.0 * d * (z["d_in"] + z["conv"] + h) + 2.0 * z["d_in"] * d
             + 2.0 * cfg["conv_kernel"] * z["conv"]
             + 5.0 * z["d_in"] * cfg["ssm_state_size"])
    attn = 2.0 * d * (z["hq"] + 2 * z["hkv"]) + 2.0 * z["hq"] * d
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    moe = (2.0 * d * cfg["router_experts"] + 2.0 * d * lat + 2.0 * lat * d
           + 4.0 * d * cfg["moe_shared_expert_intermediate_size"]
           + expected_local_hits(cfg) * 4.0 * lat * f)
    body = n["mamba"] * mamba + n["attn"] * attn + n["moe"] * moe
    return body + (2.0 * d * z["rows"] if head else 0.0)


def attn_flops_token(cfg: dict, context: float) -> float:
    """q.K^T and p.V of one query token over ``context`` keys, all query
    heads, every ``*`` layer."""
    return _counts(cfg)["attn"] * 4.0 * context * ref.sizes(cfg)["hq"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Every prompt token through the layers, causal attention, and the head
    for the last position only."""
    body = prompt_len * matmul_flops_per_token(cfg, head=False)
    attn = attn_flops_token(cfg, 1.0) * prompt_len * (prompt_len + 1) / 2.0
    z = ref.sizes(cfg)
    return body + attn + 2.0 * z["d"] * z["rows"]


def decode_flops(cfg: dict, context: float) -> float:
    """One decode token attending over ``context`` cached tokens."""
    return matmul_flops_per_token(cfg) + attn_flops_token(cfg, context)


def weight_bytes(cfg: dict, itemsize: int = 2) -> dict:
    """Bytes of the held weights by layer kind (one layer each) and of the
    top leaves, at ``itemsize`` a parameter."""
    z = ref.sizes(cfg)
    return {kind: itemsize * sum(int(np.prod(shape(z, cfg)))
                                 for _, shape, _ in leaves)
            for kind, leaves in ref.LEAVES.items()}


def state_bytes_per_slot(cfg: dict) -> int:
    """One slot's recurrent state over every ``M`` layer: ``S`` in float32
    and the convolution's last ``k - 1`` inputs in bf16."""
    z = ref.sizes(cfg)
    return _counts(cfg)["mamba"] * (
        4 * z["d_in"] * cfg["ssm_state_size"]
        + 2 * (cfg["conv_kernel"] - 1) * z["conv"])


def decode_bytes(cfg: dict, context_tokens: int, n_slots: int) -> float:
    """The LEAST HBM bytes one decode step must move: every held weight
    once (of the embedding only the rows gathered), each active slot's
    recurrent state read and written, K and V of every token in context at
    the ``*`` layers plus the step's own K/V written, and the float32
    logits written."""
    z, n, w = ref.sizes(cfg), _counts(cfg), weight_bytes(cfg)
    weights = (sum(n[k] * w[k] for k in n) + w["top"]
               - 2 * z["rows"] * z["d"] + 2 * n_slots * z["d"])
    state = 2 * n_slots * state_bytes_per_slot(cfg)
    kv = n["attn"] * 2 * 2 * z["hkv"] * (context_tokens + n_slots)
    logits = 4 * n_slots * z["rows"]
    return float(weights + state + kv + logits)
