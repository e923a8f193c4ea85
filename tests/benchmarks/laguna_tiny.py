"""A tiny stand-in for the window-and-full-attention cell (``serve_arch``
driver, the ``laguna`` adapter), as ``ouro_tiny`` is for the looped one:
the real files with every size shrunk (never used for a number)."""

import copy

from benchmarks import run as bench_run

WORKLOAD = "lagunaxs2.serve.backlog"

#: limit for the tiny sizes, set as the cell's own is: above what sound
#: tiny runs read on the CPU (5 seeds: 0, 0.0002, 0.0004, 0.0007, 0.0014)
#: and below what the fp8 control reads there (0.0092, 0.0106, 0.0242) and
#: what a model without its gate, window, shared expert or routed scale
#: reads (at least 0.008 over the same three seeds)
TINY_LIMIT = 0.004

TINY_SIZES = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"],
    num_attention_heads_per_layer=[4, 8, 4], num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, sliding_window=16, vocab_size=211,
    n_embd=64, n_head=4, n_inner=96, hybrid_override_pattern="*-wE*E",
    experts_held=[0, 8])


def tiny_config() -> dict:
    cfg = copy.deepcopy(bench_run.load_cell(WORKLOAD)["cfg"])
    cfg.update(TINY_SIZES)
    # the window's 16 tokens against contexts of up to 64: a YaRN rule
    # whose ramp falls inside the 8 rotated dims' 4 frequencies
    cfg["rope_parameters"]["full_attention"].update(
        factor=8, original_max_position_embeddings=32, beta_fast=4,
        attention_factor=1.2079441541679836)
    # router logits of std 0.16 over 8 experts: every position counts
    cfg["check"]["routing_margin"] = 0.0
    cfg["run"].update(precision="bf16", weights="bf16", max_batch=4,
                      max_context=64, block_size=8, num_blocks=33)
    return cfg


def tiny_cell() -> dict:
    loaded = copy.deepcopy(bench_run.load_cell(WORKLOAD))
    loaded["cfg"] = tiny_config()
    loaded["cell"]["limits"] = {"widest_logit_gap": TINY_LIMIT}
    loaded["traffic"]["pairs"] = [[8 + (i * 7) % 20, 18 + (i * 5) % 12]
                                  for i in range(4096)]
    loaded["traffic"].update(lead_in_s=0.3, check_requests=3)
    return loaded


def rehearse(seed: int = 2**31 + 77, seconds: float = 2.0) -> dict:
    """The harness without its look for a chip: a whole run on the CPU."""
    import jax

    return bench_run.execute(tiny_cell(), WORKLOAD, seed=seed, seconds=seconds,
                             trace=0, devices=jax.devices()[:1])
