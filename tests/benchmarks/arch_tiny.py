"""A tiny stand-in for the architecture cells (``serve_arch`` driver), as
``bench_tiny`` is for the others: the real files with every size shrunk
(never used for a number)."""

import copy

from benchmarks import run as bench_run

WORKLOAD = "nemotron3s.serve.backlog"

#: limit for the tiny sizes, set as the cell's own is: above what sound
#: tiny runs read on the CPU (at most 0.0014) and below what the fp8
#: control reads there (at least 0.034); readings in test_bench_arch.py
TINY_LIMIT = 0.02

TINY_SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=16, expand=2, ssm_state_size=16,
    n_groups=2, chunk_size=8, moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=64, router_experts=32,
    n_routed_experts=8, experts_held=[8, 16], num_experts_per_tok=8,
    vocab_rows_held=211, hybrid_override_pattern="MEM*E", num_hidden_layers=5)


def tiny_cell() -> dict:
    loaded = copy.deepcopy(bench_run.load_cell(WORKLOAD))
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    loaded["cell"]["limits"] = {"widest_logit_gap": TINY_LIMIT}
    cfg.update(TINY_SIZES)
    cfg["run"].update(precision="bf16", weights="bf16", max_batch=8,
                      max_context=64, block_size=8, num_blocks=65)
    traffic["pairs"] = [[8 + (i * 7) % 20, 18 + (i * 5) % 12]
                        for i in range(4096)]
    traffic.update(lead_in_s=0.3, check_requests=3)
    return loaded


def rehearse(seed: int = 2**31 + 77, seconds: float = 2.0) -> dict:
    """The harness without its look for a chip: a whole run on the CPU."""
    import jax

    return bench_run.execute(tiny_cell(), WORKLOAD, seed=seed, seconds=seconds,
                             trace=0, devices=jax.devices()[:1])
