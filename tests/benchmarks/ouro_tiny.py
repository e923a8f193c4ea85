"""A tiny stand-in for the looped-stack cell (``serve_arch`` driver, the
``ouro`` adapter), as ``arch_tiny`` is for the hybrid one: the real files
with every size shrunk (never used for a number)."""

import copy

from benchmarks import run as bench_run

WORKLOAD = "ouro26.serve.backlog"

#: limit for the tiny sizes, set as the cell's own is: above what sound
#: tiny runs read on the CPU (5 seeds: 0, 0, 0, 0.0016, 0.0016) and below
#: what the fp8 control reads there (0.032, 0.032, 0.077, 0.079, 0.097)
TINY_LIMIT = 0.01

TINY_SIZES = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
    max_window_layers=2, layer_types=["full_attention"] * 2, total_ut_steps=3,
    vocab_size=211, n_embd=64, n_head=4, n_inner=96)


def tiny_cell() -> dict:
    loaded = copy.deepcopy(bench_run.load_cell(WORKLOAD))
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    loaded["cell"]["limits"] = {"widest_logit_gap": TINY_LIMIT}
    cfg.update(TINY_SIZES)
    cfg["run"].update(precision="bf16", weights="bf16", max_batch=4,
                      max_context=64, block_size=8, num_blocks=33)
    traffic["pairs"] = [[8 + (i * 7) % 20, 18 + (i * 5) % 12]
                        for i in range(4096)]
    traffic.update(lead_in_s=0.3, check_requests=3)
    return loaded


def rehearse(seed: int = 2**31 + 77, seconds: float = 2.0) -> dict:
    """The harness without its look for a chip: a whole run on the CPU."""
    import jax

    return bench_run.execute(tiny_cell(), WORKLOAD, seed=seed, seconds=seconds,
                             trace=0, devices=jax.devices()[:1])
