"""A tiny stand-in for the block-diffusion cell (the ``serve_arch`` harness, the
``sdar_moe`` adapter), as ``laguna_tiny`` is for the window cell: the real
files with every size shrunk (never used for a number)."""

import copy

from benchmarks import run as bench_run

WORKLOAD = "sdar30.serve.backlog"

#: limit for the tiny sizes, set as the cell's own is (the wider of the
#: two mean gaps), over the positions whose routing the reference decides
#: by more than 0.01 router logits and the pairs it orders by more than
#: 0.02 nats: above what sound tiny runs read on the CPU (9 seeds: 0.0012
#: to 0.0016) and below what the fp8 control reads there (3 samples: at
#: least 0.020), what a model without its in-block bidirectional
#: attention, its q/k norm or its top-k renormalisation reads (at least
#: 0.057, 0.15, 0.020) and what a selection of the least confident
#: positions reads (3 seeds: at least 0.051)
TINY_LIMIT = 0.01

TINY_SIZES = dict(
    hidden_size=256, intermediate_size=512, num_attention_heads=4,
    num_key_value_heads=2, head_dim=64, num_hidden_layers=2, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=64, vocab_size=512,
    mask_token_id=509, n_embd=256, n_head=4, n_inner=512,
    hybrid_override_pattern="*E*E", experts_held=[0, 8])


def tiny_config() -> dict:
    cfg = copy.deepcopy(bench_run.load_cell(WORKLOAD)["cfg"])
    cfg.update(TINY_SIZES)
    # about one position in eight has its top-2 of 8 decided by less than
    # 0.01 router logits, which bf16 rounding can swap
    cfg["check"]["routing_margin"] = 0.01
    cfg["run"].update(precision="bf16", weights="bf16", max_batch=4,
                      max_context=64, block_size=8, num_blocks=33)
    return cfg


def tiny_cell() -> dict:
    loaded = copy.deepcopy(bench_run.load_cell(WORKLOAD))
    loaded["cfg"] = tiny_config()
    loaded["cell"]["limits"] = {"widest_logit_gap": TINY_LIMIT}
    loaded["traffic"]["pairs"] = [[6 + (i * 7) % 21, 17 + (i * 5) % 13]
                                  for i in range(4096)]
    loaded["traffic"].update(lead_in_s=0.3, check_requests=3)
    return loaded


def rehearse(seed: int = 2**31 + 77, seconds: float = 2.0) -> dict:
    """The harness without its look for a chip: a whole run on the CPU."""
    import jax

    return bench_run.execute(tiny_cell(), WORKLOAD, seed=seed, seconds=seconds,
                             trace=0, devices=jax.devices()[:1])
