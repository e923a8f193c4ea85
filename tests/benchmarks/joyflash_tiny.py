"""A tiny stand-in for the latent-attention cell (the ``serve_arch``
harness, the ``joyai_llm_flash`` adapter), as ``laguna_tiny`` is for the
window cell: the real files with every size shrunk (never used for a
number)."""

import copy

from benchmarks import run as bench_run
from benchmarks.arch import joyai_llm_flash_reference as ref

WORKLOAD = "joyflash.serve.longdoc"

#: limit for the tiny sizes, set as the cell's own is: above what sound
#: tiny runs read on the CPU and below what the fp8 control and the
#: reference without each of its four dropped terms read there
#: (tests/benchmarks/test_bench_joyflash.py holds the readings)
TINY_LIMIT = 0.004

TINY_SIZES = dict(
    hidden_size=256, intermediate_size=512, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=96, kv_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, qk_head_dim=48, v_head_dim=32,
    num_hidden_layers=3, router_experts=16, n_routed_experts=8,
    experts_held=[0, 8], num_experts_per_tok=4, moe_intermediate_size=64,
    vocab_size=512, n_embd=256, n_head=4, n_inner=512, n_positions=131072,
    hybrid_override_pattern="L-LELE", rope_theta=10000)


def tiny_config() -> dict:
    cfg = copy.deepcopy(bench_run.load_cell(WORKLOAD)["cfg"])
    cfg.update(TINY_SIZES)
    cfg.update(parameters=ref.parameter_count(cfg),
               cache_bytes_per_token=ref.cache_bytes_per_token(cfg))
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
