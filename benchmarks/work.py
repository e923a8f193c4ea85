"""Operations and bytes the algorithm needs, from the shapes alone.

Every share of a peak or of a roofline in this benchmark divides one of
these counts by a time from the chip.  They count required work only:
causal attention once (the masked half is not work), no recomputation
(flash attention's and the fused loss's rematerialisation is real time but
not required work), embedding lookups as zero.  ``cfg`` is a configuration
file's dict (``n_embd``, ``n_layer``, ``n_inner``, ``vocab_size``).
"""

from __future__ import annotations


def matmul_flops_per_token(cfg: dict, head: bool = True) -> float:
    """Forward FLOPs of one token through every weight matmul: q, k, v, o
    (4 D^2), the FFN (2 D F) per layer, and the output head (D V)."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 2.0 * (4 * d * d + 2 * d * f)
    return cfg["n_layer"] * per_layer + (2.0 * d * cfg["vocab_size"] if head else 0.0)


def attn_flops_token(cfg: dict, context: float) -> float:
    """Forward attention FLOPs of one query token over ``context`` keys:
    q.K^T and p.V, 2 FLOPs per multiply-add, every layer, all heads."""
    return cfg["n_layer"] * 4.0 * context * cfg["n_embd"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token: 3x the forward (each
    matmul has two gradient matmuls).  A causal sequence of T tokens
    attends over (T + 1) / 2 keys on average."""
    fwd = matmul_flops_per_token(cfg) + attn_flops_token(cfg, (seq_len + 1) / 2.0)
    return 3.0 * fwd


def flash_train_flops_per_row(cfg: dict, seq_len: int) -> float:
    """Attention-core FLOPs of one sequence, forward (q.K^T, p.V) and
    backward (dV, dP, dQ, dK): six causal T x T x D matmuls per layer.
    The kernels' roof is the MXU: their bytes (q, k, v, o and gradients,
    a few T x D tensors) are far below FLOPs / peak x bandwidth."""
    t = seq_len
    causal_pairs = t * (t + 1) / 2.0
    return cfg["n_layer"] * 6.0 * 2.0 * causal_pairs * cfg["n_embd"]


def flash_train_bytes_per_row(cfg: dict, seq_len: int, itemsize: int = 2) -> float:
    """HBM bytes the attention core must move for one sequence: read q, k,
    v, write o (forward); read q, k, v, o, dO, write dq, dk, dv (backward)."""
    return cfg["n_layer"] * 12.0 * seq_len * cfg["n_embd"] * itemsize


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Forward FLOPs to prefill one prompt: every token through the layers'
    matmuls, causal attention, and the head for the last position only (the
    one logit row the first output token needs)."""
    body = prompt_len * matmul_flops_per_token(cfg, head=False)
    attn = attn_flops_token(cfg, 1.0) * prompt_len * (prompt_len + 1) / 2.0
    return body + attn + 2.0 * cfg["n_embd"] * cfg["vocab_size"]


def decode_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one decode token attending over ``context`` cached
    tokens (itself included)."""
    return matmul_flops_per_token(cfg) + attn_flops_token(cfg, context)


def paged_decode_bytes(cfg: dict, context_tokens: int, n_slots: int,
                       itemsize: int = 2) -> float:
    """HBM bytes one decode step's attention must move: K and V of every
    token actually in context (summed over the active slots), each layer,
    plus q in and the context vector out per active slot."""
    d = cfg["n_embd"]
    kv = 2.0 * context_tokens * d * itemsize
    qo = 2.0 * n_slots * d * itemsize
    return cfg["n_layer"] * (kv + qo)


def paged_decode_flops(cfg: dict, context_tokens: int) -> float:
    return attn_flops_token(cfg, 1.0) * context_tokens


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which roof binds."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
