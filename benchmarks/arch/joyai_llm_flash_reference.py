"""Plain reference of the ``joyai_llm_flash`` layer stack, and its weights.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no kernel, no absorption, no batching of requests into one step,
nothing of the program.  With ``u = RMSNorm(h)`` at ``rms_norm_eps`` and no
bias anywhere, layer ``l``: ``h <- h + MLA(RMSNorm(h))``, then ``h <- h +
FFN_l(RMSNorm(h))``; a final RMSNorm and an untied head.

- **Latent attention** in its published, decompressed form, 32 heads:
  ``c_q = RMSNorm(u W_qa)`` (``q_lora_rank``), ``q = c_q W_qb`` a head
  ``q_nope`` (``qk_nope_head_dim``) then ``q_pe`` (``qk_rope_head_dim``);
  ``[c_kv | k_pe] = u W_kva``, ``c_kv = RMSNorm(c_kv)`` (``kv_lora_rank``),
  ``k_pe`` one rotary key shared by the heads; ``[k_nope | v] = c_kv W_kvb``
  a head (``qk_nope_head_dim`` + ``v_head_dim``).  Rotary over ``q_pe`` and
  ``k_pe`` at ``rope_theta`` as the ``transformers`` DeepSeek-V3 code
  applies it under ``rope_interleave``: each head's rope dims de-interleaved
  (``view(d / 2, 2).transpose``: evens, then odds), then rotate-half with
  ``cos = [cos f ; cos f]``, ``f_i = theta ** (-2 i / d)``.  Head ``h``
  scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(qk_head_dim)``, causal
  softmax, context ``sum p v``; the heads' contexts through ``W_o``.  The
  scores of a long sequence are computed a block of queries at a time
  (``QUERY_BLOCK``) so that they fit; every block sees all the keys.
- **FFN, dense** (the first ``first_k_dense_replace`` layers): ``W_d
  (silu(W_g u) * W_u u)`` at ``intermediate_size``.
- **FFN, experts**: ``s = sigmoid(W_r u)`` over ``router_experts`` (256) in
  float32; the ``num_experts_per_tok`` largest of ``s + b_corr`` selected
  (``noaux_tc``; ``n_group`` = ``topk_group`` = 1, so no group is left out);
  ``w_i = routed_scaling_factor x s_i / sum_selected s_j``; ``sum_i w_i
  E_i(u) + S(u)`` over the selected experts that this chip holds
  (``experts_held``: the same share as the program's), every ``E_i`` and the
  shared ``S`` a gated SiLU FFN of ``moe_intermediate_size``, gate and up
  one leaf ``[D, 2 F]`` (gate first).  The experts are a loop over the held
  experts that receive a row, each over its rows alone.

**Weights.**  Every leaf is a pure function of (seed, layer, leaf name) —
an expert's of its global index too, so that any share of the experts holds
the same expert alike — drawn in float32 and rounded once to bfloat16: those
bf16 values ARE the model's weights, for the program (which holds them in
bf16) and for this reference (which computes on them in float32).  They
are made layer by layer and never held as a tree.  ``b_corr`` is drawn
``N(0, B_CORR_STD)`` (the configuration's ``assumed.b_corr``).

``precision="fp8"`` is the CONTROL, not a reference: both operands of every
matrix product rounded to float8_e4m3 under a per-tensor scale, the
nearest precision below the bf16 the configuration states.  ``drop`` leaves
one term of the mathematics out (``kv_norm``: ``c_kv`` unnormed;
``interleave``: rotate-half over the rope dims as they lie; ``b_corr``:
selection by ``s`` alone; ``route_scale``: 1.0 for 2.5): what the cell's
limit must refuse.

**Which positions are compared.**  The top-8 of 256 nearly even selection
scores is ill-conditioned, as in the other sigmoid-routed cell: a served
position counts only where THIS reference, in float32, decides every expert
layer's selection by more than ``check.routing_margin`` (the 8th selection
score's lead over the 9th, :func:`route`); the control and a dropped term
are read over the same kind of positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: queries whose scores are held at a time: [H, QUERY_BLOCK, T] float32
QUERY_BLOCK = 256
#: the standard deviation ``b_corr`` is drawn at (``assumed.b_corr``)
B_CORR_STD = 0.05


# -- sizes ------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    lo, hi = cfg["experts_held"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                v=cfg["v_head_dim"], f=cfg["intermediate_size"],
                fe=cfg["moe_intermediate_size"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                router=cfg["router_experts"], held=hi - lo,
                rows=cfg["vocab_size"], layers=cfg["num_hidden_layers"])


def layer_kind(cfg: dict, layer: int) -> str:
    """``dense`` or ``sparse``: the FFN of layer ``layer``."""
    sparse = (layer >= cfg["first_k_dense_replace"]
              and layer % cfg["moe_layer_freq"] == 0)
    return "sparse" if sparse else "dense"


#: kind -> ((leaf, shape from sizes, kind of draw), ...)
LEAVES = {
    "attn": (
        ("norm1", lambda z: (z["d"],), "scale"),
        ("wq_a", lambda z: (z["d"], z["q_lora"]), "matrix"),
        ("q_norm", lambda z: (z["q_lora"],), "scale"),
        ("wq_b", lambda z: (z["q_lora"], z["h"] * (z["nope"] + z["rope"])),
         "matrix"),
        ("wkv_a", lambda z: (z["d"], z["kv_lora"] + z["rope"]), "matrix"),
        ("kv_norm", lambda z: (z["kv_lora"],), "scale"),
        ("wkv_b", lambda z: (z["kv_lora"], z["h"] * (z["nope"] + z["v"])),
         "matrix"),
        ("wo", lambda z: (z["h"] * z["v"], z["d"]), "out_matrix"),
    ),
    "dense": (
        ("norm2", lambda z: (z["d"],), "scale"),
        ("w_gate", lambda z: (z["d"], z["f"]), "matrix"),
        ("w_up", lambda z: (z["d"], z["f"]), "matrix"),
        ("w_down", lambda z: (z["f"], z["d"]), "out_matrix"),
    ),
    "sparse": (
        ("norm2", lambda z: (z["d"],), "scale"),
        ("router_w", lambda z: (z["d"], z["router"]), "matrix"),
        ("b_corr", lambda z: (z["router"],), "bias"),
        ("w1", lambda z: (z["held"], z["d"], 2 * z["fe"]), "expert_matrix"),
        ("w2", lambda z: (z["held"], z["fe"], z["d"]), "expert_out_matrix"),
        ("v1", lambda z: (z["d"], 2 * z["fs"]), "matrix"),
        ("v2", lambda z: (z["fs"], z["d"]), "out_matrix"),
    ),
    "top": (
        ("embed", lambda z: (z["rows"], z["d"]), "matrix"),
        ("norm_f", lambda z: (z["d"],), "scale"),
        ("head", lambda z: (z["d"], z["rows"]), "matrix"),
    ),
}
_ORDER = [(k, n) for k in ("top", "attn", "dense", "sparse")
          for n, _, _ in LEAVES[k]]


def kind_params(cfg: dict, kind: str) -> int:
    """Parameters of one layer's ``kind`` part (or of the top leaves)."""
    z = sizes(cfg)
    return sum(math.prod(shape(z)) for _, shape, _ in LEAVES[kind])


def parameter_count(cfg: dict) -> int:
    """Every parameter of the layers and the share of experts the
    configuration holds."""
    return kind_params(cfg, "top") + sum(
        kind_params(cfg, "attn") + kind_params(cfg, layer_kind(cfg, l))
        for l in range(cfg["num_hidden_layers"]))


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """The latent row ``[c_kv | k_pe]`` of every layer, one a token."""
    z = sizes(cfg)
    return z["layers"] * (z["kv_lora"] + z["rope"]) * itemsize


def seed_key(seed: int):
    """``--seed`` may exceed 32 signed bits: fold both halves in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def draw(key, shape, how: str, n_layers: int):
    """One leaf in float32, rounded once to bfloat16.  Matrices N(0, 0.02),
    output projections N(0, 0.02 / sqrt(2 L)), norm scales 1 + N(0, 0.02),
    the correction bias N(0, B_CORR_STD)."""
    how = how.replace("expert_", "")
    std = {"out_matrix": 0.02 / math.sqrt(2.0 * n_layers),
           "bias": B_CORR_STD}.get(how, 0.02)
    x = std * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if how == "scale" else x).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "how", "n_layers", "lo"))
def _leaf(key, index, layer, *, shape, how, n_layers, lo=0):
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    if how.startswith("expert_"):  # expert ``lo + e`` by its global index
        return jax.vmap(lambda e: draw(jax.random.fold_in(k, e), shape[1:],
                                       how, n_layers))(
            lo + jnp.arange(shape[0]))
    return draw(k, shape, how, n_layers)


def leaf(cfg: dict, key, kind: str, name: str, layer: int):
    """The bf16 leaf ``name`` of layer ``layer`` (0 for ``top``), made on
    the device by a program that depends on its shape and draw alone."""
    z = sizes(cfg)
    shape, how = next((s(z), h) for n, s, h in LEAVES[kind] if n == name)
    return _leaf(key, _ORDER.index((kind, name)), layer, shape=tuple(shape),
                 how=how, n_layers=z["layers"], lo=int(cfg["experts_held"][0]))


def layer_leaves(cfg: dict, key, kind: str, layer: int) -> dict:
    """One layer's ``kind`` leaves as the reference computes on them:
    float32 holding the bf16 values."""
    return {n: leaf(cfg, key, kind, n, layer).astype(jnp.float32)
            for n, _, _ in LEAVES[kind]}


# -- the layer equations ----------------------------------------------------------

def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(precision: str):
    """``fp32``, or ``fp8`` (the control)."""
    def ein(spec, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta: float, interleave: bool):
    """``x`` ``[T, H, d]`` at positions ``0..T-1``: rotate-half with the
    dims de-interleaved first where ``interleave`` (the ``transformers``
    DeepSeek-V3 rule; its output keeps that order)."""
    t, h, d = x.shape
    if interleave:
        x = x.reshape(t, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, d)
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def attention(cfg, ein, p, u, drop: str = ""):
    """``u`` ``[T, D]`` (normed) -> ``[T, D]``, decompressed MLA."""
    z, eps = sizes(cfg), cfg["rms_norm_eps"]
    t, h, nope, kvl = u.shape[0], z["h"], z["nope"], z["kv_lora"]
    theta, inter = float(cfg["rope_theta"]), (
        cfg["rope_interleave"] and drop != "interleave")
    c_q = rms(ein("td,dq->tq", u, p["wq_a"]), p["q_norm"], eps)
    q = ein("tq,qe->te", c_q, p["wq_b"]).reshape(t, h, nope + z["rope"])
    kv = ein("td,dc->tc", u, p["wkv_a"])
    c_kv = kv[:, :kvl]
    if drop != "kv_norm":
        c_kv = rms(c_kv, p["kv_norm"], eps)
    k_pe = rope(kv[:, None, kvl:], theta, inter)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta, inter)],
                        axis=-1)
    kvb = ein("tc,ce->te", c_kv, p["wkv_b"]).reshape(t, h, nope + z["v"])
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (t, h, z["rope"]))], axis=-1)
    v = kvb[..., nope:]
    j = jnp.arange(t)

    def block(start):
        i = start + jnp.arange(min(QUERY_BLOCK, t))
        qb = jax.lax.dynamic_slice_in_dim(q, start, i.shape[0], axis=0)
        s = ein("thd,shd->hts", qb, k) / math.sqrt(nope + z["rope"])
        s = jnp.where((j[None, :] <= i[:, None])[None], s, -jnp.inf)
        return ein("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    if t % min(QUERY_BLOCK, t):
        raise ValueError(f"{t} positions are not whole blocks of {QUERY_BLOCK}")
    n = -(-t // QUERY_BLOCK)
    ctx = jax.lax.map(block, jnp.arange(n) * QUERY_BLOCK).reshape(t, -1)
    return ein("te,ed->td", ctx, p["wo"])


def gated_ffn(ein, w_in, w_out, u):
    """``w_in`` ``[D, 2 F]``: gate and up side by side."""
    f = w_in.shape[-1] // 2
    hid = ein("td,df->tf", u, w_in)
    return ein("tf,fd->td", jax.nn.silu(hid[:, :f]) * hid[:, f:], w_out)


def route(cfg, p, u, drop: str = ""):
    """-> (selected experts ``[M, k]`` (global ids), their weights ``[M,
    k]``, by how much the ``k``-th selection score leads the first
    unselected ``[M]``).  Always float32 at ``highest``."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum("md,de->me", u, p["router_w"],
                                  precision=jax.lax.Precision.HIGHEST))
    pick = s if drop == "b_corr" else s + p["b_corr"]
    top, idx = jax.lax.top_k(pick, k + 1)
    w = jnp.take_along_axis(s, idx[:, :k], axis=-1)
    scale = 1.0 if drop == "route_scale" else cfg["routed_scaling_factor"]
    return (idx[:, :k], scale * w / jnp.sum(w, axis=-1, keepdims=True),
            top[:, k - 1] - top[:, k])


@functools.partial(jax.jit, static_argnames=("precision",))
def _experts(p, u, experts, rows, weights, *, precision):
    """``sum_i w_i E_i(u)`` over ``u`` ``[M, D]``: a loop over the held
    ``experts`` (local indices) that receive a row, each over its ``rows``
    (weights ``weights``, 0 on padding)."""
    ein = _ein(precision)

    def one(out, x):
        e, r, w = x
        w1 = jax.lax.dynamic_index_in_dim(p["w1"], e, keepdims=False)
        w2 = jax.lax.dynamic_index_in_dim(p["w2"], e, keepdims=False)
        return out.at[r].add(w[:, None] * gated_ffn(ein, w1, w2, u[r])), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (experts, rows, weights))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "drop"))
def _route(p, h, *, cfg_items, drop):
    cfg = dict(cfg_items)
    u = rms(h, p["norm2"], cfg["rms_norm_eps"])
    return (u, *route(cfg, p, u, drop))


@functools.partial(jax.jit, static_argnames=("precision",))
def _shared(p, h, u, routed, *, precision):
    return h + routed + gated_ffn(_ein(precision), p["v1"], p["v2"], u)


def sparse(cfg, p, h, live, precision: str, drop: str = ""):
    """``h <- h + MoE(RMSNorm(h))`` over tokens ``h`` ``[M, D]``, the rows
    ``live`` marks (padding takes no expert).  -> (h, each token's routing
    margin ``[M]``)."""
    u, idx, w, margin = _route(p, h, cfg_items=_items(cfg), drop=drop)
    idx, w, live = np.asarray(idx), np.asarray(w), np.asarray(live)
    lo, hi = cfg["experts_held"]
    hit = [(e, np.flatnonzero((idx == lo + e).any(-1) & live))
           for e in range(hi - lo)]
    hit = [(e, r) for e, r in hit if len(r)]
    routed = jnp.zeros_like(h)
    if hit:
        cap = 1 << (max(len(r) for _, r in hit) - 1).bit_length()
        experts = np.array([e for e, _ in hit], np.int32)
        rows = np.zeros((len(hit), cap), np.int32)
        weights = np.zeros((len(hit), cap), np.float32)
        for i, (e, r) in enumerate(hit):
            rows[i, :len(r)] = r
            weights[i, :len(r)] = np.where(idx[r] == lo + e, w[r], 0.0).sum(-1)
        routed = _experts(p, u, experts, rows, weights, precision=precision)
    return _shared(p, h, u, routed, precision=precision), margin


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("kind", "cfg_items", "precision", "drop"))
def _part(p, x, *, kind, cfg_items, precision, drop):
    """The attention half or a dense FFN half of a layer over every row of
    ``x`` ``[R, T, D]``, one row at a time."""
    cfg = dict(cfg_items)
    ein = _ein(precision)

    def row(h):
        if kind == "attn":
            return h + attention(cfg, ein, p, rms(h, p["norm1"],
                                                 cfg["rms_norm_eps"]), drop)
        u = rms(h, p["norm2"], cfg["rms_norm_eps"])
        gate = jax.nn.silu(ein("td,df->tf", u, p["w_gate"]))
        return h + ein("tf,fd->td", gate * ein("td,df->tf", u, p["w_up"]),
                       p["w_down"])

    return jax.lax.map(row, x)


def hidden(cfg: dict, seed: int, tokens, lengths, precision: str = "fp32",
           drop: str = ""):
    """``tokens`` ``[R, T]``, the first ``lengths[r]`` of row ``r`` real ->
    (the stack's output ``[R, T, D]``, the top leaves, each position's
    least routing margin over the expert layers ``[R, T]``); each layer's
    weights made once, all rows through it."""
    key, items = seed_key(seed), _items(cfg)
    top = layer_leaves(cfg, key, "top", 0)
    x = top["embed"][jnp.asarray(tokens, jnp.int32)]
    r, t, d = x.shape
    live = np.arange(t)[None, :] < np.asarray(lengths)[:, None]
    least = jnp.full((r, t), jnp.inf, jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        p = layer_leaves(cfg, key, "attn", l)
        x = _part(p, x, kind="attn", cfg_items=items, precision=precision,
                  drop=drop)
        kind = layer_kind(cfg, l)
        p = layer_leaves(cfg, key, kind, l)
        if kind == "dense":
            x = _part(p, x, kind="dense", cfg_items=items,
                      precision=precision, drop=drop)
        else:
            x, m = sparse(cfg, p, x.reshape(r * t, d), live.reshape(-1),
                          precision, drop)
            x = x.reshape(r, t, d)
            least = jnp.minimum(least, m.reshape(r, t))
        del p
    return x, top, least


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(top, x, *, eps, precision):
    return _ein(precision)("td,dv->tv", rms(x, top["norm_f"], eps), top["head"])


def logits(cfg: dict, seed: int, tokens, precision: str = "fp32",
           drop: str = ""):
    """Logits ``[R, T, V]`` over whole sequences ``tokens`` ``[R, T]``."""
    tokens = np.asarray(tokens)
    x, top, _ = hidden(cfg, seed, tokens, [tokens.shape[1]] * len(tokens),
                       precision, drop)
    return jnp.stack([_logits(top, row, eps=cfg["rms_norm_eps"],
                              precision=precision) for row in x])


@jax.jit
def _gap(lg, served, mask):
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(mask, gap, 0.0))


def served_gap(cfg: dict, seed: int, tokens, lengths, at, served, mask,
               control: bool = False, drop: str = "") -> tuple[float, int]:
    """-> (the widest gap, over the served positions whose routing is
    decided, by which the logit of the token served after a position lies
    below the reference's best there; how many positions that was).
    ``tokens`` ``[R, T]`` (the first ``lengths[r]`` real); ``at`` ``[R,
    S]`` the served positions of each row (the head reads those alone),
    ``served`` ``[R, S]`` the tokens served after them, ``mask`` ``[R, S]``
    which of the ``S`` are real.  A position counts where this reference's
    own least routing margin exceeds ``check.routing_margin``.
    ``control``: instead of the served token, the one the fp8 control puts
    first at that position.  ``drop``: the reference WITHOUT that term."""
    eps = cfg["rms_norm_eps"]
    x, top, least = hidden(cfg, seed, tokens, lengths, drop=drop)
    x8 = hidden(cfg, seed, tokens, lengths, "fp8")[0] if control else None
    widest, n = 0.0, 0
    for r in range(x.shape[0]):
        pos = jnp.asarray(at[r], jnp.int32)
        decided = jnp.asarray(mask[r], bool) & (
            least[r][pos] > cfg["check"]["routing_margin"])
        lg = _logits(top, x[r][pos], eps=eps, precision="fp32")
        tok = jnp.asarray(served[r], jnp.int32)
        if control:
            tok = jnp.argmax(_logits(top, x8[r][pos], eps=eps, precision="fp8"),
                             axis=-1).astype(jnp.int32)
        widest = max(widest, float(_gap(lg, tok, decided)))
        n += int(jnp.sum(decided))
    return widest, n


_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "rope_interleave", "rms_norm_eps", "num_experts_per_tok",
         "routed_scaling_factor", "router_experts", "n_shared_experts",
         "moe_intermediate_size", "intermediate_size", "first_k_dense_replace",
         "moe_layer_freq", "vocab_size", "num_hidden_layers")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS) + (
        ("experts_held", tuple(cfg["experts_held"])),)
