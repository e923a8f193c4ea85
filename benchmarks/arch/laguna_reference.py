"""Plain reference of the ``laguna`` layer stack, and its weights.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no kernel, no batching of requests into one step, nothing of the
program.  With ``u = RMSNorm(h)`` at ``rms_norm_eps`` and no bias anywhere,
layer ``l``: ``h <- h + Attn_l(RMSNorm(h))``, then ``h <- h +
FFN_l(RMSNorm(h))``; a final RMSNorm and an untied head.

- **Attention.**  ``H_l = num_attention_heads_per_layer[l]`` query heads
  over ``num_key_value_heads`` K/V heads of ``head_dim``: ``q = u W_q``,
  ``k = u W_k``, ``v = u W_v``; query head ``h`` reads K/V head ``h // (H_l
  / Hkv)``; scores ``q.k / sqrt(head_dim)``.  The rotary rule is the one
  ``rope_parameters`` gives the layer's type: over the leading
  ``partial_rotary_factor x head_dim`` dims, rotate-half pairing inside
  them, the rest unrotated; ``default``: angles ``position x rope_theta **
  (-2 i / rot)``; ``yarn``: :func:`yarn_inv_freq`, and ``cos`` / ``sin``
  multiplied by ``attention_factor``.  ``full_attention``: causal mask;
  ``sliding_attention``: position ``i`` attends ``j`` with ``0 <= i - j <
  sliding_window``.  The gate: ``g = sigmoid(u W_g)``, ``W_g`` ``[D,
  H_l]``; head ``h``'s context is multiplied by ``g_h`` before ``W_o``.
  The scores of a long sequence are computed a block of queries at a time
  (``QUERY_BLOCK``) so that they fit; every block sees all the keys.
- **FFN, dense** (``mlp_layer_types[l] == "dense"``): ``W_d (silu(W_g u) *
  W_u u)`` at ``intermediate_size``.
- **FFN, sparse**: ``s = sigmoid(W_r u)`` over ``num_experts`` in float32;
  the ``num_experts_per_tok`` largest; ``w_i = moe_routed_scaling_factor x
  s_i / sum_selected s_j``; ``sum_i w_i E_i(u) + S(u)``, every ``E_i`` and
  the shared ``S`` a gated SiLU FFN (a dense loop over the experts, the
  unselected ones weighted 0).  An expert's gate and up matrices are the
  two halves of ONE leaf ``[D, 2 F]`` (gate first), as are the shared
  expert's.

**Weights.**  Every leaf is a pure function of (seed, layer, leaf name),
drawn in float32 and rounded once to bfloat16: those bf16 values ARE the
model's weights, for the program (which holds them in bf16) and for this
reference (which computes on them in float32).  They are made layer by
layer and never held as a tree: a sparse layer is 3.4 GB in float32.

``precision="fp8"`` is the CONTROL, not a reference: both operands of every
matrix product rounded to float8_e4m3 under a per-tensor scale, the
nearest precision below the bf16 the configuration states.  ``drop`` leaves
one term of the mathematics out (``gate``, ``yarn_factor``, ``window``,
``shared``, ``route_scale``): what the cell's limit must refuse.

**Which positions are compared.**  The top-k of 256 nearly even scores is
ill-conditioned: rounding the router's input to bf16 (0.6 to 5 % of its
norm, measured) carries the 8th and 9th scores past each other on 6 to 19 %
of the tokens a layer, and one swapped expert moves that token's logits by
0.1 to 0.25 rms on a std of 0.9 — what the fp8 control moves EVERY token's
by (PERF.md section 6).  A faithful bf16 program may take either expert
there, so :func:`served_gap` counts a served position only where THIS
reference, in float32, decides every sparse layer's selection by more than
``check.routing_margin`` router logits (:func:`routing_margin`); the
control and a dropped term are read over the same kind of positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: queries whose scores are held at a time: [H, QUERY_BLOCK, T] float32
QUERY_BLOCK = 256


# -- sizes ------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], hd=cfg["head_dim"],
                hkv=cfg["num_key_value_heads"] * cfg["head_dim"],
                f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
                fs=cfg["shared_expert_intermediate_size"],
                experts=cfg["num_experts"], rows=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"])


def layer_kinds(cfg: dict, layer: int) -> tuple[str, str]:
    """-> (``full`` | ``window``, ``dense`` | ``sparse``) of layer ``layer``."""
    attn = {"full_attention": "full", "sliding_attention": "window"}
    return (attn[cfg["layer_types"][layer]], cfg["mlp_layer_types"][layer])


def _hq(cfg, layer):
    return cfg["num_attention_heads_per_layer"][layer] * cfg["head_dim"]


#: kind -> ((leaf, shape from sizes, config and layer, kind of draw), ...)
LEAVES = {
    "attn": (
        ("norm1", lambda z, c, l: (z["d"],), "scale"),
        ("wq", lambda z, c, l: (z["d"], _hq(c, l)), "matrix"),
        ("wk", lambda z, c, l: (z["d"], z["hkv"]), "matrix"),
        ("wv", lambda z, c, l: (z["d"], z["hkv"]), "matrix"),
        ("wg", lambda z, c, l: (z["d"], c["num_attention_heads_per_layer"][l]),
         "matrix"),
        ("wo", lambda z, c, l: (_hq(c, l), z["d"]), "out_matrix"),
    ),
    "dense": (
        ("norm2", lambda z, c, l: (z["d"],), "scale"),
        ("w_gate", lambda z, c, l: (z["d"], z["f"]), "matrix"),
        ("w_up", lambda z, c, l: (z["d"], z["f"]), "matrix"),
        ("w_down", lambda z, c, l: (z["f"], z["d"]), "out_matrix"),
    ),
    "sparse": (
        ("norm2", lambda z, c, l: (z["d"],), "scale"),
        ("router_w", lambda z, c, l: (z["d"], z["experts"]), "matrix"),
        ("w1", lambda z, c, l: (z["experts"], z["d"], 2 * z["fe"]), "matrix"),
        ("w2", lambda z, c, l: (z["experts"], z["fe"], z["d"]), "out_matrix"),
        ("v1", lambda z, c, l: (z["d"], 2 * z["fs"]), "matrix"),
        ("v2", lambda z, c, l: (z["fs"], z["d"]), "out_matrix"),
    ),
    "top": (
        ("embed", lambda z, c, l: (z["rows"], z["d"]), "matrix"),
        ("norm_f", lambda z, c, l: (z["d"],), "scale"),
        ("head", lambda z, c, l: (z["d"], z["rows"]), "matrix"),
    ),
}
_ORDER = [(k, n) for k in ("top", "attn", "dense", "sparse")
          for n, _, _ in LEAVES[k]]


def kind_params(cfg: dict, kind: str, layer: int = 0) -> int:
    """Parameters of one layer's ``kind`` part (or of the top leaves)."""
    z = sizes(cfg)
    return sum(math.prod(shape(z, cfg, layer)) for _, shape, _ in LEAVES[kind])


def parameter_count(cfg: dict) -> int:
    """Every parameter of the layers the configuration holds."""
    return kind_params(cfg, "top") + sum(
        kind_params(cfg, "attn", l) + kind_params(cfg, layer_kinds(cfg, l)[1], l)
        for l in range(cfg["num_hidden_layers"]))


def seed_key(seed: int):
    """``--seed`` may exceed 32 signed bits: fold both halves in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def draw(key, shape, how: str, n_layers: int):
    """One leaf in float32, rounded once to bfloat16.  Matrices N(0, 0.02),
    output projections N(0, 0.02 / sqrt(2 L)), norm scales 1 + N(0, 0.02)."""
    std = 0.02 / math.sqrt(2.0 * n_layers) if how == "out_matrix" else 0.02
    x = std * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if how == "scale" else x).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "how", "n_layers"))
def _leaf(key, index, layer, *, shape, how, n_layers):
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    return draw(k, shape, how, n_layers)


def leaf(cfg: dict, key, kind: str, name: str, layer: int):
    """The bf16 leaf ``name`` of layer ``layer`` (0 for ``top``), made on
    the device by a program that depends on its shape and draw alone."""
    z = sizes(cfg)
    shape, how = next((s(z, cfg, layer), h) for n, s, h in LEAVES[kind]
                      if n == name)
    return _leaf(key, _ORDER.index((kind, name)), layer, shape=tuple(shape),
                 how=how, n_layers=z["layers"])


def layer_leaves(cfg: dict, key, kind: str, layer: int) -> dict:
    """One layer's ``kind`` leaves as the reference computes on them:
    float32 holding the bf16 values."""
    return {n: leaf(cfg, key, kind, n, layer).astype(jnp.float32)
            for n, _, _ in LEAVES[kind]}


# -- the layer equations ----------------------------------------------------------

def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _b16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _ein(precision: str):
    """``fp32``; ``fp8`` (the control); ``bf16`` — a reading, not a control:
    the operands of every product, and in :func:`_part` the residual
    stream after every add, rounded to the precision the configuration
    states, the rest float32: what rounding alone costs (PERF.md section 6)."""
    def ein(spec, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        elif precision == "bf16":
            a, b = _b16(a), _b16(b)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def yarn_inv_freq(rule: dict, rot: int) -> np.ndarray:
    """The ``rot / 2`` inverse frequencies of a ``yarn`` rule, in float64:
    ``f_i = theta ** (-2 i / rot)`` blended with ``f_i / factor`` by the
    linear ramp between the dim that makes ``beta_fast`` turns in
    ``original_max_position_embeddings`` positions (floored) and the dim
    that makes ``beta_slow`` (ceiled), both held to ``0..rot - 1``."""
    theta, factor = float(rule["rope_theta"]), float(rule["factor"])
    orig = rule["original_max_position_embeddings"]

    def dim_of(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rule["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rule["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    i = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rot)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)  # 1: interpolated
    return f / factor * ramp + f * (1.0 - ramp)


def rope(x, rule: dict, drop: str = ""):
    """``x`` ``[T, H, Dh]`` at positions ``0..T-1`` under one entry of
    ``rope_parameters``."""
    t, _, hd = x.shape
    rot = int(hd * rule["partial_rotary_factor"])
    half = rot // 2
    if rule["rope_type"] == "yarn":
        freq = yarn_inv_freq(rule, rot)
        scale = 1.0 if drop == "yarn_factor" else rule["attention_factor"]
    else:
        freq = float(rule["rope_theta"]) ** (
            -2.0 * np.arange(half, dtype=np.float64) / rot)
        scale = 1.0
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]],
                           axis=-1)


def attention(cfg, ein, p, u, layer: int, drop: str = ""):
    """``u`` ``[T, D]`` (normed) -> ``[T, D]``."""
    t = u.shape[0]
    h = cfg["num_attention_heads_per_layer"][layer]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][layer]
    rule = dict(cfg["rope_parameters"])[kind]
    q = rope(ein("td,de->te", u, p["wq"]).reshape(t, h, hd), rule, drop)
    k = rope(ein("td,de->te", u, p["wk"]).reshape(t, kv, hd), rule, drop)
    v = ein("td,de->te", u, p["wv"]).reshape(t, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    window = (cfg["sliding_window"]
              if kind == "sliding_attention" and drop != "window" else None)
    j = jnp.arange(t)

    def block(start):
        i = start + jnp.arange(min(QUERY_BLOCK, t))
        qb = jax.lax.dynamic_slice_in_dim(q, start, i.shape[0], axis=0)
        s = ein("thd,shd->hts", qb, k) / math.sqrt(hd)
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - j[None, :] < window
        s = jnp.where(seen[None], s, -jnp.inf)
        return ein("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    n = -(-t // QUERY_BLOCK)
    if t % min(QUERY_BLOCK, t):
        raise ValueError(f"{t} positions are not whole blocks of {QUERY_BLOCK}")
    ctx = jax.lax.map(block, jnp.arange(n) * QUERY_BLOCK).reshape(t, h, hd)
    if drop != "gate":
        ctx = ctx * jax.nn.sigmoid(ein("td,dh->th", u, p["wg"]))[..., None]
    return ein("te,ed->td", ctx.reshape(t, h * hd), p["wo"])


def gated_ffn(ein, w_in, w_out, u):
    """``w_in`` ``[D, 2 F]``: gate and up side by side."""
    f = w_in.shape[-1] // 2
    hid = ein("td,df->tf", u, w_in)
    return ein("tf,fd->td", jax.nn.silu(hid[:, :f]) * hid[:, f:], w_out)


def dense(cfg, ein, p, u, drop: str = ""):
    gate = jax.nn.silu(ein("td,df->tf", u, p["w_gate"]))
    return ein("tf,fd->td", gate * ein("td,df->tf", u, p["w_up"]), p["w_down"])


def router_logits(p, u):
    """``W_r u`` ``[T, num_experts]``.  Always float32 at ``highest``."""
    return jnp.einsum("td,de->te", u, p["router_w"],
                      precision=jax.lax.Precision.HIGHEST)


def routing_margin(cfg, p, u):
    """By how much the router decides each token's selection: the last
    selected expert's logit less the first unselected one's, ``[T]``."""
    k = cfg["num_experts_per_tok"]
    best, _ = jax.lax.top_k(router_logits(p, u), k + 1)
    return best[:, k - 1] - best[:, k]


def route(cfg, p, u, drop: str = ""):
    """-> weights ``[T, num_experts]``: ``w_i`` at the selected experts, 0
    elsewhere."""
    s = jax.nn.sigmoid(router_logits(p, u))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], idx].set(1.0)
    w = s * picked
    scale = 1.0 if drop == "route_scale" else cfg["moe_routed_scaling_factor"]
    return scale * w / jnp.sum(w, axis=-1, keepdims=True)


def sparse(cfg, ein, p, u, drop: str = ""):
    w = route(cfg, p, u, drop)

    def one(r, e):
        w1, w2, w_e = e
        return r + w_e[:, None] * gated_ffn(ein, w1, w2, u), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (p["w1"], p["w2"], w.T))
    if drop != "shared":
        out = out + gated_ffn(ein, p["v1"], p["v2"], u)
    return out


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("kind", "layer", "cfg_items", "precision",
                                    "drop", "margin"))
def _part(p, x, *, kind, layer, cfg_items, precision, drop, margin=False):
    """One half of a layer (``attn`` | ``dense`` | ``sparse``) over every
    row of ``x`` ``[R, T, D]``, one row at a time.  ``margin`` (a sparse
    half): -> (the rows, each position's :func:`routing_margin` ``[R, T]``)."""
    cfg = dict(cfg_items)
    ein = _ein(precision)
    norm = p["norm1" if kind == "attn" else "norm2"]

    keep = _b16 if precision == "bf16" else (lambda h: h)

    def row(h):
        u = rms(h, norm, cfg["rms_norm_eps"])
        if kind == "attn":
            return keep(h + attention(cfg, ein, p, u, layer, drop))
        out = keep(h + {"dense": dense, "sparse": sparse}[kind](
            cfg, ein, p, u, drop))
        return (out, routing_margin(cfg, p, u)) if margin else out

    return jax.lax.map(row, x)


def hidden(cfg: dict, seed: int, tokens, precision: str = "fp32",
           drop: str = "", margins: bool = False):
    """``tokens`` ``[R, T]`` -> (the stack's output ``[R, T, D]``, the top
    leaves); each layer's weights made once, all rows through it.
    ``margins``: a third value, each position's least
    :func:`routing_margin` over the sparse layers ``[R, T]``."""
    key, items = seed_key(seed), _items(cfg)
    top = layer_leaves(cfg, key, "top", 0)
    x = top["embed"][jnp.asarray(tokens, jnp.int32)]
    least = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        for kind in ("attn", layer_kinds(cfg, l)[1]):
            p = layer_leaves(cfg, key, kind, l)
            x = _part(p, x, kind=kind, layer=l, cfg_items=items,
                      precision=precision, drop=drop,
                      margin=margins and kind == "sparse")
            if margins and kind == "sparse":
                x, m = x
                least = jnp.minimum(least, m)
            del p
    return (x, top, least) if margins else (x, top)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(top, x, *, eps, precision):
    return _ein(precision)("td,dv->tv", rms(x, top["norm_f"], eps), top["head"])


def logits(cfg: dict, seed: int, tokens, precision: str = "fp32",
           drop: str = ""):
    """Logits ``[R, T, V]`` over whole sequences ``tokens`` ``[R, T]``."""
    x, top = hidden(cfg, seed, tokens, precision, drop)
    return jnp.stack([_logits(top, row, eps=cfg["rms_norm_eps"],
                              precision=precision) for row in x])


@jax.jit
def _gap(lg, served, mask):
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(mask, gap, 0.0))


def served_gap(cfg: dict, seed: int, tokens, at, served, mask,
               control: bool = False, drop: str = "") -> tuple[float, int]:
    """-> (the widest gap, over the served positions whose routing is
    decided, by which the logit of the token served after a position lies
    below the reference's best there; how many positions that was).
    ``tokens`` ``[R, T]``; ``at`` ``[R, S]`` the served positions of each
    row (the head reads those alone: ``[S, V]`` a row, never ``[T, V]``),
    ``served`` ``[R, S]`` the tokens served after them, ``mask`` ``[R, S]``
    which of the ``S`` are real.  A position counts where this reference's
    own least :func:`routing_margin` exceeds the configuration's
    ``check.routing_margin`` (module docstring).  ``control``: instead of the served token, the one the fp8
    control puts first at that position.  ``drop``: the reference WITHOUT
    that term (the served tokens then lie below the altered reference's
    best: the readings ``set_from`` quotes)."""
    eps = cfg["rms_norm_eps"]
    x, top, least = hidden(cfg, seed, tokens, drop=drop, margins=True)
    x8 = hidden(cfg, seed, tokens, "fp8")[0] if control else None
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


_KEYS = ("hidden_size", "head_dim", "num_key_value_heads", "num_experts",
         "num_experts_per_tok", "moe_routed_scaling_factor", "sliding_window",
         "rms_norm_eps")


def _hashable(x):
    if isinstance(x, dict):
        return tuple((k, _hashable(v)) for k, v in x.items())
    return tuple(x) if isinstance(x, list) else x


class _Rule(dict):
    """A ``rope_parameters`` entry that hashes (a static argument)."""

    def __hash__(self):
        return hash(_hashable(self))


def _items(cfg: dict) -> tuple:
    rules = tuple((k, _Rule(v)) for k, v in cfg["rope_parameters"].items()
                  if isinstance(v, dict))
    return tuple((k, cfg[k]) for k in _KEYS) + (
        ("layer_types", tuple(cfg["layer_types"])),
        ("num_attention_heads_per_layer",
         tuple(cfg["num_attention_heads_per_layer"])),
        ("rope_parameters", rules))
