"""Plain reference of the ``nemotron_h`` layer stack, and its weights.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no batching of requests into one step, no chunks, no kernels,
nothing of the program.  Every layer is ``x <- x + mixer(RMSNorm(x))`` with
one mixer chosen by the layer's letter in ``hybrid_override_pattern``:

- ``M`` Mamba-2: ``[z | xBC | dt] = W_in u``; ``xBC <- silu(conv(xBC) + b)``
  (causal, depthwise, kernel ``conv_kernel``); ``dt <- softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t`` — a ``lax.scan`` over time;
  ``out = W_out RMSNorm_groups(y * silu(z))``.
- ``*`` attention: 32 query heads over 2 K/V heads of 128, causal softmax,
  no positional term, no bias.
- ``E`` latent experts: ``s = sigmoid(W_r u)`` over all ``router_experts``;
  the ``num_experts_per_tok`` largest of ``s + b_corr`` are selected;
  ``w_i = routed_scaling_factor * s_i / sum_selected s_j``; ``r = sum_i w_i
  W2_i relu(W1_i W_down u)^2`` over the selected experts that lie in
  ``experts_held`` (a dense loop over the held experts, absent ones add
  nothing); ``out = W_up r + V2 relu(V1 u)^2``.

then a final RMSNorm and an untied head over the ``vocab_rows_held`` rows.

**Weights.**  Every leaf is a pure function of (seed, layer, leaf name),
drawn in float32 and rounded once to bfloat16: those bf16 values ARE the
model's weights, for the program (which holds them in bf16) and for this
reference (which computes on them in float32).  They are made layer by
layer and never held as a tree: an ``E`` layer is 3.0 GB in float32.

``precision="fp8"`` is the CONTROL, not a reference: both operands of every
matrix product rounded to float8_e4m3 under a per-tensor scale, the
nearest precision below the bf16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


# -- sizes ----------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    lo, hi = cfg["experts_held"]
    return dict(
        d=cfg["hidden_size"], d_in=d_in,
        conv=d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
        hq=cfg["num_attention_heads"] * cfg["head_dim"],
        hkv=cfg["num_key_value_heads"] * cfg["head_dim"],
        held=hi - lo, rows=cfg["vocab_rows_held"],
        layers=len(cfg["hybrid_override_pattern"]))


#: kind of layer -> ((leaf, shape from the sizes and the config, kind of draw), ...)
LEAVES = {
    "mamba": (
        ("norm", lambda z, c: (z["d"],), "scale"),
        ("in_proj", lambda z, c: (z["d"], z["d_in"] + z["conv"]
                                  + c["mamba_num_heads"]), "matrix"),
        ("conv_w", lambda z, c: (c["conv_kernel"], z["conv"]), "conv"),
        ("conv_b", lambda z, c: (z["conv"],), "bias"),
        ("dt_bias", lambda z, c: (c["mamba_num_heads"],), "dt_bias"),
        ("A_log", lambda z, c: (c["mamba_num_heads"],), "a_log"),
        ("D", lambda z, c: (c["mamba_num_heads"],), "scale"),
        ("gnorm", lambda z, c: (z["d_in"],), "scale"),
        ("out_proj", lambda z, c: (z["d_in"], z["d"]), "out_matrix"),
    ),
    "attn": (
        ("norm", lambda z, c: (z["d"],), "scale"),
        ("wq", lambda z, c: (z["d"], z["hq"]), "matrix"),
        ("wk", lambda z, c: (z["d"], z["hkv"]), "matrix"),
        ("wv", lambda z, c: (z["d"], z["hkv"]), "matrix"),
        ("wo", lambda z, c: (z["hq"], z["d"]), "out_matrix"),
    ),
    "moe": (
        ("norm", lambda z, c: (z["d"],), "scale"),
        ("router_w", lambda z, c: (z["d"], c["router_experts"]), "matrix"),
        ("b_corr", lambda z, c: (c["router_experts"],), "bias"),
        ("down", lambda z, c: (z["d"], c["moe_latent_size"]), "matrix"),
        ("w1", lambda z, c: (z["held"], c["moe_latent_size"],
                             c["moe_intermediate_size"]), "matrix"),
        ("w2", lambda z, c: (z["held"], c["moe_intermediate_size"],
                             c["moe_latent_size"]), "out_matrix"),
        ("up", lambda z, c: (c["moe_latent_size"], z["d"]), "out_matrix"),
        ("v1", lambda z, c: (z["d"], c["moe_shared_expert_intermediate_size"]),
         "matrix"),
        ("v2", lambda z, c: (c["moe_shared_expert_intermediate_size"], z["d"]),
         "out_matrix"),
    ),
    "top": (
        ("embed", lambda z, c: (z["rows"], z["d"]), "matrix"),
        ("norm_f", lambda z, c: (z["d"],), "scale"),
        ("head", lambda z, c: (z["d"], z["rows"]), "matrix"),
    ),
}
_ORDER = [(k, n) for k in ("top", "mamba", "attn", "moe") for n, _, _ in LEAVES[k]]


def seed_key(seed: int):
    """``--seed`` may exceed 32 signed bits: fold both halves in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def draw(key, shape, how: str, n_layers: int):
    """One leaf in float32, rounded once to bfloat16.  Matrices N(0, 0.02),
    output projections N(0, 0.02 / sqrt(2 L)), norm scales and ``D`` 1 +
    N(0, 0.02), biases N(0, 0.02) (so that a dropped one shows), the
    convolution's taps N(0, 0.3); ``dt_bias`` the inverse softplus of a
    step log-uniform in [1e-3, 1e-1] and ``A_log`` the log of a uniform in
    [1, 16], as the published initialisation draws them."""
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif how == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    else:
        std = {"conv": 0.3, "out_matrix": 0.02 / math.sqrt(2.0 * n_layers)
               }.get(how, 0.02)
        x = std * jax.random.normal(key, shape, jnp.float32)
        if how == "scale":
            x = 1.0 + x
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "how", "n_layers"))
def _leaf(key, index, layer, *, shape, how, n_layers):
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    return draw(k, shape, how, n_layers)


def leaf(cfg: dict, key, kind: str, name: str, layer: int):
    """The bf16 leaf ``name`` of layer ``layer`` (0 for ``top``), made on
    the device by a program that depends on its shape and draw alone."""
    z = sizes(cfg)
    shape, how = next((s(z, cfg), h) for n, s, h in LEAVES[kind] if n == name)
    return _leaf(key, _ORDER.index((kind, name)), layer, shape=tuple(shape),
                 how=how, n_layers=z["layers"])


def layer_leaves(cfg: dict, key, kind: str, layer: int) -> dict:
    """One layer's leaves as the reference computes on them: float32 holding
    the bf16 values."""
    return {n: leaf(cfg, key, kind, n, layer).astype(jnp.float32)
            for n, _, _ in LEAVES[kind]}


# -- the layer equations ----------------------------------------------------------

def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(precision: str):
    def ein(spec, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def _rms(x, g, eps, groups: int = 1):
    y = x.reshape(*x.shape[:-1], groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return y.reshape(x.shape) * g


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba(cfg, ein, p, u):
    """``u`` ``[T, D]`` -> ``[T, D]``; the recurrence step by step."""
    t = u.shape[0]
    h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_in = h * hd
    zxbcdt = ein("td,de->te", u, p["in_proj"])
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:-h], zxbcdt[:, -h:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(p["conv_w"][i] * padded[i:i + t] for i in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(t, h, hd)
    b = jnp.repeat(xbc[:, d_in:d_in + g * n].reshape(t, g, n), h // g, axis=1)
    c = jnp.repeat(xbc[:, d_in + g * n:].reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                        # [T, H]
    a = -jnp.exp(p["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h, hd, n)), (x, b, c, dt))
    y = (y + p["D"][:, None] * x).reshape(t, d_in)
    y = _rms(y * jax.nn.silu(z), p["gnorm"], cfg["norm_eps"], g)
    return ein("te,ed->td", y, p["out_proj"])


def attention(cfg, ein, p, u):
    t = u.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = ein("td,de->te", u, p["wq"]).reshape(t, h, hd)
    k = jnp.repeat(ein("td,de->te", u, p["wk"]).reshape(t, kv, hd), h // kv, 1)
    v = jnp.repeat(ein("td,de->te", u, p["wv"]).reshape(t, kv, hd), h // kv, 1)
    s = ein("thd,shd->hts", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    ctx = ein("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return ein("te,ed->td", ctx.reshape(t, h * hd), p["wo"])


def route(cfg, p, u):
    """-> weights ``[T, router_experts]``: ``w_i`` at the selected experts,
    0 elsewhere.  Always float32 at ``highest``: the published code keeps
    the router out of the low-precision path."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", u, p["router_w"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + p["b_corr"], cfg["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], idx].set(1.0)
    w = s * picked
    return cfg["routed_scaling_factor"] * w / jnp.sum(w, axis=-1, keepdims=True)


def experts(cfg, ein, p, u, routed: bool = True, shared: bool = True):
    out = jnp.zeros_like(u)
    if routed:
        lo, hi = cfg["experts_held"]
        w = route(cfg, p, u)[:, lo:hi]                   # the held experts'
        lat = ein("td,dl->tl", u, p["down"])

        def one(r, e):
            w1, w2, w_e = e
            y = ein("tf,fl->tl", _relu2(ein("tl,lf->tf", lat, w1)), w2)
            return r + w_e[:, None] * y, None

        r, _ = jax.lax.scan(one, jnp.zeros_like(lat), (p["w1"], p["w2"], w.T))
        out = out + ein("tl,ld->td", r, p["up"])
    if shared:
        out = out + ein("tf,fd->td", _relu2(ein("td,df->tf", u, p["v1"])), p["v2"])
    return out


MIXERS = {"mamba": mamba, "attn": attention, "moe": experts}


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "precision"),
                   donate_argnums=(1,))
def _layer(p, x, *, kind, cfg_items, precision):
    """One layer over every row of ``x`` ``[R, T, D]``, one row at a time."""
    cfg = dict(cfg_items)
    mixer = functools.partial(MIXERS[kind], cfg, _ein(precision), p)
    return jax.lax.map(
        lambda row: row + mixer(_rms(row, p["norm"], cfg["norm_eps"])), x)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits(top, x, *, cfg_items, precision):
    cfg = dict(cfg_items)
    x = _rms(x, top["norm_f"], cfg["norm_eps"])
    return _ein(precision)("td,dv->tv", x, top["head"])


def hidden(cfg: dict, seed: int, tokens, precision: str = "fp32"):
    """``tokens`` ``[R, T]`` -> (the stack's output ``[R, T, D]``, the top
    leaves); each layer's weights made once, all rows through it."""
    key, items = seed_key(seed), _items(cfg)
    top = layer_leaves(cfg, key, "top", 0)
    x = top["embed"][jnp.asarray(tokens, jnp.int32)]
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        p = layer_leaves(cfg, key, KINDS[letter], i)
        x = _layer(p, x, kind=KINDS[letter], cfg_items=items,
                   precision=precision)
        del p
    return x, top


def logits(cfg: dict, seed: int, tokens, precision: str = "fp32"):
    """Logits ``[R, T, V]`` over whole sequences ``tokens`` ``[R, T]``."""
    x, top = hidden(cfg, seed, tokens, precision)
    return jnp.stack([_logits(top, row, cfg_items=_items(cfg),
                              precision=precision) for row in x])


@jax.jit
def _gap(lg, served, mask):
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(mask, gap, 0.0))


def served_gap(cfg: dict, seed: int, tokens, served, mask,
               control: bool = False) -> float:
    """The widest gap, over the positions ``mask`` marks, by which the logit
    of the token served after each position (``served``) lies below the
    reference's best there; all three ``[R, T]``.  ``control``: instead of
    the served token, the one the fp8 control puts first at that position."""
    items = _items(cfg)
    x, top = hidden(cfg, seed, tokens)
    x8 = hidden(cfg, seed, tokens, "fp8")[0] if control else None
    widest = 0.0
    for r in range(x.shape[0]):
        lg = _logits(top, x[r], cfg_items=items, precision="fp32")
        tok = jnp.asarray(served[r], jnp.int32)
        if control:
            tok = jnp.argmax(_logits(top, x8[r], cfg_items=items,
                                     precision="fp8"), axis=-1).astype(jnp.int32)
        widest = max(widest, float(_gap(lg, tok, jnp.asarray(mask[r], bool))))
    return widest


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
         "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
         "conv_kernel", "router_experts", "num_experts_per_tok",
         "routed_scaling_factor", "moe_latent_size", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "norm_eps",
         "hybrid_override_pattern", "vocab_rows_held")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS) + (
        ("experts_held", tuple(cfg["experts_held"])),)

