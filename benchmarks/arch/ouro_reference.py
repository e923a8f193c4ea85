"""Plain reference of the ``ouro`` looped stack, and its weights.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no kernel, no batching of requests into one step, a Python loop
over loop steps and layers, nothing of the program.  With ``T =
total_ut_steps`` and ``L = num_hidden_layers``, every norm an RMSNorm at
``rms_norm_eps`` and no bias except the gate's:

- ``h <- Embed[x]``; for step ``t = 1..T``, for layer ``l = 1..L``, with the
  SAME weights at every ``t``:
  ``a = RMSNorm(h; g1)``; ``q, k, v = a W_q, a W_k, a W_v``; ``q, k <-
  RoPE(q, k; position, rope_theta)`` over the whole head, rotate-half
  pairing (``i`` with ``i + head_dim / 2``); ``o = softmax(q k^T /
  sqrt(head_dim) + causal) v`` over the keys and values of the same ``(t,
  l)``; ``h <- h + RMSNorm(o W_o; g2)``; ``m = RMSNorm(h; g3)``; ``f =
  (silu(m W_gate) * (m W_up)) W_down``; ``h <- h + RMSNorm(f; g4)``;
- after layer ``L`` of step ``t``: ``h <- u_t = RMSNorm(h; g_f)`` (the
  step's output and the next step's input) and the exit gate ``lam_t =
  sigmoid(u_t . w_e + b_e)``;
- ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for ``t < T``, ``p_T = prod_{j<T}
  (1 - lam_j)``; ``t* = min {t : sum_{j<=t} p_j >= early_exit_threshold}``,
  else ``T``, per token; ``logits = u_{t*} W_head`` (untied).

**Weights.**  Every leaf is a pure function of (seed, layer, leaf name),
drawn in float32 and rounded once to bfloat16: those bf16 values ARE the
model's weights, for the program (which holds them in bf16) and for this
reference (which computes on them in float32).  The 48 layers are HELD
WHOLE as those bf16 values (4.9 GB on the chip, made once and walked four
times) and each is widened to float32 where it is used; the float32 tree
(10.7 GB) is never made.

``precision="fp8"`` is the CONTROL, not a reference: both operands of every
matrix product rounded to float8_e4m3 under a per-tensor scale, the
nearest precision below the bf16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


# -- sizes and weights ------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                hq=cfg["num_attention_heads"] * cfg["head_dim"],
                hkv=cfg["num_key_value_heads"] * cfg["head_dim"],
                rows=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                loops=cfg["total_ut_steps"])


#: kind -> ((leaf, shape from the sizes, kind of draw), ...)
LEAVES = {
    "layer": (
        ("g1", lambda z: (z["d"],), "scale"),
        ("wq", lambda z: (z["d"], z["hq"]), "matrix"),
        ("wk", lambda z: (z["d"], z["hkv"]), "matrix"),
        ("wv", lambda z: (z["d"], z["hkv"]), "matrix"),
        ("wo", lambda z: (z["hq"], z["d"]), "out_matrix"),
        ("g2", lambda z: (z["d"],), "scale"),
        ("g3", lambda z: (z["d"],), "scale"),
        ("w_gate", lambda z: (z["d"], z["f"]), "matrix"),
        ("w_up", lambda z: (z["d"], z["f"]), "matrix"),
        ("w_down", lambda z: (z["f"], z["d"]), "out_matrix"),
        ("g4", lambda z: (z["d"],), "scale"),
    ),
    "top": (
        ("embed", lambda z: (z["rows"], z["d"]), "matrix"),
        ("norm_f", lambda z: (z["d"],), "scale"),
        ("head", lambda z: (z["d"], z["rows"]), "matrix"),
        ("gate_w", lambda z: (z["d"],), "matrix"),
        ("gate_b", lambda z: (), "bias"),
    ),
}
_ORDER = [(k, n) for k in ("top", "layer") for n, _, _ in LEAVES[k]]


def kind_params(cfg: dict, kind: str) -> int:
    """Parameters of one layer, or of the top leaves."""
    z = sizes(cfg)
    return sum(math.prod(shape(z)) for _, shape, _ in LEAVES[kind])


def parameter_count(cfg: dict) -> int:
    """Every parameter of the model: the layers once, whatever the loops."""
    return (cfg["num_hidden_layers"] * kind_params(cfg, "layer")
            + kind_params(cfg, "top"))


def seed_key(seed: int):
    """``--seed`` may exceed 32 signed bits: fold both halves in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def draw(key, shape, how: str, n_layers: int):
    """One leaf in float32, rounded once to bfloat16.  Matrices N(0, 0.02),
    output projections N(0, 0.02 / sqrt(2 L)), norm scales 1 + N(0, 0.02),
    the gate's bias N(0, 0.02) (so that a dropped one shows)."""
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
    shape, how = next((s(z), h) for n, s, h in LEAVES[kind] if n == name)
    return _leaf(key, _ORDER.index((kind, name)), layer, shape=tuple(shape),
                 how=how, n_layers=z["layers"])


def leaves(cfg: dict, key, kind: str, layer: int = 0) -> dict:
    """One layer's (or the top's) leaves: the bf16 values."""
    return {n: leaf(cfg, key, kind, n, layer) for n, _, _ in LEAVES[kind]}


def _f32(p: dict) -> dict:
    return {n: x.astype(jnp.float32) for n, x in p.items()}


# -- the layer equations ----------------------------------------------------------

def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _b16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _ein(precision: str):
    """``fp32``; ``fp8`` (the control); ``bf16`` — the operands of every
    product, and in :func:`block` the residual stream after every add,
    rounded to the precision the configuration states, the rest float32:
    what rounding alone costs through 192 layer-runs (PERF.md section 6)."""
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


def rope(x, theta: float):
    """``x`` ``[T, H, Dh]`` at positions ``0..T-1``: pair ``(i, i + Dh/2)``
    turned by ``position * theta ** (-2 i / Dh)``."""
    t, _, hd = x.shape
    half = hd // 2
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, ein, p, a):
    """``a`` ``[T, D]`` (normed) -> ``o W_o`` ``[T, D]``."""
    t = a.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = rope(ein("td,de->te", a, p["wq"]).reshape(t, h, hd), cfg["rope_theta"])
    k = rope(ein("td,de->te", a, p["wk"]).reshape(t, kv, hd), cfg["rope_theta"])
    v = ein("td,de->te", a, p["wv"]).reshape(t, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    s = ein("thd,shd->hts", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    ctx = ein("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return ein("te,ed->td", ctx.reshape(t, h * hd), p["wo"])


def ffn(ein, p, m):
    gate = jax.nn.silu(ein("td,df->tf", m, p["w_gate"]))
    return ein("tf,fd->td", gate * ein("td,df->tf", m, p["w_up"]), p["w_down"])


def block(cfg, ein, p, h, keep=lambda x: x):
    """One layer over one sequence ``h`` ``[T, D]``; ``keep`` rounds the
    residual stream after an add (the ``bf16`` reading only)."""
    eps = cfg["rms_norm_eps"]
    h = keep(h + rms(attention(cfg, ein, p, rms(h, p["g1"], eps)), p["g2"], eps))
    return keep(h + rms(ffn(ein, p, rms(h, p["g3"], eps)), p["g4"], eps))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(p, x, *, cfg_items, precision):
    """One layer over every row of ``x`` ``[R, T, D]``, one row at a time;
    ``p`` the layer's bf16 values, widened here."""
    p = _f32(p)
    keep = _b16 if precision == "bf16" else (lambda h: h)
    return jax.lax.map(lambda row: block(dict(cfg_items), _ein(precision), p,
                                         row, keep), x)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _close(top, x, *, cfg_items):
    """The end of a loop step: -> (``u_t`` ``[R, T, D]``, ``lam_t`` ``[R, T]``)."""
    u = rms(x, top["norm_f"], dict(cfg_items)["rms_norm_eps"])
    return u, jax.nn.sigmoid(jnp.sum(u * top["gate_w"], axis=-1) + top["gate_b"])


def exit_steps(lams, tau: float):
    """``lams`` ``[T][...]`` -> each token's 1-based exit step ``t*``."""
    n = len(lams)
    survive = jnp.ones_like(lams[0])
    cdf, t_star = jnp.zeros_like(lams[0]), jnp.zeros(lams[0].shape, jnp.int32)
    for t, lam in enumerate(lams):
        cdf = cdf + (lam * survive if t < n - 1 else survive)
        t_star = jnp.where((t_star == 0) & (cdf >= tau), t + 1, t_star)
        survive = survive * (1.0 - lam)
    return jnp.where(t_star == 0, n, t_star)


def forward(cfg: dict, seed: int, tokens, precision: str = "fp32",
            tau: float | None = None) -> dict:
    """``tokens`` ``[R, T]`` -> ``{"u": [T_steps][R, T, D], "lam":
    [T_steps][R, T], "t_star": [R, T], "read": [R, T, D] (u at t*), "top"}``;
    ``tau`` defaults to the configuration's ``early_exit_threshold``."""
    key, items = seed_key(seed), _items(cfg)
    top = _f32(leaves(cfg, key, "top"))
    stack = [leaves(cfg, key, "layer", i) for i in range(cfg["num_hidden_layers"])]
    x = top["embed"][jnp.asarray(tokens, jnp.int32)]
    us, lams = [], []
    for _ in range(cfg["total_ut_steps"]):
        for p in stack:
            x = _layer(p, x, cfg_items=items, precision=precision)
        u, lam = _close(top, x, cfg_items=items)
        us.append(u)
        lams.append(lam)
        x = u  # the step's output is the next step's input
    t_star = exit_steps(lams, cfg["early_exit_threshold"] if tau is None else tau)
    read = us[-1]
    for t in range(len(us) - 1):
        read = jnp.where((t_star == t + 1)[..., None], us[t], read)
    return {"u": us, "lam": lams, "t_star": t_star, "read": read, "top": top}


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(head, x, *, precision):
    return _ein(precision)("td,dv->tv", x, head)


def logits(cfg: dict, seed: int, tokens, precision: str = "fp32",
           tau: float | None = None):
    """Logits ``[R, T, V]`` over whole sequences ``tokens`` ``[R, T]``."""
    out = forward(cfg, seed, tokens, precision, tau)
    return jnp.stack([_logits(out["top"]["head"], row, precision=precision)
                      for row in out["read"]])


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
    out = forward(cfg, seed, tokens)
    head, x = out["top"]["head"], out["read"]
    del out
    x8 = forward(cfg, seed, tokens, "fp8")["read"] if control else None
    widest = 0.0
    for r in range(x.shape[0]):
        lg = _logits(head, x[r], precision="fp32")
        tok = jnp.asarray(served[r], jnp.int32)
        if control:
            tok = jnp.argmax(_logits(head, x8[r], precision="fp8"),
                             axis=-1).astype(jnp.int32)
        widest = max(widest, float(_gap(lg, tok, jnp.asarray(mask[r], bool))))
    return widest


_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS)
