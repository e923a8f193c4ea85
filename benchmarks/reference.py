"""Plain reference of the GPT-2 block stack the configurations state.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no cache, no batching tricks; it imports nothing of the
program and makes its own weights from the seed (:mod:`benchmarks.weights`).

Architecture, as the configuration files state it (departures from the
published Cerebras-GPT-1.3B are listed there): learned positions,
pre-LayerNorm blocks (eps from the file), full multi-head causal attention
scaled by 1/sqrt(head size), GELU (tanh form) FFN, final LayerNorm, untied
output head with bias.  Training: mean token cross entropy, global-norm
gradient clipping, momentum SGD  v <- mu v - lr g ; p <- p + v.

``precision="fp8"`` is the CONTROL, not a reference: the same code with
both operands of every matmul rounded to float8_e4m3 and every matmul's
cotangent to float8_e5m2 (each per-tensor scaled to its format's range,
float32 accumulation): the nearest precision below the bf16 the
configurations state.  ``correct`` must come out false for it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights

def _round(x, dtype, top):
    """Round to an fp8 ``dtype`` under a per-tensor scale; carried as fp32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _q8(x):
    """Operands in float8_e4m3; straight-through for the gradient (the
    backward matmuls still read the rounded operands)."""
    return x + jax.lax.stop_gradient(_round(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _g8(y):
    """Identity whose cotangent is rounded to float8_e5m2: the usual fp8
    recipe (e4m3 forward, e5m2 gradients), each under a per-tensor scale."""
    return y


_g8.defvjp(lambda y: (y, None),
           lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


def _ein(precision: str):
    def ein(spec, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        y = jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return _g8(y) if precision == "fp8" else y
    return ein


def _ln(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _block(cfg, ein, p, x):
    """One block over one sequence ``x`` ``[T, D]``."""
    t, d = x.shape
    h_n = cfg["n_head"]
    h = _ln(x, p["ln1_g"], p["ln1_b"], cfg["layer_norm_epsilon"])
    q = (ein("td,de->te", h, p["wq"]) + p["bq"]).reshape(t, h_n, d // h_n)
    k = (ein("td,de->te", h, p["wk"]) + p["bk"]).reshape(t, h_n, d // h_n)
    v = (ein("td,de->te", h, p["wv"]) + p["bv"]).reshape(t, h_n, d // h_n)
    s = ein("thd,shd->hts", q, k) / math.sqrt(d // h_n)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = ein("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(t, d)
    x = x + ein("td,de->te", a, p["wo"]) + p["bo"]
    h = _ln(x, p["ln2_g"], p["ln2_b"], cfg["layer_norm_epsilon"])
    h = jax.nn.gelu(ein("td,df->tf", h, p["w_up"]) + p["b_up"], approximate=True)
    return x + ein("tf,fd->td", h, p["w_down"]) + p["b_down"]


def _logits_row(cfg, ein, top, blocks_of, tokens):
    """``tokens`` ``[T]`` -> logits ``[T, V]``.  ``blocks_of(i)`` gives
    layer ``i``'s leaves (a slice of stacked leaves, or made on the fly)."""
    x = top["wte"][tokens] + top["wpe"][: tokens.shape[0]]

    def body(x, i):
        return jax.checkpoint(functools.partial(_block, cfg, ein))(blocks_of(i), x), None

    x, _ = jax.lax.scan(body, x, jnp.arange(cfg["n_layer"]))
    x = _ln(x, top["lnf_g"], top["lnf_b"], cfg["layer_norm_epsilon"])
    return ein("td,dv->tv", x, top["head_w"]) + top["head_b"]


# -- serving: logits of prompt + served tokens, weights made layer by layer ---

def _logits_from_seed(cfg, key, tokens, precision):
    """No weight tree is ever held (each layer's leaves are made inside the
    scan), so the reference fits beside nothing but itself."""
    return _logits_row(cfg, _ein(precision), weights.top_leaves(cfg, key),
                       lambda i: weights.layer_leaves(cfg, key, i), tokens)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _served_logits(key, tokens, *, cfg_items, precision):
    return _logits_from_seed(dict(cfg_items), key, tokens, precision)


def served_logits(cfg: dict, seed: int, tokens, precision: str = "fp32"):
    """Logits ``[T, V]`` over one whole sequence."""
    return _served_logits(weights.seed_key(seed), jnp.asarray(tokens, jnp.int32),
                          cfg_items=_items(cfg), precision=precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _served_gap(key, tokens, served, mask, *, cfg_items, control):
    cfg = dict(cfg_items)
    logits = _logits_from_seed(cfg, key, tokens, "fp32")
    if control:
        served = jnp.argmax(_logits_from_seed(cfg, key, tokens, "fp8"), axis=-1)
    gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(mask, gap, 0.0))


def served_gap(cfg: dict, seed: int, tokens, served, mask, control: bool = False):
    """The widest gap, over the positions ``mask`` marks, by which the
    logit of the token served after each position (``served``) lies below
    the reference's best there.  All three are ``[n_positions]``, so one
    program serves every request.  ``control``: instead of the served
    token, the one the fp8 control puts first at that position."""
    return float(_served_gap(
        weights.seed_key(seed), jnp.asarray(tokens, jnp.int32),
        jnp.asarray(served, jnp.int32), jnp.asarray(mask, bool),
        cfg_items=_items(cfg), control=control))


def _items(cfg: dict) -> tuple:
    keys = ("n_layer", "n_embd", "n_head", "n_inner", "n_positions",
            "vocab_size", "layer_norm_epsilon")
    return tuple((k, cfg[k]) for k in keys)


# -- training: three steps of clipped momentum SGD -----------------------------

def _loss_row(cfg, ein, params, x, y):
    logits = _logits_row(cfg, ein, params["top"],
                         lambda i: jax.tree.map(lambda a: a[i], params["blocks"]), x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def init_params(key, *, cfg_items):
    cfg = dict(cfg_items)
    return {"top": weights.top_leaves(cfg, key),
            "blocks": weights.stacked_blocks(cfg, key)}


def leaf_norms(cfg: dict, tree) -> dict:
    """-> {(canonical name, layer): l2 norm} of a reference-layout tree."""
    out = {}
    for name, x in tree["top"].items():
        out[(name, 0)] = jnp.sqrt(jnp.sum(jnp.square(x)))
    for name, x in tree["blocks"].items():
        n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
        for i in range(cfg["n_layer"]):
            out[(name, i)] = n[i]
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision", "hp"),
                   donate_argnums=(0, 1))
def _train_step(params, vel, xs, ys, *, cfg_items, precision, hp):
    cfg, ein = dict(cfg_items), _ein(precision)
    lr, momentum, clip = hp

    def row(carry, xy):
        loss_sum, g_sum = carry
        loss, g = jax.value_and_grad(
            lambda p: _loss_row(cfg, ein, p, xy[0], xy[1]))(params)
        return (loss_sum + loss, jax.tree.map(jnp.add, g_sum, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(row, (jnp.float32(0), zeros), (xs, ys))
    n = xs.shape[0]
    loss, grads = loss / n, jax.tree.map(lambda g: g / n, grads)
    if clip:
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
    vel = jax.tree.map(lambda v, g: momentum * v - lr * g, vel, grads)
    params = jax.tree.map(jnp.add, params, vel)
    return params, vel, loss, leaf_norms(cfg, grads)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _change_norms(params, key, *, cfg_items):
    p0 = init_params(key, cfg_items=cfg_items)
    return leaf_norms(dict(cfg_items), jax.tree.map(jnp.subtract, params, p0))


def train_steps(cfg: dict, seed: int, batches, hp: tuple, precision: str = "fp32"):
    """Follow ``len(batches)`` steps from the seed's weights.  ``batches``
    is a list of ``(x, y)`` int arrays ``[rows, T]``; ``hp`` is
    ``(lr, momentum, grad_clip)``.  -> (losses, first-gradient norms per
    leaf as the optimizer got it, norms of the parameters' change)."""
    items = _items(cfg)
    params = init_params(weights.seed_key(seed), cfg_items=items)
    vel = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for x, y in batches:
        params, vel, loss, gn = _train_step(
            params, vel, jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32),
            cfg_items=items, precision=precision, hp=hp)
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in gn.items()}
    change = _change_norms(params, weights.seed_key(seed), cfg_items=items)
    change = {k: float(v) for k, v in change.items()}
    del params, vel
    return losses, first, change
