"""Plain reference of the ``sdar_moe`` layer stack served by block
diffusion, and its weights.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no kernel, no batching of the program's, nothing of the program.
With ``u = RMSNorm(h)`` at ``rms_norm_eps`` and no bias anywhere, every
layer is ``h <- h + Attn(RMSNorm(h))``, then ``h <- h + MoE(RMSNorm(h))``;
a final RMSNorm and an untied head.

- **Attention.**  ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads of ``head_dim``: ``q = u W_q``, ``k = u
  W_k``, ``v = u W_v``; each query and key head RMS-normed over its
  ``head_dim`` with a scale of its own (``q_norm``, ``k_norm``), then
  rotated (rotate-half pairing over the whole head, angles ``position x
  rope_theta ** (-2 i / head_dim)``); query head ``h`` reads K/V head ``h //
  (H / Hkv)``; scores ``q.k / sqrt(head_dim)``; context through ``W_o``.
- **Experts**, every layer: ``p = softmax(W_r u)`` over ``num_experts`` in
  float32; the ``num_experts_per_tok`` largest; their ``p`` renormalised to
  sum 1 (``norm_topk_prob``); ``sum_i w_i E_i(u)``, ``E_i(u) = W2_i
  (silu(u G_i) * u U_i)`` at ``moe_intermediate_size``, ``G_i`` and ``U_i``
  the two halves of ONE leaf ``[D, 2 F]`` (gate first).  No shared expert.
  The experts run as a loop over the experts that receive a row, each over
  its own rows only.

**Generation by block diffusion** (the configuration's ``assumed``): blocks
of ``block_length`` positions aligned from 0; a block starts with its
unknown positions set to ``mask_token_id``; a pass runs the block's
positions against the K/V of every earlier block with no mask inside the
block (query ``i`` sees key ``j`` iff ``j // B <= i // B``), position
``i``'s logits predicting position ``i``'s token, and commits the masked
positions whose greedy token is the most probable.  So the served result
is checked pass by pass: :func:`served_gap` rebuilds, from a request's
tokens and the pass that committed each, the block as it stood at every
pass; one block-causal forward over the request's committed sequence gives
every layer's K/V of the blocks before it; each recorded pass then goes
through as a row of one layer-outer batch, attending its own context and
its own block, and its logits say how far the log-probability the program
stated for each token it committed lies from the reference's, and whether
the pass committed the positions the reference finds the most probable
(:func:`served_gap`).

**Weights.**  Every leaf is a pure function of (seed, layer, leaf name),
drawn in float32 and rounded once to bfloat16: those bf16 values ARE the
model's weights, for the program (which holds them in bf16) and for this
reference (which computes on them in float32).  They are made layer by
layer and never held as a tree: a layer's experts are 2.4 GB in float32.

**What is compared.**  The top-8 of 128 router logits is ill-conditioned
on random weights: the least lead of the 8th logit over the 9th across
the six layers is 0.0065 at the median position, and a position whose
selection bf16 rounding swaps reads as far off as the fp8 control
(PERF.md section 6).  So :func:`served_gap` counts a committed position
only where THIS reference, in float32, decides every layer's selection by
more than ``check.routing_margin`` router logits, and a pair of
positions of one pass only where both are so decided and the reference
orders them by more than ``check.order_margin`` nats (on random weights
every position's top log-probability lies within a few hundredths of a nat
of the others', and bf16 rounding reorders pairs closer than that).  A
greedy token agrees with the reference's at nearly every such position
whatever the precision, so the program states, for each token it commits,
the log-probability it ranked it by, and the check compares that number at
every one of them; means over all of them, since a widest gap rests on the
few positions where a near-tie flips.

``precision="fp8"`` is the CONTROL, not a reference: both operands of every
matrix product rounded to float8_e4m3 under a per-tensor scale, the
nearest precision below the bf16 the configuration states.  ``drop`` leaves
one term of the mathematics out (``bidirectional``: the block's own
positions causal among themselves; ``qk_norm``; ``topk_renorm``): what the
cell's limit must refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: queries whose scores are held at a time in the context forward
QUERY_BLOCK = 256
#: recorded passes whose scores or logits are held at a time
ROW_CHUNK = 64


# -- sizes ------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    hd = cfg["head_dim"]
    return dict(d=cfg["hidden_size"], hd=hd,
                hq=cfg["num_attention_heads"] * hd,
                hkv=cfg["num_key_value_heads"] * hd,
                fe=cfg["moe_intermediate_size"], experts=cfg["num_experts"],
                rows=cfg["vocab_size"], layers=cfg["num_hidden_layers"])


#: kind -> ((leaf, shape from sizes, kind of draw), ...)
LEAVES = {
    "attn": (
        ("norm1", lambda z: (z["d"],), "scale"),
        ("wq", lambda z: (z["d"], z["hq"]), "matrix"),
        ("wk", lambda z: (z["d"], z["hkv"]), "matrix"),
        ("wv", lambda z: (z["d"], z["hkv"]), "matrix"),
        ("q_norm", lambda z: (z["hd"],), "scale"),
        ("k_norm", lambda z: (z["hd"],), "scale"),
        ("wo", lambda z: (z["hq"], z["d"]), "out_matrix"),
    ),
    "moe": (
        ("norm2", lambda z: (z["d"],), "scale"),
        ("router_w", lambda z: (z["d"], z["experts"]), "matrix"),
        ("w1", lambda z: (z["experts"], z["d"], 2 * z["fe"]), "matrix"),
        ("w2", lambda z: (z["experts"], z["fe"], z["d"]), "out_matrix"),
    ),
    "top": (
        ("embed", lambda z: (z["rows"], z["d"]), "matrix"),
        ("norm_f", lambda z: (z["d"],), "scale"),
        ("head", lambda z: (z["d"], z["rows"]), "matrix"),
    ),
}
_ORDER = [(k, n) for k in ("top", "attn", "moe") for n, _, _ in LEAVES[k]]


def kind_params(cfg: dict, kind: str) -> int:
    """Parameters of one layer's ``kind`` part (or of the top leaves)."""
    z = sizes(cfg)
    return sum(math.prod(shape(z)) for _, shape, _ in LEAVES[kind])


def parameter_count(cfg: dict) -> int:
    """Every parameter of the layers the configuration holds."""
    return kind_params(cfg, "top") + cfg["num_hidden_layers"] * (
        kind_params(cfg, "attn") + kind_params(cfg, "moe"))


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
    shape, how = next((s(z), h) for n, s, h in LEAVES[kind] if n == name)
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


def _ein(precision: str):
    """``fp32``, or ``fp8`` (the control): both operands of every product
    rounded to float8_e4m3."""
    def ein(spec, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def rope(x, positions, theta: float):
    """``x`` ``[..., T, H, Dh]`` at ``positions`` ``[..., T]``, rotate-half
    pairing over the whole head."""
    hd = x.shape[-1]
    half = hd // 2
    freq = float(theta) ** (-2.0 * np.arange(half, dtype=np.float64) / hd)
    ang = (positions.astype(jnp.float32)[..., None]
           * jnp.asarray(freq, jnp.float32))[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(cfg, ein, p, u, positions, drop: str = ""):
    """``u`` ``[..., T, D]`` (normed) at ``positions`` -> q ``[..., T, H,
    Dh]``, k, v ``[..., T, Hkv, Dh]``: projected, q/k-normed, rotated."""
    lead = u.shape[:-1]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = ein("...d,de->...e", u, p["wq"]).reshape(*lead, -1, hd)
    k = ein("...d,de->...e", u, p["wk"]).reshape(*lead, -1, hd)
    v = ein("...d,de->...e", u, p["wv"]).reshape(*lead, -1, hd)
    if drop != "qk_norm":
        q, k = rms(q, p["q_norm"], eps), rms(k, p["k_norm"], eps)
    theta = cfg["rope_theta"]
    return rope(q, positions, theta), rope(k, positions, theta), v


def _groups(cfg, x):
    """``[..., H, Dh]`` -> ``[..., Hkv, H / Hkv, Dh]``."""
    return x.reshape(*x.shape[:-2], cfg["num_key_value_heads"], -1,
                     x.shape[-1])


def context_attention(cfg, ein, p, u, drop: str = ""):
    """``u`` ``[T, D]`` (normed), a whole committed sequence ->
    (``[T, D]``, its K and V ``[T, Hkv, Dh]``).  Block-causal: query ``i``
    sees key ``j`` iff ``j // B <= i // B`` (``bidirectional`` dropped:
    ``j <= i``); the scores a block of queries at a time."""
    t = u.shape[0]
    b = cfg["block_length"]
    pos = jnp.arange(t)
    q, k, v = qkv(cfg, ein, p, u, pos, drop)
    qg = _groups(cfg, q)

    def block(start):
        i = start + jnp.arange(QUERY_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(qg, start, QUERY_BLOCK, axis=0)
        s = ein("tgrd,sgd->grts", qb, k) / math.sqrt(cfg["head_dim"])
        seen = (pos[None, :] <= i[:, None] if drop == "bidirectional"
                else pos[None, :] // b <= i[:, None] // b)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return ein("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v)

    if t % QUERY_BLOCK:
        raise ValueError(f"{t} positions are not whole blocks of {QUERY_BLOCK}")
    ctx = jax.lax.map(block, jnp.arange(t // QUERY_BLOCK) * QUERY_BLOCK)
    ctx = ctx.reshape(t, -1)
    return ein("te,ed->td", ctx, p["wo"]), k, v


def pass_attention(cfg, ein, p, u, starts, kc, vc, drop: str = ""):
    """Recorded passes of one request: ``u`` ``[N, B, D]`` (normed) at
    positions ``starts[n] .. starts[n] + B - 1``, each attending the keys
    ``kc``/``vc`` ``[T, Hkv, Dh]`` of its context ``j < starts[n]`` and its
    own block's B keys, every one of them (``bidirectional`` dropped: the
    block's ``j <= i``).  -> ``[N, B, D]``; ``ROW_CHUNK`` passes at a time."""
    n, b, _ = u.shape
    t = kc.shape[0]
    pos = starts[:, None] + jnp.arange(b)
    q, k, v = qkv(cfg, ein, p, u, pos, drop)
    own = (jnp.arange(b)[None, :] <= jnp.arange(b)[:, None]
           if drop == "bidirectional" else jnp.ones((b, b), bool))
    scale = 1.0 / math.sqrt(cfg["head_dim"])

    def chunk(c):
        at = c * ROW_CHUNK
        qc = _groups(cfg, jax.lax.dynamic_slice_in_dim(q, at, ROW_CHUNK))
        kb = jax.lax.dynamic_slice_in_dim(k, at, ROW_CHUNK)
        vb = jax.lax.dynamic_slice_in_dim(v, at, ROW_CHUNK)
        st = jax.lax.dynamic_slice_in_dim(starts, at, ROW_CHUNK)
        s_ctx = ein("nigrd,tgd->ngrit", qc, kc) * scale
        s_ctx = jnp.where((jnp.arange(t)[None, :] < st[:, None])
                          [:, None, None, None, :], s_ctx, -jnp.inf)
        s_own = ein("nigrd,njgd->ngrij", qc, kb) * scale
        s_own = jnp.where(own[None, None, None], s_own, -jnp.inf)
        w = jax.nn.softmax(jnp.concatenate([s_ctx, s_own], axis=-1), axis=-1)
        ctx = (ein("ngrit,tgd->nigrd", w[..., :t], vc)
               + ein("ngrij,njgd->nigrd", w[..., t:], vb))
        return ctx.reshape(ROW_CHUNK, b, -1)

    if n % ROW_CHUNK:
        raise ValueError(f"{n} passes are not whole chunks of {ROW_CHUNK}")
    ctx = jax.lax.map(chunk, jnp.arange(n // ROW_CHUNK)).reshape(n, b, -1)
    return ein("nbe,ed->nbd", ctx, p["wo"])


def router_logits(p, u):
    """``W_r u`` ``[M, num_experts]``.  Always float32 at ``highest``."""
    return jnp.einsum("md,de->me", u, p["router_w"],
                      precision=jax.lax.Precision.HIGHEST)


def route(cfg, p, u, drop: str = ""):
    """-> (selected experts ``[M, k]``, their weights ``[M, k]``, by how
    many router logits the ``k``-th selected leads the first unselected
    ``[M]``)."""
    k = cfg["num_experts_per_tok"]
    logits = router_logits(p, u)
    top, idx = jax.lax.top_k(logits, k + 1)
    probs = jax.nn.softmax(logits, axis=-1)
    w = jnp.take_along_axis(probs, idx[:, :k], axis=-1)
    if drop != "topk_renorm":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx[:, :k], w, top[:, k - 1] - top[:, k]


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _experts(p, u, experts, rows, weights, *, cfg_items, precision):
    """``sum_i w_i E_i(u)`` over ``u`` ``[M, D]``: a loop over the
    ``experts`` that receive a row, expert ``experts[e]`` over the rows
    ``rows[e]`` (weights ``weights[e]``, 0 on padding)."""
    ein = _ein(precision)
    f = dict(cfg_items)["moe_intermediate_size"]

    def one(out, x):
        e, r, w = x
        w1 = jax.lax.dynamic_index_in_dim(p["w1"], e, keepdims=False)
        w2 = jax.lax.dynamic_index_in_dim(p["w2"], e, keepdims=False)
        hid = ein("md,df->mf", u[r], w1)
        y = ein("mf,fd->md", jax.nn.silu(hid[:, :f]) * hid[:, f:], w2)
        return out.at[r].add(w[:, None] * y), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (experts, rows, weights))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "drop"))
def _route(p, h, *, cfg_items, drop):
    cfg = dict(cfg_items)
    u = rms(h, p["norm2"], cfg["rms_norm_eps"])
    return (u, *route(cfg, p, u, drop))


def moe(cfg, p, h, live, precision: str, drop: str = ""):
    """``h <- h + MoE(RMSNorm(h))`` over tokens ``h`` ``[M, D]``, the rows
    ``live`` marks (padding takes no expert).  -> (h, each token's routing
    margin ``[M]``)."""
    items = _items(cfg)
    u, idx, w, margin = _route(p, h, cfg_items=items, drop=drop)
    idx, w = np.asarray(idx), np.asarray(w)
    live = np.asarray(live)
    hit = [np.flatnonzero((idx == e).any(-1) & live)
           for e in range(cfg["num_experts"])]
    hit = [(e, r) for e, r in enumerate(hit) if len(r)]
    cap = 1 << (max(len(r) for _, r in hit) - 1).bit_length()
    experts = np.array([e for e, _ in hit], np.int32)
    rows = np.zeros((len(hit), cap), np.int32)
    weights = np.zeros((len(hit), cap), np.float32)
    for i, (e, r) in enumerate(hit):
        rows[i, :len(r)] = r
        weights[i, :len(r)] = np.where(idx[r] == e, w[r], 0.0).sum(-1)
    out = _experts(p, u, experts, rows, weights, cfg_items=items,
                   precision=precision)
    return h + out, margin


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("cfg_items", "precision", "drop"))
def _context(p, x, *, cfg_items, precision, drop):
    """The attention half of a layer over every committed sequence ``x``
    ``[R, T, D]``, one at a time; -> (x, K, V ``[R, T, Hkv, Dh]``)."""
    cfg = dict(cfg_items)

    def row(h):
        y, k, v = context_attention(
            cfg, _ein(precision), p, rms(h, p["norm1"], cfg["rms_norm_eps"]),
            drop)
        return h + y, k, v

    return jax.lax.map(row, x)


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("cfg_items", "precision", "drop"))
def _passes(p, x, starts, kc, vc, *, cfg_items, precision, drop):
    """The attention half of a layer over every recorded pass ``x`` ``[R,
    N, B, D]``, request by request against its own context K/V."""
    cfg = dict(cfg_items)

    def row(args):
        h, st, k, v = args
        return h + pass_attention(cfg, _ein(precision), p,
                                  rms(h, p["norm1"], cfg["rms_norm_eps"]),
                                  st, k, v, drop)

    return jax.lax.map(row, (x, starts, kc, vc))


def passes_of(cfg: dict, prompt, generated, record) -> dict:
    """The recorded passes of one served request, as the blocks stood at
    each.  ``record``: ``passes`` (the pass that committed each generated
    position, then each of the last block's dropped ones; -1: committed
    before the block's first pass), ``confidence`` (the program's
    log-probability of each such token in that pass; None for -1) and
    ``dropped`` (the tokens past ``max_new_tokens`` of the last block).
    -> ``tokens`` (the committed sequence, whole blocks), ``starts``
    ``[N]``, ``inputs`` ``[N, B]`` (the mask where a position was not
    committed yet), ``chosen`` ``[N, B]`` (the positions the pass
    committed), ``open`` ``[N, B]`` (the positions it left masked),
    ``conf`` ``[N, B]`` (the program's confidence where ``chosen``, else
    0)."""
    b, mask = cfg["block_length"], cfg["mask_token_id"]
    seq = list(prompt) + list(generated) + list(record["dropped"])
    when = np.array([-1] * len(prompt) + list(record["passes"]))
    said = np.array([0.0] * len(prompt) + [
        0.0 if c is None else c for c in record["confidence"]], np.float32)
    if len(seq) % b or not len(when) == len(said) == len(seq):
        raise ValueError(f"{len(seq)} positions recorded, {len(when)} passes, "
                         f"{len(said)} confidences: not whole blocks of {b}")
    seq = np.asarray(seq, np.int64)
    starts, inputs, chosen, left = [], [], [], []
    for s in range(len(prompt) - len(prompt) % b, len(seq), b):
        w = when[s:s + b]
        for j in range(int(w.max()) + 1):
            starts.append(s)
            inputs.append(np.where(w < j, seq[s:s + b], mask))
            chosen.append(w == j)
            left.append(w > j)
    starts, chosen = np.asarray(starts, np.int32), np.asarray(chosen)
    conf = np.where(chosen, said[starts[:, None] + np.arange(b)], 0.0)
    return dict(tokens=seq, starts=starts, inputs=np.asarray(inputs, np.int32),
                chosen=chosen, open=np.asarray(left),
                conf=conf.astype(np.float32))


def _pad(n: int, unit: int) -> int:
    return max(unit, -(-n // unit) * unit)


def pass_logits(cfg: dict, seed: int, sample: list, precision: str = "fp32",
                drop: str = "", margins: bool = False):
    """Every recorded pass of every sampled ``(prompt, generated, record)``
    through the stack, layer-outer.  -> ([per request ``[N_r, B, V]``
    logits], and with ``margins`` [per request each pass position's least
    routing margin over the layers ``[N_r, B]``])."""
    b = cfg["block_length"]
    key, items = seed_key(seed), _items(cfg)
    reqs = [passes_of(cfg, *s) for s in sample]
    n_rows = _pad(max(len(r["starts"]) for r in reqs), ROW_CHUNK)
    t_ctx = _pad(max(len(r["tokens"]) for r in reqs), QUERY_BLOCK)
    ctx_tok = np.zeros((len(reqs), t_ctx), np.int32)
    ctx_live = np.zeros((len(reqs), t_ctx), bool)
    row_tok = np.zeros((len(reqs), n_rows, b), np.int32)
    row_live = np.zeros((len(reqs), n_rows, b), bool)
    starts = np.zeros((len(reqs), n_rows), np.int32)
    for i, r in enumerate(reqs):
        n, t = len(r["starts"]), len(r["tokens"])
        ctx_tok[i, :t], ctx_live[i, :t] = r["tokens"], True
        row_tok[i, :n], row_live[i, :n] = r["inputs"], True
        starts[i, :n] = r["starts"]
    top = layer_leaves(cfg, key, "top", 0)
    x = top["embed"][jnp.asarray(ctx_tok)]
    xr = top["embed"][jnp.asarray(row_tok)]
    least = jnp.full(row_tok.shape, jnp.inf, jnp.float32)
    starts = jnp.asarray(starts)
    for l in range(cfg["num_hidden_layers"]):
        p = layer_leaves(cfg, key, "attn", l)
        x, kc, vc = _context(p, x, cfg_items=items, precision=precision,
                             drop=drop)
        xr = _passes(p, xr, starts, kc, vc, cfg_items=items,
                     precision=precision, drop=drop)
        del p, kc, vc
        p = layer_leaves(cfg, key, "moe", l)
        h = jnp.concatenate([x.reshape(-1, x.shape[-1]),
                             xr.reshape(-1, xr.shape[-1])])
        live = np.concatenate([ctx_live.reshape(-1), row_live.reshape(-1)])
        h, margin = moe(cfg, p, h, live, precision, drop)
        x = h[:x.size // x.shape[-1]].reshape(x.shape)
        xr = h[x.size // x.shape[-1]:].reshape(xr.shape)
        least = jnp.minimum(least, margin[x.size // x.shape[-1]:]
                            .reshape(least.shape))
        del p, h
    out, edges = [], []
    for i, r in enumerate(reqs):
        n = len(r["starts"])
        out.append(jnp.concatenate([
            _logits(top, xr[i, c:c + ROW_CHUNK], eps=cfg["rms_norm_eps"],
                    precision=precision)
            for c in range(0, n, ROW_CHUNK)])[:n])
        edges.append(least[i, :n])
    return (out, edges) if margins else out


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(top, x, *, eps, precision):
    return _ein(precision)("nbd,dv->nbv", rms(x, top["norm_f"], eps),
                           top["head"])


@jax.jit
def _gaps(lg, tokens, conf, chosen, left, decided, order_margin):
    """One request's passes, over the positions where ``decided``: the sum
    and count of how far the confidence the program stated for each token
    it committed lies from this reference's log-probability of that token,
    and of how far the reference's top log-probability at a position the
    pass left masked lies above one it committed in the same pass (0 where
    below), pair by pair over the pairs whose two top log-probabilities
    differ by more than ``order_margin`` (nats)."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    logp = jnp.take_along_axis(lg, tokens[..., None], axis=-1)[..., 0] - lse
    mine = chosen & decided
    top = jnp.max(lg, axis=-1) - lse
    ahead = top[:, None, :] - top[:, :, None]
    pairs = (mine[:, :, None] & (left & decided)[:, None, :]
             & (jnp.abs(ahead) > order_margin))
    return (jnp.sum(jnp.where(mine, jnp.abs(conf - logp), 0.0)),
            jnp.sum(mine), jnp.sum(jnp.where(pairs, jnp.maximum(ahead, 0.0),
                                             0.0)),
            jnp.sum(pairs))


def _own_choice(lg, inputs_open, n_chosen):
    """What a pass of ``lg`` ``[N, B, V]`` would commit itself: of the
    masked positions, the ``n_chosen`` whose greedy token is the most
    probable (ties to the lower position); -> (tokens, chosen, each
    position's top log-probability)."""
    conf = np.asarray(jnp.max(lg, -1) - jax.nn.logsumexp(lg, axis=-1))
    best = np.asarray(jnp.argmax(lg, axis=-1))
    chosen = np.zeros(conf.shape, bool)
    for i in range(conf.shape[0]):
        order = sorted(np.flatnonzero(inputs_open[i]), key=lambda j: (-conf[i, j], j))
        chosen[i, order[:n_chosen[i]]] = True
    return best, chosen, conf


def served_gap(cfg: dict, seed: int, sample: list, control: bool = False,
               drop: str = "") -> dict:
    """Over the recorded passes of ``sample``, each in the state the
    program ran it in: -> ``logprob_gap``, the mean by which the
    confidence the program stated for a token it committed lies from this
    reference's log-probability of that token (a wrong token lies below by
    the reference's own margin for the best, a wrong number by its error),
    over ``positions`` committed positions; ``order_gap``, the mean by
    which a position a pass left masked is more probable to this reference
    than one it committed in the same pass, over ``pairs`` such pairs.
    Counted where this reference's least routing margin over the layers
    exceeds the configuration's ``check.routing_margin`` (a pair: at both
    of its positions, and only where the two positions' top
    log-probabilities differ by more than ``check.order_margin``: an order
    the reference itself decides).  ``control``: in place of the program's
    choices and confidences, those the fp8 control makes itself in the same
    recorded states.  ``drop``: the reference WITHOUT that term (the
    readings ``set_from`` quotes)."""
    logits, least = pass_logits(cfg, seed, sample, drop=drop, margins=True)
    lg8 = pass_logits(cfg, seed, sample, "fp8") if control else None
    margin, order = cfg["check"]["routing_margin"], cfg["check"]["order_margin"]
    sums = np.zeros(4)
    for i, s in enumerate(sample):
        r = passes_of(cfg, *s)
        served = np.asarray(r["tokens"])[r["starts"][:, None]
                                         + np.arange(cfg["block_length"])]
        chosen, left, conf = r["chosen"], r["open"], r["conf"]
        if control:
            served, chosen, conf = _own_choice(lg8[i], chosen | left,
                                               chosen.sum(-1))
            left = (r["chosen"] | r["open"]) & ~chosen
        sums += [float(x) for x in _gaps(
            logits[i], jnp.asarray(served, jnp.int32),
            jnp.asarray(conf, jnp.float32), jnp.asarray(chosen),
            jnp.asarray(left), least[i] > margin, order)]
    return dict(logprob_gap=sums[0] / max(sums[1], 1), positions=int(sums[1]),
                order_gap=sums[2] / max(sums[3], 1), pairs=int(sums[3]))


_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "num_experts", "num_experts_per_tok",
         "moe_intermediate_size", "rope_theta", "rms_norm_eps",
         "block_length")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS)
