"""Seeded weights: every leaf is a pure function of (seed, leaf name, layer).

The benchmark owns the weights.  The program is handed them as its
parameter tree (``program_tree``) and the plain reference regenerates the
same leaves on its own (``stacked_blocks`` / ``layer_leaves``), so the
comparison that decides ``correct`` takes nothing the program has made.

Initialisation (``assumed``, the GPT-2 recipe the Cerebras-GPT paper
follows): matrices N(0, 0.02), output projections N(0, 0.02 / sqrt(2 L)),
LayerNorm scales 1 + N(0, 0.02).  Biases get N(0, 0.02) instead of zeros
so that a dropped bias is visible to the comparison.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: (name, shape as a function of the config, kind)
BLOCK_LEAVES = (
    ("ln1_g", lambda c: (c["n_embd"],), "scale"),
    ("ln1_b", lambda c: (c["n_embd"],), "bias"),
    ("wq", lambda c: (c["n_embd"], c["n_embd"]), "matrix"),
    ("bq", lambda c: (c["n_embd"],), "bias"),
    ("wk", lambda c: (c["n_embd"], c["n_embd"]), "matrix"),
    ("bk", lambda c: (c["n_embd"],), "bias"),
    ("wv", lambda c: (c["n_embd"], c["n_embd"]), "matrix"),
    ("bv", lambda c: (c["n_embd"],), "bias"),
    ("wo", lambda c: (c["n_embd"], c["n_embd"]), "out_matrix"),
    ("bo", lambda c: (c["n_embd"],), "bias"),
    ("ln2_g", lambda c: (c["n_embd"],), "scale"),
    ("ln2_b", lambda c: (c["n_embd"],), "bias"),
    ("w_up", lambda c: (c["n_embd"], c["n_inner"]), "matrix"),
    ("b_up", lambda c: (c["n_inner"],), "bias"),
    ("w_down", lambda c: (c["n_inner"], c["n_embd"]), "out_matrix"),
    ("b_down", lambda c: (c["n_embd"],), "bias"),
)
TOP_LEAVES = (
    ("wte", lambda c: (c["vocab_size"], c["n_embd"]), "matrix"),
    ("wpe", lambda c: (c["n_positions"], c["n_embd"]), "matrix"),
    ("lnf_g", lambda c: (c["n_embd"],), "scale"),
    ("lnf_b", lambda c: (c["n_embd"],), "bias"),
    ("head_w", lambda c: (c["n_embd"], c["vocab_size"]), "matrix"),
    ("head_b", lambda c: (c["vocab_size"],), "bias"),
)
_NAMES = [n for n, _, _ in TOP_LEAVES + BLOCK_LEAVES]
_KINDS = {n: k for n, _, k in TOP_LEAVES + BLOCK_LEAVES}
_SHAPES = {n: s for n, s, _ in TOP_LEAVES + BLOCK_LEAVES}


def seed_key(seed: int):
    """``--seed`` may exceed 32 signed bits: fold both halves in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def leaf(cfg: dict, key, name: str, layer) -> jax.Array:
    """One fp32 leaf.  ``layer`` may be traced (inside a scan or vmap)."""
    k = jax.random.fold_in(jax.random.fold_in(key, _NAMES.index(name)), layer)
    noise = 0.02 * jax.random.normal(k, _SHAPES[name](cfg), jnp.float32)
    kind = _KINDS[name]
    if kind == "scale":
        return 1.0 + noise
    if kind == "out_matrix":
        return noise / math.sqrt(2.0 * cfg["n_layer"])
    return noise


def top_leaves(cfg: dict, key) -> dict:
    return {n: leaf(cfg, key, n, 0) for n, _, _ in TOP_LEAVES}


def layer_leaves(cfg: dict, key, layer) -> dict:
    return {n: leaf(cfg, key, n, layer) for n, _, _ in BLOCK_LEAVES}


def stacked_blocks(cfg: dict, key) -> dict:
    """Block leaves with a leading ``[n_layer]`` axis (the reference's scan)."""
    return jax.vmap(lambda i: layer_leaves(cfg, key, i))(jnp.arange(cfg["n_layer"]))


#: program path of a block leaf -> canonical name (TransformerLM's tree)
_PROGRAM_BLOCK = {
    ("ln1", "scale"): "ln1_g", ("ln1", "bias"): "ln1_b",
    ("attn", "q", "w"): "wq", ("attn", "q", "b"): "bq",
    ("attn", "k", "w"): "wk", ("attn", "k", "b"): "bk",
    ("attn", "v", "w"): "wv", ("attn", "v", "b"): "bv",
    ("attn", "o", "w"): "wo", ("attn", "o", "b"): "bo",
    ("ln2", "scale"): "ln2_g", ("ln2", "bias"): "ln2_b",
    ("up", "w"): "w_up", ("up", "b"): "b_up",
    ("down", "w"): "w_down", ("down", "b"): "b_down",
}


def canonical(path: tuple[str, ...]) -> tuple[str, int]:
    """Program tree path -> (canonical leaf name, layer).  The program's
    Sequential names its layers ``NN_kind``: embedding, position embedding,
    ``n_layer`` blocks, final LayerNorm; the head is a top-level key."""
    top, rest = path[0], tuple(path[1:])
    if top == "head":
        return ("head_w" if rest == ("w",) else "head_b"), 0
    kind = top.split("_", 1)[1]
    if kind == "embedding":
        return "wte", 0
    if kind == "positionembedding":
        return "wpe", 0
    if kind == "layernorm":
        return ("lnf_g" if rest == ("scale",) else "lnf_b"), 0
    if kind == "_block":
        return _PROGRAM_BLOCK[rest], int(top.split("_", 1)[0]) - 2
    raise KeyError(f"no canonical leaf for program path {path}")


def tree_paths(shapes) -> list[tuple[tuple[str, ...], object]]:
    return [(tuple(k.key for k in p), s)
            for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def program_tree(cfg: dict, key, shapes):
    """The program's parameter tree filled from ``key`` (``seed_key`` of the
    seed, passed as an argument so one compiled program serves every seed),
    every leaf made on the device inside the caller's jit.  ``shapes`` is
    the tree of shapes the program expects (``jax.eval_shape`` of its init)."""
    leaves = []
    for path, want in tree_paths(shapes):
        name, layer = canonical(path)
        x = leaf(cfg, key, name, layer)
        if tuple(x.shape) != tuple(want.shape):
            raise ValueError(f"{path}: benchmark leaf {name} has shape "
                             f"{x.shape}, the program expects {want.shape}")
        leaves.append(x.astype(want.dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes), leaves)


def seeded_params(model, cfg: dict, seed: int, out_sharding=None):
    """The program's fp32 parameter tree for ``--seed``, made on the device
    in one jitted call."""
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))[0]
    make = jax.jit(lambda key: program_tree(cfg, key, shapes),
                   out_shardings=out_sharding)
    return make(seed_key(seed))
