"""Optimizers: pytree-based SGD family + Adam/RMSProp.

Reference (unverified — SURVEY.md §2.1): ``theanompi/lib/opt.py`` built Theano
update lists — vanilla/momentum/Nesterov SGD with optional L2, and the
BSP-specific cumulative-gradient variants.  Here an optimizer is an immutable
object with ``init(params) -> opt_state`` and
``update(grads, opt_state, params, lr) -> (new_params, new_opt_state)``; both
are pure and run inside the compiled train step, so the whole update fuses
into the step's HLO.  ``lr`` is a traced scalar → epoch-wise LR schedules
(``adjust_hyperp``) never trigger recompilation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def _tmap(fn, *trees):
    return jax.tree.map(fn, *trees)


def _spec_axes(spec) -> tuple:
    """All mesh axes a PartitionSpec places dims on."""
    if spec is None:
        return ()
    axes = []
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            if a not in axes:
                axes.append(a)
    return tuple(axes)


def global_sq_norm(grads, param_specs=None):
    """Global squared L2 norm of a gradient pytree, sharding-aware.

    A leaf whose spec shards dims over mesh axes (``model`` under tensor
    parallelism, ``pipe`` under pipeline parallelism) holds only its
    shard's slice; its squared norm must be psummed over those axes to get
    the true global norm (replicated leaves are identical on every shard
    and must NOT be).  ``param_specs=None`` (or no bound sharding axes)
    degrades to the plain sum.
    """
    from theanompi_tpu.parallel.tensor import axis_bound

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if param_specs is None:
        return sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    spec_leaves = treedef.flatten_up_to(param_specs)
    # group per-leaf norms by the exact set of bound sharding axes, then
    # psum each group over its axes once
    groups: dict = {}
    for g, spec in zip(leaves, spec_leaves):
        axes = tuple(sorted(
            a for a in _spec_axes(spec)
            if axis_bound(a) and jax.lax.axis_size(a) > 1
        ))
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        groups[axes] = groups.get(axes, jnp.zeros((), jnp.float32)) + s
    total = jnp.zeros((), jnp.float32)
    for axes, s in groups.items():
        for a in axes:
            s = jax.lax.psum(s, a)
        total = total + s
    return total


@jax.named_scope("clip")
def clip_by_global_norm(grads, max_norm: float, param_specs=None):
    """Scale the whole gradient pytree so its global L2 norm <= max_norm
    (the tutorial-era LSTM BPTT stabilizer; reference lstm.py lineage).
    ``param_specs`` makes the norm exact under tensor parallelism
    (see :func:`global_sq_norm`)."""
    norm = jnp.sqrt(global_sq_norm(grads, param_specs))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return _tmap(lambda g: (g * scale).astype(g.dtype), grads)


def sharded_update(opt, grads, opt_state, params, lr, axis_name=None,
                   chain=None):
    """ZeRO-1 shard-local optimizer update (the exchanger's ``zero1`` entry
    point): same math as ``opt.update`` on the full tree, applied to the
    1/n shard each device owns of the flattened bucket buffers.

    Weight decay and every update rule here (SGD/momentum/Nesterov, Adam,
    RMSProp) are elementwise, so they shard transparently.  Gradient
    clipping's global norm is the one cross-shard quantity: the shards
    partition the gradient tree exactly (no element appears twice), so the
    psum of per-shard squared norms over ``axis_name`` IS the global norm.
    Clipping is applied here and then disabled on the inner optimizer so it
    is never double-applied.

    ``chain`` (overlapped exchange only) is ``(order, fence)``: ``order``
    lists bucket indices in scatter-arrival order and ``fence(buf, prev)``
    is the value-preserving dependency fence from
    :mod:`theanompi_tpu.parallel.overlap`.  Each *updated* shard is
    fenced on the previous arrival's updated shard, so buckets are
    released to the downstream all-gathers in arrival order — the
    shard-local updates consume buckets as they arrive instead of
    floating free of the collective schedule.  The fence sits on the
    OUTPUTS, never the update's inputs: because every update rule here is
    elementwise over the bucket list, bucket k's update already depends
    on nothing but its own scattered grads (arrival-ordered upstream by
    the exchanger), and fencing the inputs would reorganize the update's
    fusion clusters — different FMA contractions, a one-ulp drift, and a
    broken fused-vs-overlapped bit-equality lock (tests/test_overlap.py).
    With ``grad_clip`` set, the global-norm psum is an inherent
    all-bucket sync point; the chain still pins the release order.
    """
    if opt.grad_clip:
        with jax.named_scope("clip"):
            sq = global_sq_norm(grads)
            if axis_name is not None:
                axes = (axis_name if isinstance(axis_name, tuple)
                        else (axis_name,))
                for a in axes:
                    sq = jax.lax.psum(sq, a)
            norm = jnp.sqrt(sq)
            scale = jnp.minimum(1.0,
                                opt.grad_clip / jnp.maximum(norm, 1e-12))
            grads = _tmap(lambda g: (g * scale).astype(g.dtype), grads)
        opt = dataclasses.replace(opt, grad_clip=None)
    new_params, new_opt_state = opt.update(grads, opt_state, params, lr)
    if chain is not None:
        order, fence = chain
        new_params = list(new_params)
        prev = None
        for i in order:
            if prev is not None:
                new_params[i] = fence(new_params[i], prev)
            prev = new_params[i]
    return new_params, new_opt_state


class Optimizer:
    #: defaults for the _preprocess contract; subclasses carry the fields
    grad_clip: float | None = None
    weight_decay: float = 0.0

    def init(self, params):
        raise NotImplementedError

    def init_specs(self, param_specs):
        """PartitionSpecs mirroring ``init``'s structure (momenta shard like
        their params; counters replicate)."""
        raise NotImplementedError

    def update(self, grads, opt_state, params, lr, param_specs=None):
        raise NotImplementedError

    def _preprocess(self, grads, params, param_specs=None):
        if self.grad_clip:
            grads = clip_by_global_norm(grads, self.grad_clip, param_specs)
        if self.weight_decay:
            grads = _tmap(lambda g, p: g + self.weight_decay * p, grads, params)
        return grads


@dataclasses.dataclass(frozen=True)
class SGD(Optimizer):
    """Vanilla / momentum / Nesterov SGD with optional L2 weight decay.

    ``momentum=0`` → vanilla; ``nesterov=True`` matches the reference's
    Nesterov formulation (lookahead applied to the update, not the gradient).
    """

    momentum: float = 0.0
    nesterov: bool = False
    weight_decay: float = 0.0
    grad_clip: float | None = None

    def init(self, params):
        if self.momentum == 0.0:
            return {}
        return {"velocity": _tmap(jnp.zeros_like, params)}

    def init_specs(self, param_specs):
        if self.momentum == 0.0:
            return {}
        return {"velocity": param_specs}

    def update(self, grads, opt_state, params, lr, param_specs=None):
        grads = self._preprocess(grads, params, param_specs)
        if self.momentum == 0.0:
            new_params = _tmap(lambda p, g: p - lr * g, params, grads)
            return new_params, opt_state
        vel = _tmap(
            lambda v, g: self.momentum * v - lr * g, opt_state["velocity"], grads
        )
        if self.nesterov:
            step = _tmap(lambda v, g: self.momentum * v - lr * g, vel, grads)
        else:
            step = vel
        new_params = _tmap(lambda p, s: p + s, params, step)
        return new_params, {"velocity": vel}


@dataclasses.dataclass(frozen=True)
class Adam(Optimizer):
    """Adam (DCGAN per the original paper: lr=2e-4, b1=0.5)."""

    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float | None = None

    def init(self, params):
        return {
            "m": _tmap(jnp.zeros_like, params),
            "v": _tmap(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32),
        }

    def init_specs(self, param_specs):
        from jax.sharding import PartitionSpec as P

        return {"m": param_specs, "v": param_specs, "t": P()}

    def update(self, grads, opt_state, params, lr, param_specs=None):
        grads = self._preprocess(grads, params, param_specs)
        t = opt_state["t"] + 1
        m = _tmap(lambda m, g: self.b1 * m + (1 - self.b1) * g, opt_state["m"], grads)
        v = _tmap(
            lambda v, g: self.b2 * v + (1 - self.b2) * jnp.square(g),
            opt_state["v"], grads,
        )
        tf = t.astype(jnp.float32)
        scale = jnp.sqrt(1 - self.b2**tf) / (1 - self.b1**tf)
        new_params = _tmap(
            lambda p, m_, v_: p - lr * scale * m_ / (jnp.sqrt(v_) + self.eps),
            params, m, v,
        )
        return new_params, {"m": m, "v": v, "t": t}


@dataclasses.dataclass(frozen=True)
class RMSProp(Optimizer):
    """RMSProp (WGAN per the original paper: lr=5e-5)."""

    decay: float = 0.9
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float | None = None

    def init(self, params):
        return {"sq": _tmap(jnp.zeros_like, params)}

    def init_specs(self, param_specs):
        return {"sq": param_specs}

    def update(self, grads, opt_state, params, lr, param_specs=None):
        grads = self._preprocess(grads, params, param_specs)
        sq = _tmap(
            lambda s, g: self.decay * s + (1 - self.decay) * jnp.square(g),
            opt_state["sq"], grads,
        )
        new_params = _tmap(
            lambda p, g, s: p - lr * g / (jnp.sqrt(s) + self.eps), params, grads, sq
        )
        return new_params, {"sq": sq}
