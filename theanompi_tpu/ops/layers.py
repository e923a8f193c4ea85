"""Neural-net layer library: shape-inferred functional layers.

Reference (unverified — SURVEY.md §2.1): ``theanompi/models/layers2.py`` —
``Conv``/``Pool``/``FC``/``Dropout``/``Softmax``/``BN`` over Theano's cuDNN
bindings plus a ``Weight`` init/save class.  The TPU rebuild makes each layer
a pair of pure functions:

- ``init(key, in_shape) -> (params, state, out_shape)`` — shape-inferred, so
  models never hand-thread channel counts (the reference passed explicit
  ``input_shape`` tuples through every layer);
- ``apply(params, state, x, *, train, rng) -> (y, new_state)`` — traced under
  ``jit``; ``state`` carries non-learned buffers (BN running stats).

Conventions (TPU-first, deliberately not the reference's GPU-isms):

- activations are NHWC (XLA's preferred TPU conv layout; reference was bc01),
  conv kernels HWIO;
- ``in_shape``/``out_shape`` are per-example (no batch dim); ``apply`` takes
  batched arrays;
- params are created fp32; ``apply`` computes in ``x.dtype``, so the caller's
  precision policy (cast inputs+params to bf16) decides MXU precision;
- BatchNorm statistics are always fp32 and can be reduced across the ``data``
  mesh axis (sync-BN) by passing ``axis_name`` — the cross-replica analogue
  the reference never had (its BN was per-GPU).
"""

from __future__ import annotations

import dataclasses
from dataclasses import field
from typing import Any, Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import quant

Shape = tuple


class Layer:
    """Base layer: stateless identity. Subclasses are frozen dataclasses."""

    def init(self, key, in_shape: Shape):
        del key
        return {}, {}, tuple(in_shape)

    def apply(self, params, state, x, *, train: bool = False, rng=None):
        del params, train, rng
        return x, state

    @property
    def name(self) -> str:
        return type(self).__name__.lower()


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


ACTIVATIONS: dict[str, Callable] = {
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "leaky_relu": lambda x: jax.nn.leaky_relu(x, 0.2),
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "gelu": jax.nn.gelu,
    "elu": jax.nn.elu,
    "identity": lambda x: x,
    # squared ReLU (the Nemotron-H family's ungated expert activation)
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


@dataclasses.dataclass(frozen=True)
class Activation(Layer):
    kind: str = "relu"

    def apply(self, params, state, x, *, train=False, rng=None):
        return ACTIVATIONS[self.kind](x), state


@dataclasses.dataclass(frozen=True)
class Dense(Layer):
    """Fully-connected layer (reference ``FC``). Acts on the trailing dim."""

    units: int
    use_bias: bool = True
    w_init: Callable = init_lib.he_normal
    b_init: Callable = init_lib.zeros

    def init(self, key, in_shape):
        d = in_shape[-1]
        kw, kb = jax.random.split(key)
        params = {"w": self.w_init(kw, (d, self.units))}
        if self.use_bias:
            params["b"] = self.b_init(kb, (self.units,))
        return params, {}, (*in_shape[:-1], self.units)

    def apply(self, params, state, x, *, train=False, rng=None):
        # matmul_any: identical to ``x @ w`` for array weights; the
        # serving fast path leaves int8 QuantizedTensor weights in the
        # tree and this dispatch consumes them fused (ISSUE 18)
        y = quant.matmul_any(x, params["w"])
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state


@dataclasses.dataclass(frozen=True)
class Conv2D(Layer):
    """2-D convolution, NHWC/HWIO (reference ``Conv`` on cuDNN ``dnn_conv``)."""

    filters: int
    kernel: Any = 3
    stride: Any = 1
    padding: Any = "SAME"  # 'SAME' | 'VALID' | int | ((ph0,ph1),(pw0,pw1))
    dilation: Any = 1
    groups: int = 1
    use_bias: bool = True
    w_init: Callable = init_lib.he_normal
    b_init: Callable = init_lib.zeros

    def _padding(self):
        if isinstance(self.padding, str):
            return self.padding
        if isinstance(self.padding, int):
            p = self.padding
            return ((p, p), (p, p))
        return tuple(tuple(p) for p in self.padding)

    def init(self, key, in_shape):
        h, w, c = in_shape
        kh, kw_ = _pair(self.kernel)
        kkey, bkey = jax.random.split(key)
        params = {
            "w": self.w_init(kkey, (kh, kw_, c // self.groups, self.filters))
        }
        if self.use_bias:
            params["b"] = self.b_init(bkey, (self.filters,))
        out = jax.eval_shape(
            lambda x: self._conv(x, params["w"]),
            jax.ShapeDtypeStruct((1, h, w, c), jnp.float32),
        )
        return params, {}, tuple(out.shape[1:])

    def _conv(self, x, w):
        return lax.conv_general_dilated(
            x,
            w.astype(x.dtype),
            window_strides=_pair(self.stride),
            padding=self._padding(),
            rhs_dilation=_pair(self.dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        )

    def apply(self, params, state, x, *, train=False, rng=None):
        y = self._conv(x, params["w"])
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state


@dataclasses.dataclass(frozen=True)
class ConvTranspose2D(Layer):
    """Transposed conv (DCGAN generator upsampling)."""

    filters: int
    kernel: Any = 4
    stride: Any = 2
    padding: Any = "SAME"
    use_bias: bool = True
    w_init: Callable = init_lib.he_normal
    b_init: Callable = init_lib.zeros

    def init(self, key, in_shape):
        h, w, c = in_shape
        kh, kw_ = _pair(self.kernel)
        kkey, bkey = jax.random.split(key)
        params = {"w": self.w_init(kkey, (kh, kw_, c, self.filters))}
        if self.use_bias:
            params["b"] = self.b_init(bkey, (self.filters,))
        out = jax.eval_shape(
            lambda x: self._conv(x, params["w"]),
            jax.ShapeDtypeStruct((1, h, w, c), jnp.float32),
        )
        return params, {}, tuple(out.shape[1:])

    def _conv(self, x, w):
        return lax.conv_transpose(
            x,
            w.astype(x.dtype),
            strides=_pair(self.stride),
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def apply(self, params, state, x, *, train=False, rng=None):
        y = self._conv(x, params["w"])
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state


@dataclasses.dataclass(frozen=True)
class _Pool(Layer):
    window: Any = 2
    stride: Any = None
    padding: Any = "VALID"

    def _dims(self):
        wh, ww = _pair(self.window)
        sh, sw = _pair(self.stride if self.stride is not None else self.window)
        return (1, wh, ww, 1), (1, sh, sw, 1)

    def _padding(self, window):
        if isinstance(self.padding, str):
            return self.padding
        p = _pair(self.padding)
        return ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))

    def init(self, key, in_shape):
        del key
        h, w, c = in_shape
        window, stride = self._dims()
        out = jax.eval_shape(
            lambda x: self._reduce(x),
            jax.ShapeDtypeStruct((1, h, w, c), jnp.float32),
        )
        return {}, {}, tuple(out.shape[1:])

    def apply(self, params, state, x, *, train=False, rng=None):
        return self._reduce(x), state


@dataclasses.dataclass(frozen=True)
class MaxPool(_Pool):
    def _reduce(self, x):
        window, stride = self._dims()
        return lax.reduce_window(
            x, -jnp.inf, lax.max, window, stride, self._padding(window)
        )


@dataclasses.dataclass(frozen=True)
class AvgPool(_Pool):
    def _reduce(self, x):
        window, stride = self._dims()
        summed = lax.reduce_window(
            x, 0.0, lax.add, window, stride, self._padding(window)
        )
        if isinstance(self.padding, str) and self.padding == "SAME":
            counts = lax.reduce_window(
                jnp.ones_like(x), 0.0, lax.add, window, stride, "SAME"
            )
            return summed / counts
        return summed / float(np.prod(_pair(self.window)))


@dataclasses.dataclass(frozen=True)
class GlobalAvgPool(Layer):
    def init(self, key, in_shape):
        del key
        return {}, {}, (in_shape[-1],)

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), state


@dataclasses.dataclass(frozen=True)
class Flatten(Layer):
    def init(self, key, in_shape):
        del key
        return {}, {}, (int(np.prod(in_shape)),)

    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@dataclasses.dataclass(frozen=True)
class Dropout(Layer):
    rate: float = 0.5

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout needs an rng key when train=True")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class BatchNorm(Layer):
    """Batch normalization with optional cross-replica (sync) statistics.

    ``axis_name`` set → batch stats are psum-averaged over that mesh axis
    inside the train step, giving global-batch statistics under data
    parallelism (the reference's per-GPU BN divergence problem, solved the
    SPMD way).  Running stats live in ``state`` in fp32.

    Precision split (measured on TPU, not guessed): statistics are
    accumulated in fp32 (the reductions convert inline — no fp32 copy of
    ``x`` is materialized), but the per-element normalize runs in the input
    dtype as ``x·inv + shift`` with the two fp32 [C] vectors folded on the
    host side of the broadcast.  Upcasting the whole activation to fp32
    for the normalize doubled the step's HBM traffic share around every BN
    — a ResNet-50/256 train step is bandwidth-bound, and this change alone
    was worth ~8% throughput (86.2→77.8 GB accessed/step).
    """

    momentum: float = 0.9
    eps: float = 1e-5
    axis_name: str | None = None
    scale_init: Callable = init_lib.ones
    bias_init: Callable = init_lib.zeros

    def init(self, key, in_shape):
        c = in_shape[-1]
        ks, kb = jax.random.split(key)
        params = {"scale": self.scale_init(ks, (c,)), "bias": self.bias_init(kb, (c,))}
        state = {
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32),
        }
        return params, state, tuple(in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean_sq = lax.pmean(mean_sq, self.axis_name)
            # clamp: E[x^2]-E[x]^2 cancellation can go (slightly) negative in
            # fp32 for large-mean activations, and rsqrt(negative+eps) is NaN
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps) * params["scale"].astype(jnp.float32)
        shift = params["bias"].astype(jnp.float32) - mean * inv
        y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
        return y, new_state


@dataclasses.dataclass(frozen=True)
class LayerNorm(Layer):
    """Layer normalization over the trailing dim (transformer/LSTM stacks)."""

    eps: float = 1e-6

    def init(self, key, in_shape):
        c = in_shape[-1]
        del key
        params = {"scale": jnp.ones((c,), jnp.float32),
                  "bias": jnp.zeros((c,), jnp.float32)}
        return params, {}, tuple(in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        # fp32 row stats (inline-converted reductions), input-dtype
        # elementwise — same bandwidth rationale as BatchNorm.apply
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        inv = lax.rsqrt(var + self.eps)
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        y = y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)
        return y, state


@dataclasses.dataclass(frozen=True)
class RMSNorm(Layer):
    """Root-mean-square normalization over the trailing dim, learned scale,
    no mean and no bias.  ``groups > 1`` normalizes each of ``groups`` equal
    slices of the trailing dim by its own mean square (Mamba-2's grouped
    norm); ``gated`` multiplies the input by ``silu(gate)`` first
    (``apply(..., gate=z)``), the order the Mamba-2 mixer uses."""

    eps: float = 1e-5
    groups: int = 1
    gated: bool = False

    def init(self, key, in_shape):
        del key
        if in_shape[-1] % self.groups:
            raise ValueError(f"RMSNorm: {in_shape[-1]} features do not "
                             f"divide into {self.groups} groups")
        return ({"scale": jnp.ones((in_shape[-1],), jnp.float32)}, {},
                tuple(in_shape))

    def apply(self, params, state, x, *, train=False, rng=None, gate=None):
        # fp32 statistics, input-dtype result (LayerNorm's discipline)
        xf = x.astype(jnp.float32)
        if self.gated:
            xf = xf * jax.nn.silu(gate.astype(jnp.float32))
        g = xf.reshape(*xf.shape[:-1], self.groups, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + self.eps)
        y = g.reshape(xf.shape) * params["scale"].astype(jnp.float32)
        return y.astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class GatedFFN(Layer):
    """Bias-free gated feed-forward over the trailing dim:
    ``(silu(x W_gate) * (x W_up)) W_down``, ``dim -> hidden -> dim``; the
    activation in float32, the products in ``x.dtype``."""

    hidden: int

    def init(self, key, in_shape):
        d = in_shape[-1]
        kg, ku, kd = jax.random.split(key, 3)
        w02 = init_lib.normal(0.02)
        params = {"gate": {"w": w02(kg, (d, self.hidden))},
                  "up": {"w": w02(ku, (d, self.hidden))},
                  "down": {"w": w02(kd, (self.hidden, d))}}
        return params, {}, tuple(in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        g = (x @ params["gate"]["w"].astype(x.dtype)).astype(jnp.float32)
        u = (x @ params["up"]["w"].astype(x.dtype)).astype(jnp.float32)
        y = (jax.nn.silu(g) * u).astype(x.dtype)
        return y @ params["down"]["w"].astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class LRN(Layer):
    """Across-channel local response normalization (AlexNet/GoogLeNet).

    The reference used cuDNN LRN; XLA has no LRN HLO, so it is expressed as a
    windowed sum over the channel axis — elementwise ops XLA fuses into the
    surrounding graph.
    """

    size: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    k: float = 2.0

    def apply(self, params, state, x, *, train=False, rng=None):
        xf = x.astype(jnp.float32)
        sq = jnp.square(xf)
        half = self.size // 2
        window = lax.reduce_window(
            sq, 0.0, lax.add,
            (1,) * (x.ndim - 1) + (self.size,),
            (1,) * x.ndim,
            [(0, 0)] * (x.ndim - 1) + [(half, half)],
        )
        y = xf / jnp.power(self.k + (self.alpha / self.size) * window, self.beta)
        return y.astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class Embedding(Layer):
    """Token embedding (PTB LSTM front end)."""

    vocab: int
    dim: int
    w_init: Callable = init_lib.uniform(0.1)

    def init(self, key, in_shape):
        params = {"w": self.w_init(key, (self.vocab, self.dim))}
        return params, {}, (*in_shape, self.dim)

    @jax.named_scope("embed")
    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.take(params["w"], x, axis=0), state


@dataclasses.dataclass(frozen=True)
class LSTM(Layer):
    """Single-layer LSTM over [B, T, D] → [B, T, H] via ``lax.scan``.

    Reference (unverified): ``theanompi/models/lstm.py`` PTB LM used Theano
    ``scan`` BPTT; ``lax.scan`` is its compiled, statically-unrollable
    equivalent — required under jit (no Python loops over time).
    """

    hidden: int
    w_init: Callable = init_lib.glorot_uniform
    r_init: Callable = init_lib.orthogonal()

    def init(self, key, in_shape):
        t, d = in_shape
        kx, kh = jax.random.split(key)
        params = {
            "wx": self.w_init(kx, (d, 4 * self.hidden)),
            "wh": self.r_init(kh, (self.hidden, 4 * self.hidden)),
            "b": jnp.zeros((4 * self.hidden,), jnp.float32),
        }
        return params, {}, (t, self.hidden)

    def apply(self, params, state, x, *, train=False, rng=None):
        b_sz = x.shape[0]
        h0 = jnp.zeros((b_sz, self.hidden), x.dtype)
        c0 = jnp.zeros((b_sz, self.hidden), x.dtype)
        wx = params["wx"].astype(x.dtype)
        wh = params["wh"].astype(x.dtype)
        bias = params["b"].astype(x.dtype)
        # Hoist the input projection out of the scan: one [B*T, D]x[D, 4H]
        # matmul keeps the MXU busy instead of T small ones.
        xproj = x @ wx + bias

        def step(carry, xt):
            h, c = carry
            gates = xt + h @ wh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        (_, _), hs = lax.scan(step, (h0, c0), jnp.swapaxes(xproj, 0, 1))
        return jnp.swapaxes(hs, 0, 1), state


@dataclasses.dataclass(frozen=True)
class Sequential(Layer):
    """Composes layers; threads params/state/rng; infers shapes once."""

    layers: Sequence[Layer] = field(default_factory=tuple)

    def init(self, key, in_shape):
        params, state = {}, {}
        shape = tuple(in_shape)
        keys = jax.random.split(key, max(len(self.layers), 1))
        for i, (layer, k) in enumerate(zip(self.layers, keys)):
            p, s, shape = layer.init(k, shape)
            name = f"{i:02d}_{layer.name}"
            if p:
                params[name] = p
            if s:
                state[name] = s
        return params, state, shape

    def apply(self, params, state, x, *, train=False, rng=None):
        new_state = dict(state)
        rngs = (
            jax.random.split(rng, max(len(self.layers), 1))
            if rng is not None
            else [None] * len(self.layers)
        )
        for i, layer in enumerate(self.layers):
            name = f"{i:02d}_{layer.name}"
            x, s = layer.apply(
                params.get(name, {}), state.get(name, {}), x,
                train=train, rng=rngs[i],
            )
            if s:
                new_state[name] = s
        return x, new_state
