"""Multi-head attention layers: full, flash-blockwise, and ring (seq-parallel).

Beyond-reference capability (the 2016 reference has no attention — SURVEY.md
§5), built on the same primitives as the exchanger: the ring variant
circulates KV blocks over the ``seq`` mesh axis with ``ppermute``
(:mod:`theanompi_tpu.parallel.ring_attention`).  Head projections are
tensor-parallel-ready: Q/K/V are column-parallel (heads shard over the
``model`` axis), the output projection is row-parallel.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import quant
from theanompi_tpu.parallel.mesh import SEQ_AXIS
from theanompi_tpu.parallel.ring_attention import blockwise_attention, ring_attention
from theanompi_tpu.parallel.tensor import (
    ColumnParallelDense,
    RowParallelDense,
    axis_bound,
    identity_fwd_psum_bwd,
)


def resolve_attn_impl(impl: str, t: int, head_dim: int) -> str:
    """The concrete path ``MultiHeadAttention.apply`` takes for an
    UNSHARDED seq axis: ``'pallas'`` or ``'blockwise'``.

    ``'auto'`` = pallas flash kernels on TPU when the shape gate admits
    them (elsewhere interpret mode would be pure slowdown).  Shared with
    the models' ``attention_impl`` report so the recorded path can't drift
    from the gate the model actually applies (code-review r5).
    """
    if impl == "auto":
        from theanompi_tpu.ops.pallas_attention import (
            flash_attention_supported,
        )

        return ("pallas"
                if jax.default_backend() == "tpu"
                and flash_attention_supported(t, head_dim)
                else "blockwise")
    return impl


@dataclasses.dataclass(frozen=True)
class MultiHeadAttention(L.Layer):
    """Causal/bidirectional MHA over ``[B, T, D]``.

    ``heads`` is the GLOBAL head count; under tensor parallelism each model
    shard holds ``heads / mesh['model']`` heads (the column-parallel Q/K/V
    slices are head-aligned because ``D % heads == 0`` weights shard on the
    feature dim).  When the ``seq`` axis is bound with size > 1, attention
    runs as a KV ring over the sequence shards.
    """

    dim: int
    heads: int
    causal: bool = True
    #: "auto" = pallas flash kernels on TPU when shapes allow — for both
    #: training and inference (measured: train step ~3.2x over the XLA
    #: blockwise path at T=2048, ~4.7x at T=8192, and T=16384 trains where
    #: XLA out-of-memories).  "pallas"/"blockwise" force one when the seq
    #: axis is NOT sharded; ring attention always wins under sequence
    #: parallelism.
    impl: str = "auto"

    def __post_init__(self):
        if self.impl not in ("auto", "pallas", "blockwise"):
            raise ValueError(
                f"MultiHeadAttention impl {self.impl!r} not in"
                " ('auto', 'pallas', 'blockwise')"
            )

    def _subs(self):
        # q/k/v share one input; apply() runs the Megatron ``f`` operator on
        # it once, so the projections skip their own (3x the backward
        # all-reduce traffic for the same — linear — result otherwise)
        w02 = init_lib.normal(0.02)
        return (
            ("q", ColumnParallelDense(self.dim, w_init=w02, input_synced=True)),
            ("k", ColumnParallelDense(self.dim, w_init=w02, input_synced=True)),
            ("v", ColumnParallelDense(self.dim, w_init=w02, input_synced=True)),
            ("o", RowParallelDense(self.dim, w_init=w02)),
        )

    def init(self, key, in_shape):
        if in_shape[-1] != self.dim:
            raise ValueError(f"MHA dim {self.dim} != input {in_shape[-1]}")
        if self.dim % self.heads:
            raise ValueError(f"dim {self.dim} not divisible by {self.heads} heads")
        params = {}
        keys = jax.random.split(key, 4)
        for (name, layer), k in zip(self._subs(), keys):
            p, _, _ = layer.init(k, in_shape)
            params[name] = p
        return params, {}, tuple(in_shape)

    def project_qkv(self, params, x):
        """Fused QKV projection: ``[B, T, D] -> 3 x [B, T, h_local, Dh]``.

        The params stay three separate leaves (TP rules, checkpoints, tests
        address them unchanged) but the weights concatenate at apply time so
        x is read once, not three times — under TP each leaf is the local
        ``[D, D/tp]`` slice and the concat is the local slice of the fused
        projection (Megatron's layout).  Shared between training ``apply``
        and the serving prefill/decode paths (ISSUE 6), which write the
        k/v halves into the paged KV cache.
        """
        b, t, _ = x.shape
        head_dim = self.dim // self.heads
        ws = [params["q"]["w"], params["k"]["w"], params["v"]["w"]]
        if any(isinstance(w, quant.QuantizedTensor) for w in ws):
            # int8 serving weights can't concatenate; three fused-kernel
            # matmuls read x three times — decode is KV-DMA-bound, not
            # qkv-bound, so the fused int8 reads still win (ISSUE 18)
            qkv = jnp.concatenate(
                [quant.matmul_any(x, w) for w in ws], axis=-1)
            d_local = int(ws[0].shape[1])
        else:
            w_qkv = jnp.concatenate(ws, axis=1).astype(x.dtype)
            qkv = x @ w_qkv
            d_local = params["q"]["w"].shape[1]
        if "b" in params["q"]:
            qkv = qkv + jnp.concatenate(
                [params["q"]["b"], params["k"]["b"], params["v"]["b"]]
            ).astype(x.dtype)
        q = qkv[..., :d_local]
        k = qkv[..., d_local:2 * d_local]
        v = qkv[..., 2 * d_local:]
        # local head count falls out of the (possibly sharded) width
        h_local = q.shape[-1] // head_dim
        q = q.reshape(b, t, h_local, head_dim)
        k = k.reshape(b, t, h_local, head_dim)
        v = v.reshape(b, t, h_local, head_dim)
        return q, k, v

    def attend(self, q, k, v, window: int | None = None,
               block: int | None = None):
        """The attention core over ``[B, T, H, Dh]``: ring under a sharded
        seq axis, else the resolved pallas/blockwise path.  The serving
        prefill reuses exactly this dispatch (so a TPU prefill rides the
        flash kernels whenever the shape gate admits them).  ``window``
        (causal, forward only, never the ring): query ``i`` sees keys
        ``i - window < j <= i``.  ``block`` (the same terms): block-causal,
        query ``i`` sees keys ``j < (i // block + 1) * block``."""
        t, head_dim = q.shape[1], q.shape[3]
        if axis_bound(SEQ_AXIS) and jax.lax.axis_size(SEQ_AXIS) > 1:
            if window is not None or block is not None:
                raise NotImplementedError(
                    "a window or a block mask over a sharded seq axis")
            return ring_attention(q, k, v, causal=self.causal)
        from theanompi_tpu.ops.pallas_attention import flash_attention

        masks = {} if block is None else {"block": block}
        if resolve_attn_impl(self.impl, t, head_dim) == "pallas":
            return flash_attention(q, k, v, causal=self.causal, window=window,
                                   **masks)
        return blockwise_attention(q, k, v, causal=self.causal, window=window,
                                   **masks)

    def project_out(self, params, out):
        """Output projection over the flattened head dim ``[B, T, h*Dh]``."""
        subs = dict(self._subs())
        y, _ = subs["o"].apply(params["o"], {}, out)
        return y

    def apply(self, params, state, x, *, train=False, rng=None):
        b, t, _ = x.shape
        x = identity_fwd_psum_bwd(x)  # once for all three projections
        q, k, v = self.project_qkv(params, x)
        out = self.attend(q, k, v)
        out = out.reshape(b, t, q.shape[2] * q.shape[3])
        return self.project_out(params, out), state


def yarn_inv_freq(theta: float, rot: int, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's ``rot // 2`` inverse frequencies (Peng et al. 2023, as the
    ``transformers`` initialiser computes them): ``f_i = theta ** (-2 i /
    rot)`` where dim ``i`` makes more than ``beta_fast`` turns in
    ``original_max`` positions, ``f_i / factor`` where it makes fewer than
    ``beta_slow``, and between those two dims (the first floored, the
    second ceiled, both held to ``0..rot - 1``) a linear blend."""
    def turns_dim(turns):
        return (rot * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    half = rot // 2
    f = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    keep = 1.0 - jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                          / (high - low), 0.0, 1.0)
    return f / factor * (1.0 - keep) + f * keep


def rotary(q, k, positions, theta: float, share: float = 1.0,
           yarn: dict | None = None, interleave: bool = False):
    """Rotary position embedding, rotate-half pairing: ``q`` ``[..., T, H,
    Dh]`` and ``k`` ``[..., T, Hkv, Dh]`` at ``positions`` ``[..., T]`` ->
    the rotated pair in their own dtypes.  The leading ``rot = share * Dh``
    dims of a head are turned (dim ``i`` with ``i + rot // 2``), the rest
    pass as they are.  ``interleave``: adjacent pairs ``(2 i, 2 i + 1)``
    turn at frequency ``i`` instead, laid out as the ``transformers``
    interleaved rule leaves them — the even dims first, then the odd ones —
    so a query and a key turned alike meet as the pairs would.  Angles ``position * theta ** (-2 i / rot)``, or with
    ``yarn`` (``factor``, ``original_max_position``, ``beta_fast``,
    ``beta_slow``, ``attention_factor``) ``position *``
    :func:`yarn_inv_freq` with ``cos`` and ``sin`` multiplied by
    ``attention_factor`` (default ``0.1 ln factor + 1``); angles and the
    rotation itself are float32.  Applied before a cache write, so a K pool
    holds rotated keys and a decode step rotates its own token only."""
    rot = int(q.shape[-1] * share)
    half = rot // 2
    if yarn is None:
        freq, scale = theta ** (-jnp.arange(half, dtype=jnp.float32) / half), None
    else:
        freq = yarn_inv_freq(theta, rot, yarn["factor"],
                             yarn["original_max_position"],
                             yarn.get("beta_fast", 32.0),
                             yarn.get("beta_slow", 1.0))
        scale = yarn.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(yarn["factor"]) + 1.0
    ang = jnp.asarray(positions, jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale is not None:
        cos, sin = cos * scale, sin * scale

    def turn(x):
        xf = x.astype(jnp.float32)
        if interleave:
            xf = jnp.concatenate([xf[..., 0:rot:2], xf[..., 1:rot:2],
                                  xf[..., rot:]], axis=-1)
        a, b = xf[..., :half], xf[..., half:rot]
        rest = [xf[..., rot:]] if rot < x.shape[-1] else []
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, *rest],
                               axis=-1).astype(x.dtype)

    return turn(q), turn(k)


@dataclasses.dataclass(frozen=True)
class GroupedQueryAttention(L.Layer):
    """Causal attention whose ``kv_heads`` K/V heads are each shared by
    ``heads // kv_heads`` query heads (query head ``h`` reads K/V head
    ``h // (heads // kv_heads)``); head size ``head_dim`` independent of
    ``dim``, no bias.  ``rope_theta=None``: no positional term;
    a number: :func:`rotary` positions at that base over ``rope_share`` of
    the head, with ``rope_yarn`` its YaRN parameters, for which
    :meth:`project_qkv` is told the tokens' ``positions``.  ``window``:
    position ``i`` attends ``j`` with ``0 <= i - j < window`` (None: every
    ``j <= i``).  ``gate``: a sigmoid gate a head, ``sigmoid(x W_g)`` read
    from the layer's input, on the context before the output projection
    (:meth:`gated`).  ``qk_norm``: each query and key head RMS-normed over
    its ``head_dim`` at ``norm_eps`` with a learned scale of its own (one
    for the queries, one for the keys), before rotary (the Qwen3 block's
    norm).  A cache holds the ``kv_heads`` only; :meth:`attend`
    repeats them to the query heads and takes
    :meth:`MultiHeadAttention.attend`'s dispatch, so a TPU prefill rides
    the flash kernel where its gate admits the shape."""

    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    impl: str = "auto"
    rope_theta: float | None = None
    #: q, k and v as ONE product over the concatenated weights.  False:
    #: three products, fenced from the reshape to heads — what a stack
    #: inside a loop of the program takes.  There XLA hoists whatever is
    #: loop-invariant out of the loop and holds it for every layer at once:
    #: the concatenations, or, unfenced, a second layout of each weight
    #: that a product folded with its reshape wants (1.2 GB either way for
    #: 48 layers of 2048, compiled for a v5e, ISSUE 31)
    fused_qkv: bool = True
    rope_share: float = 1.0
    rope_yarn: dict | None = None
    window: int | None = None
    gate: bool = False
    qk_norm: bool = False
    norm_eps: float = 1e-6

    def init(self, key, in_shape):
        if in_shape[-1] != self.dim:
            raise ValueError(f"GQA dim {self.dim} != input {in_shape[-1]}")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads do not divide over "
                             f"{self.kv_heads} K/V heads")
        kq, kk, kv, ko = jax.random.split(key, 4)
        w02 = init_lib.normal(0.02)
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        params = {"q": {"w": w02(kq, (self.dim, hq))},
                  "k": {"w": w02(kk, (self.dim, hkv))},
                  "v": {"w": w02(kv, (self.dim, hkv))},
                  "o": {"w": w02(ko, (hq, self.dim))}}
        if self.gate:
            params["gate"] = {"w": w02(jax.random.fold_in(key, 4),
                                       (self.dim, self.heads))}
        if self.qk_norm:
            for name in ("q_norm", "k_norm"):
                params[name] = L.RMSNorm().init(None, (self.head_dim,))[0]
        return params, {}, tuple(in_shape)

    def project_qkv(self, params, x, positions=None):
        """``[B, T, D]`` -> q ``[B, T, H, Dh]``, k, v ``[B, T, Hkv, Dh]``;
        with a ``rope_theta``, q and k rotated to ``positions`` ``[B, T]``."""
        b, t, _ = x.shape
        hq = self.heads * self.head_dim
        hkv = self.kv_heads * self.head_dim
        if self.fused_qkv:
            w = jnp.concatenate([params[n]["w"] for n in "qkv"], axis=1)
            qkv = x @ w.astype(x.dtype)
            cuts = (0, hq, hq + hkv, hq + 2 * hkv)
            q, k, v = (qkv[..., a:z].reshape(b, t, -1, self.head_dim)
                       for a, z in zip(cuts, cuts[1:]))
        else:
            q, k, v = (y.reshape(b, t, -1, self.head_dim)
                       for y in jax.lax.optimization_barrier(tuple(
                           x @ params[n]["w"].astype(x.dtype) for n in "qkv")))
        if self.qk_norm:
            norm = L.RMSNorm(eps=self.norm_eps)
            q = norm.apply(params["q_norm"], {}, q)[0]
            k = norm.apply(params["k_norm"], {}, k)[0]
        if self.rope_theta is not None:
            q, k = rotary(q, k, positions, float(self.rope_theta),
                          self.rope_share, self.rope_yarn)
        return q, k, v

    def attend(self, q, k, v, block: int | None = None):
        """Causal (or banded) attention over a whole sequence; ``block``:
        block-causal instead, every key of the query's own block of
        ``block`` positions and of every earlier block."""
        rep = self.heads // self.kv_heads
        core = MultiHeadAttention(self.heads * self.head_dim, self.heads,
                                  impl=self.impl)
        return core.attend(q, jnp.repeat(k, rep, axis=2),
                           jnp.repeat(v, rep, axis=2), window=self.window,
                           block=block)

    def gated(self, params, x, ctx):
        """``ctx`` ``[..., H, Dh]`` with head ``h`` multiplied by ``sigmoid(x
        W_g)_h`` (float32), ``x`` ``[..., D]`` the layer's input; ``ctx``
        itself without a ``gate``."""
        if not self.gate:
            return ctx
        g = jax.nn.sigmoid(
            (x @ params["gate"]["w"].astype(x.dtype)).astype(jnp.float32))
        return (ctx.astype(jnp.float32) * g[..., None]).astype(ctx.dtype)

    def project_out(self, params, out):
        return out @ params["o"]["w"].astype(out.dtype)


@dataclasses.dataclass(frozen=True)
class LatentAttention(L.Layer):
    """Multi-head latent attention (DeepSeek-V2's MLA), no bias.

    Queries through a rank-``q_lora`` bottleneck with its own RMSNorm:
    ``q = RMSNorm(x W_qa) W_qb``, ``heads`` heads of ``qk_nope + qk_rope``
    (``q_nope``, then ``q_pe``).  Keys and values through ONE latent row a
    token: ``[c_kv | k_pe] = x W_kva``, ``c_kv`` (``kv_lora`` wide)
    RMS-normed, ``k_pe`` (``qk_rope``) a rotary key shared by every head.
    Decompressed, ``[k_nope | v] = c_kv W_kvb`` a head (``qk_nope`` +
    ``v_head``), and head ``h`` scores ``(q_nope . k_nope + q_pe . k_pe) /
    sqrt(qk_nope + qk_rope)``; the ``heads`` contexts of ``v_head`` go
    through ``W_o``.  Rotary over the ``qk_rope`` dims at ``rope_theta``,
    adjacent pairs with ``rope_interleave`` (:func:`rotary`).

    A cache holds the row ``[c_kv | k_pe]`` alone, :attr:`latent` numbers a
    token, read by every head.  Two routes to the same context: a prompt's
    is decompressed (:meth:`attend`: keys and values of every head inside
    the program, none of them cached); a decode step's is absorbed — ``q_lat
    = q_nope W_UK^T`` (:meth:`absorb`) scores the row itself, ``o_lat = sum
    p c_kv`` is the context in the latent space, and ``o_lat W_UV``
    (:meth:`expand`) is the head's.  ``W_UK`` and ``W_UV`` are the two
    halves of each head's columns of ``W_kvb``, views of the one leaf."""

    dim: int
    heads: int
    q_lora: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_head: int
    rope_theta: float = 1e4
    rope_interleave: bool = False
    norm_eps: float = 1e-6
    impl: str = "auto"

    @property
    def latent(self) -> int:
        """Numbers of the cached row a token: ``c_kv``, then ``k_pe``."""
        return self.kv_lora + self.qk_rope

    @property
    def scale(self) -> float:
        return (self.qk_nope + self.qk_rope) ** -0.5

    def init(self, key, in_shape):
        if in_shape[-1] != self.dim:
            raise ValueError(f"MLA dim {self.dim} != input {in_shape[-1]}")
        ks = jax.random.split(key, 5)
        w02 = init_lib.normal(0.02)
        h = self.heads
        norm = L.RMSNorm()
        params = {
            "q_a": {"w": w02(ks[0], (self.dim, self.q_lora))},
            "q_norm": norm.init(None, (self.q_lora,))[0],
            "q_b": {"w": w02(ks[1], (self.q_lora,
                                     h * (self.qk_nope + self.qk_rope)))},
            "kv_a": {"w": w02(ks[2], (self.dim, self.latent))},
            "kv_norm": norm.init(None, (self.kv_lora,))[0],
            "kv_b": {"w": w02(ks[3], (self.kv_lora,
                                      h * (self.qk_nope + self.v_head)))},
            "o": {"w": w02(ks[4], (h * self.v_head, self.dim))}}
        return params, {}, tuple(in_shape)

    def project_latent(self, params, x, positions):
        """``x`` ``[..., T, D]`` at ``positions`` ``[..., T]`` -> (the
        cached row ``[..., T, kv_lora + qk_rope]``: ``c_kv`` normed, ``k_pe``
        rotated; ``q_nope`` ``[..., T, H, qk_nope]``; ``q_pe`` ``[..., T,
        H, qk_rope]`` rotated)."""
        norm = L.RMSNorm(eps=self.norm_eps)
        c_q = norm.apply(params["q_norm"], {},
                         x @ params["q_a"]["w"].astype(x.dtype))[0]
        q = (c_q @ params["q_b"]["w"].astype(x.dtype)).reshape(
            *x.shape[:-1], self.heads, self.qk_nope + self.qk_rope)
        kv = x @ params["kv_a"]["w"].astype(x.dtype)
        c_kv = norm.apply(params["kv_norm"], {}, kv[..., :self.kv_lora])[0]
        q_pe, k_pe = rotary(q[..., self.qk_nope:],
                            kv[..., None, self.kv_lora:], positions,
                            float(self.rope_theta),
                            interleave=self.rope_interleave)
        return (jnp.concatenate([c_kv, k_pe[..., 0, :]], axis=-1),
                q[..., :self.qk_nope], q_pe)

    def _kv_b(self, params, dtype):
        """``W_kvb`` as ``[kv_lora, H, qk_nope + v_head]``: ``[..., :qk_nope]``
        is ``W_UK``, the rest ``W_UV``."""
        return params["kv_b"]["w"].astype(dtype).reshape(
            self.kv_lora, self.heads, self.qk_nope + self.v_head)

    def attend(self, params, row, q_nope, q_pe, block: int | None = None):
        """Causal attention over a whole sequence, decompressed: ``row``
        ``[B, T, latent]``, the queries ``[B, T, H, ...]`` -> context ``[B,
        T, H, v_head]``.  Keys of ``qk_nope + qk_rope`` and values of
        ``v_head`` a head go through :meth:`MultiHeadAttention.attend`'s
        dispatch (the flash kernel takes values narrower than the keys)."""
        with jax.named_scope("attn.latent.decompress"):
            kv = jnp.einsum("btc,chn->bthn", row[..., :self.kv_lora],
                            self._kv_b(params, row.dtype))
            k_pe = jnp.broadcast_to(row[..., None, self.kv_lora:],
                                    (*row.shape[:-1], self.heads,
                                     self.qk_rope))
            k = jnp.concatenate([kv[..., :self.qk_nope], k_pe], axis=-1)
            v = kv[..., self.qk_nope:]
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        core = MultiHeadAttention(self.heads * q.shape[-1], self.heads,
                                  impl=self.impl)
        return core.attend(q, k, v, block=block)

    @jax.named_scope("attn.latent.absorb")
    def absorb(self, params, q_nope, q_pe):
        """Queries ``[..., H, ...]`` -> ``[..., H, latent]``: ``q_nope
        W_UK^T`` beside ``q_pe``, a query that scores the cached row."""
        w = self._kv_b(params, q_nope.dtype)[..., :self.qk_nope]
        q_lat = jnp.einsum("...hn,chn->...hc", q_nope, w)
        return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)

    @jax.named_scope("attn.latent.absorb")
    def expand(self, params, o_lat):
        """A context in the latent space ``[..., H, kv_lora]`` -> each
        head's ``[..., H, v_head]``: ``o_lat W_UV``."""
        w = self._kv_b(params, o_lat.dtype)[..., self.qk_nope:]
        return jnp.einsum("...hc,chv->...hv", o_lat, w)

    def project_out(self, params, out):
        return out @ params["o"]["w"].astype(out.dtype)


@dataclasses.dataclass(frozen=True)
class PositionEmbedding(L.Layer):
    """Learned absolute positions, offset-aware under sequence sharding."""

    max_len: int
    dim: int

    def init(self, key, in_shape):
        t = in_shape[0]
        if t > self.max_len:
            raise ValueError(f"seq len {t} > max_len {self.max_len}")
        params = {"pos": init_lib.normal(0.02)(key, (self.max_len, self.dim))}
        return params, {}, tuple(in_shape)

    @jax.named_scope("embed")
    def apply(self, params, state, x, *, train=False, rng=None):
        t = x.shape[1]
        start = 0
        if axis_bound(SEQ_AXIS):
            # global position of this shard's first token
            start = jax.lax.axis_index(SEQ_AXIS) * t
        pos = jax.lax.dynamic_slice_in_dim(params["pos"], start, t).astype(x.dtype)
        return x + pos[None], state
