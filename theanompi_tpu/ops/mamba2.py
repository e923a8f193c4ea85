"""Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs") for serving.

One layer, per token ``t`` of one sequence, ``u`` its (already normalized)
input::

    [z | xBC | dt] = W_in u                     widths d_inner | conv_dim | H
    xBC <- silu(conv1d_causal_depthwise(xBC, k) + b)
    x [H, P], B [G, N], C [G, N] = split(xBC)   head h reads group h // (H/G)
    dt <- softplus(dt + dt_bias) ;  A_h = -exp(A_log_h)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t      S is [P, N] per head
    y_t = S_t C_t + D_h x_t
    out = W_out RMSNorm_groups(y * silu(z))

with ``d_inner = H P`` and ``conv_dim = d_inner + 2 G N``.  The recurrent
state a server has to keep per sequence is ``S`` of every head (float32)
and the last ``k - 1`` inputs of the convolution.

Two evaluations of the same recurrence:

- :meth:`Mamba2.prefill`: a whole (end-padded) prompt by chunks of
  ``chunk`` positions — inside a chunk the quadratic "attention" form,
  between chunks the state carried by a ``lax.scan``.  A recurrence carries
  padding into its state, unlike causal attention, so positions at or past
  ``true_len`` get ``dt = 0`` (the state passes them unchanged) and the
  convolution state is read at ``true_len``.
- :meth:`Mamba2.decode`: one token for every slot of a batch, in place on
  the slots' states in a server's pools.

Plain ``jax.numpy`` / ``lax``, but for the decode step's state update,
which is memory-bound: on a TPU, for a state the gate admits, it is the
kernel of :mod:`theanompi_tpu.ops.pallas_state_update`
(:func:`resolve_state_update` chooses from platform and shape, as
``ops/attention.py::resolve_attn_impl`` does for flash attention).  The
state is float32; products take the input's dtype and accumulate in
float32.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops.pallas_state_update import (
    state_update,
    state_update_supported,
)

#: what :func:`pin_state_update` holds the resolver to, None outside one
_pinned: str | None = None


def resolve_state_update(head_dim: int, state: int, dtype) -> str:
    """What runs a decode step's state update: ``"kernel"`` (the compiled
    Pallas kernel: on a TPU, for a state the gate admits) or ``"plain"``
    (the ``jax.numpy`` lines: everywhere else); under
    :func:`pin_state_update`, what was pinned where the gate admits it."""
    if not state_update_supported(head_dim, state, dtype):
        return "plain"
    if _pinned is not None:
        return _pinned
    return "kernel" if jax.default_backend() == "tpu" else "plain"


@contextlib.contextmanager
def pin_state_update(impl: str):
    """Hold :func:`resolve_state_update` to ``impl`` — ``"kernel"``,
    ``"kernel_interpret"`` (the kernel through the Pallas interpreter) or
    ``"plain"`` — whatever the platform, while a program is traced.  For
    the HLO audit, which lowers for the chip from a CPU host, and for the
    tests; nothing a server sets."""
    global _pinned
    if impl not in ("kernel", "kernel_interpret", "plain"):
        raise ValueError(f"pin_state_update({impl!r})")
    before, _pinned = _pinned, impl
    try:
        yield
    finally:
        _pinned = before


@dataclasses.dataclass(frozen=True)
class Mamba2(L.Layer):
    dim: int
    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    def state_shapes(self) -> dict:
        """What one sequence keeps between steps: name -> (shape, dtype)."""
        return {"ssm": ((self.heads, self.head_dim, self.state), jnp.float32),
                "conv": ((self.conv_kernel - 1, self.conv_dim), jnp.bfloat16)}

    def init(self, key, in_shape):
        if in_shape[-1] != self.dim:
            raise ValueError(f"Mamba2 dim {self.dim} != input {in_shape[-1]}")
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads do not divide into "
                             f"{self.groups} groups")
        k_in, k_conv, k_dt, k_out = jax.random.split(key, 4)
        w02 = init_lib.normal(0.02)
        # dt_bias: inverse softplus of a step drawn log-uniform in
        # [1e-3, 1e-1] (the published initialisation); A in [1, 16]
        dt = jnp.exp(jax.random.uniform(k_dt, (self.heads,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        params = {
            "in_proj": {"w": w02(k_in, (self.dim, 2 * self.d_inner
                                        + 2 * self.groups * self.state
                                        + self.heads))},
            "conv": {"w": w02(k_conv, (self.conv_kernel, self.conv_dim)),
                     "b": jnp.zeros((self.conv_dim,), jnp.float32)},
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, self.heads)),
            "D": jnp.ones((self.heads,), jnp.float32),
            "norm": {"scale": jnp.ones((self.d_inner,), jnp.float32)},
            "out_proj": {"w": w02(k_out, (self.d_inner, self.dim))},
        }
        return params, {}, tuple(in_shape)

    # -- the pieces both evaluations share ------------------------------------
    def _project(self, params, u):
        zxbcdt = u @ params["in_proj"]["w"].astype(u.dtype)
        z = zxbcdt[..., :self.d_inner]
        xbc = zxbcdt[..., self.d_inner:self.d_inner + self.conv_dim]
        dt = zxbcdt[..., self.d_inner + self.conv_dim:]
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))
        return z, xbc, dt

    def _split(self, xbc):
        """Activated conv output ``[..., conv_dim]`` -> x ``[..., G, R, P]``,
        B, C ``[..., G, N]`` (``R`` heads share a group's B and C)."""
        g, n = self.groups, self.state
        lead = xbc.shape[:-1]
        x = xbc[..., :self.d_inner].reshape(*lead, g, self.heads // g,
                                            self.head_dim)
        b = xbc[..., self.d_inner:self.d_inner + g * n].reshape(*lead, g, n)
        c = xbc[..., self.d_inner + g * n:].reshape(*lead, g, n)
        return x, b, c

    def _finish(self, params, y, z):
        norm = L.RMSNorm(eps=self.eps, groups=self.groups, gated=True)
        y, _ = norm.apply(params["norm"], {}, y.astype(z.dtype), gate=z)
        return y @ params["out_proj"]["w"].astype(y.dtype)

    def _heads(self, leaf):
        """A per-head parameter ``[H]`` as ``[G, R]`` float32."""
        return leaf.astype(jnp.float32).reshape(self.groups, -1)

    # -- prefill: one sequence by chunks ---------------------------------------
    @jax.named_scope("mamba")
    def prefill(self, params, u, true_len):
        """``u`` ``[T, D]`` (one sequence, end-padded), ``true_len`` its real
        length -> (out ``[T, D]``, the state after position ``true_len - 1``
        as :meth:`state_shapes` lays it out)."""
        t_in = u.shape[0]
        q = min(self.chunk, t_in)
        # whole chunks: positions past ``true_len`` change nothing
        u = jnp.pad(u, ((0, -t_in % q), (0, 0)))
        t = u.shape[0]
        k = self.conv_kernel
        z, xbc, dt = self._project(params, u)
        # causal depthwise convolution: position t reads t-k+1 .. t
        padded = jnp.concatenate(
            [jnp.zeros((k - 1, self.conv_dim), xbc.dtype), xbc], axis=0)
        w = params["conv"]["w"].astype(jnp.float32)
        conv = sum(w[i] * padded[i:i + t].astype(jnp.float32) for i in range(k))
        conv = jax.nn.silu(conv + params["conv"]["b"].astype(jnp.float32))
        # the last k-1 inputs before ``true_len`` (zeros before the start)
        conv_state = lax.dynamic_slice_in_dim(padded, true_len, k - 1, axis=0)
        x, b, c = self._split(conv)                     # f32
        live = (jnp.arange(t) < true_len)[:, None]
        dt = jnp.where(live, dt, 0.0).reshape(t, self.groups, -1)  # [T, G, R]
        a = dt * -jnp.exp(self._heads(params["A_log"]))           # <= 0

        def chunks(v):
            return v.reshape(t // q, q, *v.shape[1:])

        causal = jnp.tril(jnp.ones((q, q), bool))

        def one_chunk(s, inp):
            """``s`` ``[G, R, P, N]``: the state before the chunk."""
            x_c, b_c, c_c, dt_c, a_c = inp
            cum = jnp.cumsum(a_c, axis=0)                          # [Q, G, R]
            # decay from j (exclusive) to i (inclusive), 0 above the diagonal
            seg = cum[:, None] - cum[None, :]                      # [i, j, G, R]
            decay = jnp.exp(jnp.where(causal[:, :, None, None], seg, -jnp.inf))
            cb = jnp.einsum("ign,jgn->ijg", c_c, b_c)
            m = cb[..., None] * decay * dt_c[None]                 # [i, j, G, R]
            y = jnp.einsum("ijgr,jgrp->igrp", m, x_c)
            y = y + jnp.einsum("ign,grpn->igrp", c_c, s) * jnp.exp(cum)[..., None]
            tail = jnp.exp(cum[-1][None] - cum) * dt_c             # [j, G, R]
            s = (jnp.exp(cum[-1])[..., None, None] * s
                 + jnp.einsum("jgr,jgrp,jgn->grpn", tail, x_c, b_c))
            return s, y

        s0 = jnp.zeros((self.groups, self.heads // self.groups, self.head_dim,
                        self.state), jnp.float32)
        s, y = lax.scan(one_chunk, s0, tuple(map(chunks, (x, b, c, dt, a))))
        y = y.reshape(t, self.groups, -1, self.head_dim)
        y = y + self._heads(params["D"])[..., None] * x
        out = self._finish(params, y.reshape(t, self.d_inner), z)
        state = {"ssm": s.reshape(self.heads, self.head_dim, self.state),
                 "conv": conv_state.astype(jnp.bfloat16)}
        return out[:t_in], state

    # -- decode: one token per slot --------------------------------------------
    def state_update_impl(self) -> str:
        """:func:`resolve_state_update` for this layer's state."""
        return resolve_state_update(self.head_dim, self.state,
                                    self.state_shapes()["ssm"][1])

    @jax.named_scope("mamba")
    def decode(self, params, u, pools, layer: int):
        """``u`` ``[B, D]``; ``pools`` the states of every state layer and
        slot, whole: name -> ``[L, B, ...]`` as :meth:`state_shapes` lays a
        sequence's out; ``layer`` this layer's (static) index in them ->
        (out ``[B, D]``, the pools with this layer's states after the
        token)."""
        bsz = u.shape[0]
        z, xbc, dt = self._project(params, u)
        window = jnp.concatenate(
            [pools["conv"][layer].astype(xbc.dtype), xbc[:, None]], axis=1)
        w = params["conv"]["w"].astype(jnp.float32)
        conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w)
        conv = jax.nn.silu(conv + params["conv"]["b"].astype(jnp.float32))
        x, b, c = self._split(conv)              # [B,G,R,P], [B,G,N], [B,G,N]
        dt = dt.reshape(bsz, self.groups, -1)                     # [B, G, R]
        a = jnp.exp(dt * -jnp.exp(self._heads(params["A_log"])))
        dtx = dt[..., None] * x
        impl = self.state_update_impl()
        if impl == "plain":
            s = pools["ssm"][layer].reshape(bsz, self.groups, -1,
                                            self.head_dim, self.state)
            s = a[..., None, None] * s + dtx[..., None] * b[:, :, None, None, :]
            y = jnp.sum(s * c[:, :, None, None, :], axis=-1)      # [B,G,R,P]
            ssm = pools["ssm"].at[layer].set(
                s.reshape(pools["ssm"].shape[1:]))
        else:
            ssm, y = state_update(
                pools["ssm"], layer, a.reshape(bsz, self.heads),
                dtx.reshape(bsz, self.heads, self.head_dim), b, c,
                interpret=impl == "kernel_interpret")
            y = y.reshape(x.shape)
        y = y + self._heads(params["D"])[..., None] * x
        out = self._finish(params, y.reshape(bsz, self.d_inner), z)
        conv = pools["conv"].at[layer].set(
            window[:, 1:].astype(pools["conv"].dtype))
        return out, {"ssm": ssm, "conv": conv}


def state_update_parity(heads: int, groups: int, head_dim: int, state: int,
                        *, dim: int = 256, max_batch: int = 4,
                        impl: str = "kernel", seed: int = 0) -> dict:
    """One decode step of a :class:`Mamba2` layer through ``impl`` and
    through the plain lines, from the same random weights, inputs and
    pools, at layer 1 of a two-layer pool (the kernel's layer index is
    under the check too); -> the largest absolute differences of the
    layer's output and of the states it leaves, and the tolerances they
    are held to.  ``S'`` is the same three products and one sum per
    element either way; ``y`` sums 128 float32 terms in another order, so
    the output is held to 64 rounding steps at its magnitude.  The check
    ``chip_smoke.py`` runs on the chip at the served geometry."""
    import numpy as np

    mixer = Mamba2(dim, heads, head_dim, state, groups)
    k_p, k_u, k_s, k_c = jax.random.split(jax.random.PRNGKey(seed), 4)
    params, _, _ = mixer.init(k_p, (dim,))
    u = jax.random.normal(k_u, (max_batch, dim), jnp.float32)
    shapes = mixer.state_shapes()
    pools = {name: jax.random.normal(k, (2, max_batch, *shapes[name][0]),
                                     jnp.float32).astype(shapes[name][1])
             for name, k in (("ssm", k_s), ("conv", k_c))}
    got = {}
    for which in (impl, "plain"):
        with pin_state_update(which):
            resolved = mixer.state_update_impl()
            out, new = jax.jit(lambda p, u, s: mixer.decode(p, u, s, 1))(
                params, u, pools)
        got[which] = (resolved, np.asarray(out), np.asarray(new["ssm"]))
    (resolved, out, ssm), (_, want_out, want_ssm) = got[impl], got["plain"]
    eps = float(jnp.finfo(jnp.float32).eps)
    tol_out = 64 * eps * max(1.0, float(np.abs(want_out).max()))
    tol_ssm = 4 * eps * max(1.0, float(np.abs(want_ssm).max()))
    err_out = float(np.abs(out - want_out).max())
    err_ssm = float(np.abs(ssm - want_ssm).max())
    finite = bool(np.isfinite(out).all() and np.isfinite(ssm).all())
    untouched = bool(np.array_equal(ssm[0], np.asarray(pools["ssm"][0])))
    return {"state_update": resolved, "heads": heads, "groups": groups,
            "head_dim": head_dim, "state": state, "slots": max_batch,
            "finite": finite, "max_abs_err_out": err_out,
            "tolerance_out": tol_out, "max_abs_err_state": err_ssm,
            "tolerance_state": tol_ssm, "other_layer_unchanged": untouched,
            "ok": bool(finite and untouched and resolved == impl
                       and err_out <= tol_out and err_ssm <= tol_ssm)}
