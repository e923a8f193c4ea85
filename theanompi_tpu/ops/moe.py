"""Mixture-of-experts FFN with expert parallelism over the ``model`` axis.

Beyond the reference's capability set (SURVEY.md §2 — 2016), completing the
framework's parallelism surface: dp (rules) × tp (tensor.py) × sp (ring
attention) × pp (pipeline.py) × **ep** (here).  Expert parallelism reuses
the ``model`` mesh axis — the standard choice: EP and TP occupy the same
device group, and a layer uses one or the other.

Routing is top-1 switch style (Fedus et al. 2021) in its einsum/one-hot
form — dense masks, static shapes, no sorting — which is how every
XLA-friendly MoE is written:

- gate logits → top-1 expert per token, gate prob as the combine weight;
- per-expert capacity ``C = ceil(tokens/E · capacity_factor)``: position
  within the expert via a cumsum over the token axis, tokens beyond C are
  DROPPED (contribute zero; the transformer's residual carries them);
- dispatch einsum builds ``[E, C, D]``, ``lax.all_to_all`` over the model
  axis exchanges expert-major slabs so each shard holds its local experts'
  tokens from every peer, the local experts run as one vmapped MLP, and
  the inverse all_to_all + combine einsum returns weighted outputs.

With the model axis unbound or size 1 every expert is local and the
all_to_alls vanish — the same code is the single-device reference the EP
tests compare against.  The auxiliary load-balancing loss (same paper,
``aux_loss``) is returned alongside so callers can add it at their chosen
weight.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import pallas_grouped_matmul as gmm
from theanompi_tpu.parallel.mesh import MODEL_AXIS
from theanompi_tpu.parallel.tensor import axis_bound


def _ep_size(axis_name):
    if axis_bound(axis_name) and lax.axis_size(axis_name) > 1:
        return lax.axis_size(axis_name)
    return 1


@dataclasses.dataclass(frozen=True)
class MoEFFN(L.Layer):
    """Switch-routed expert FFN over ``[B, T, D]``.

    ``n_experts`` is GLOBAL; with EP over ``axis_name`` each shard holds
    ``n_experts / ep`` experts (stacked leading axis on every expert param
    leaf — shard dim 0 over the axis in ``param_specs``).  The
    load-balance auxiliary loss rides in the layer's *state* under
    ``"aux"`` (replicated across ranks); the model adds it to the training
    loss at its chosen weight.

    **Capacity semantics under EP are per rank-chunk**: each rank routes
    its ``tokens/ep`` chunk with ``cap = ceil(chunk * cf / E)`` slots per
    expert, so the global budget per expert is ``ep * cap`` but it is
    partitioned equally across ranks.  In the dropping regime this
    deliberately differs from the single-device model (one global
    ``ceil(tokens * cf / E)`` pool): a chunk whose tokens skew onto one
    expert drops past its per-rank slice even when the global pool has
    room.  This is the standard hardware-aligned choice — a shared global
    pool would need a cross-rank cumsum before dispatch, serializing the
    all_to_all.  Tokens kept by both variants produce identical outputs;
    only the drop SETS differ (pinned by
    ``test_moe_ep4_drop_regime_per_rank_capacity``).  With
    ``capacity_factor >= n_experts`` nothing can drop and EP is exactly
    the single-device model.
    """

    dim: int
    n_experts: int
    hidden_mult: int = 4
    capacity_factor: float = 1.25
    axis_name: str = MODEL_AXIS

    def init(self, key, in_shape):
        d = in_shape[-1]
        if d != self.dim:
            raise ValueError(f"MoEFFN dim {self.dim} != input {d}")
        kg, ku, kd = jax.random.split(key, 3)
        h = self.hidden_mult * d
        w02 = init_lib.normal(0.02)
        params = {
            "gate": {"w": w02(kg, (d, self.n_experts))},
            # stacked expert weights: [E, d, h] / [E, h] / [E, h, d] / [E, d]
            "up_w": w02(ku, (self.n_experts, d, h)),
            "up_b": jnp.zeros((self.n_experts, h), jnp.float32),
            "down_w": w02(kd, (self.n_experts, h, d)),
            "down_b": jnp.zeros((self.n_experts, d), jnp.float32),
        }
        return params, {"aux": jnp.zeros((), jnp.float32)}, tuple(in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        from theanompi_tpu.parallel.tensor import (
            identity_fwd_psum_bwd,
            psum_fwd_identity_bwd,
        )

        b, t, d = x.shape
        n_tok = b * t
        E = self.n_experts
        ep = _ep_size(self.axis_name)
        xt = x.reshape(n_tok, d)

        # token slicing: activations are replicated across the EP axis (TP
        # semantics), so each rank routes only its 1/ep slice of the tokens
        # — that is what makes the expert compute actually parallel.  The
        # Megatron-f wrap repairs the sliced cotangent (each rank's is the
        # partial for its chunk); the final g-op psum rebuilds the full
        # token output from the per-rank padded slices.
        gate_w = params["gate"]["w"]
        if ep > 1:
            if n_tok % ep:
                raise ValueError(f"tokens {n_tok} not divisible by ep={ep}")
            if E % ep:
                raise ValueError(f"{E} experts not divisible by ep={ep}")
            chunk = n_tok // ep
            me = lax.axis_index(self.axis_name)
            xt_full = identity_fwd_psum_bwd(xt, self.axis_name)
            xt_loc = lax.dynamic_slice_in_dim(xt_full, me * chunk, chunk, 0)
            # the gate weight is replicated but each rank's cotangent for it
            # covers only its token chunk: pin the param with Megatron-f so
            # the partials sum to the true (replicated) gradient
            gate_w = identity_fwd_psum_bwd(gate_w, self.axis_name)
        else:
            chunk = n_tok
            xt_loc = xt

        # -- route: top-1 expert + prob weight --------------------------------
        logits = xt_loc.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)            # [N, E]
        expert = jnp.argmax(probs, axis=-1)                # [N]
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)
        gate = jnp.sum(probs * onehot, axis=-1)            # [N]

        # load-balance aux loss (Switch eq. 4): E * sum_e f_e * P_e over the
        # GLOBAL token set.  f and P are pmean'd over the EP ranks BEFORE
        # combining (chunks are equal-sized, so the pmean is the global
        # mean): the product is nonlinear, so pmean-ing the per-chunk aux
        # instead would add a cross-chunk covariance term and silently
        # change the objective vs the single-device run
        f = jnp.mean(onehot, axis=0)
        p_mean = jnp.mean(probs, axis=0)
        if ep > 1:
            f = lax.pmean(f, self.axis_name)
            p_mean = lax.pmean(p_mean, self.axis_name)
        aux = E * jnp.sum(f * p_mean)

        # -- capacity + position ----------------------------------------------
        cap = int(max(1, -(-chunk * self.capacity_factor // E)))
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0    # [N, E]; -1 = not routed
        keep = (pos >= 0) & (pos < cap)
        pos_oh = jax.nn.one_hot(pos.max(axis=-1), cap, dtype=jnp.float32)
        sel = (keep.sum(axis=-1) > 0).astype(jnp.float32)  # token survived

        # dispatch [N, E, C]: token n -> (its expert, its slot), if kept
        dispatch = onehot[:, :, None] * pos_oh[:, None, :] * sel[:, None, None]
        slabs = jnp.einsum("nec,nd->ecd", dispatch,
                           xt_loc.astype(jnp.float32))     # [E, C, D]

        if ep > 1:
            e_local = E // ep
            # expert-major slabs: peer p gets my tokens for ITS experts
            slabs = slabs.reshape(ep, e_local, cap, d)
            slabs = lax.all_to_all(
                slabs, self.axis_name, split_axis=0, concat_axis=0,
                tiled=False,
            )  # [ep, e_local, C, D]: dim 0 now indexes source rank
            slabs = slabs.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)
        else:
            e_local = E

        # -- local experts: one vmapped MLP over the stacked weights ----------
        def expert_mlp(up_w, up_b, down_w, down_b, h_in):
            y = jnp.einsum("cd,dh->ch", h_in, up_w.astype(jnp.float32))
            y = jax.nn.gelu(y + up_b[None, :])
            y = jnp.einsum("ch,hd->cd", y, down_w.astype(jnp.float32))
            return y + down_b[None, :]

        out_slabs = jax.vmap(expert_mlp)(
            params["up_w"].astype(jnp.float32), params["up_b"],
            params["down_w"].astype(jnp.float32), params["down_b"], slabs,
        )  # [e_local, *, D]

        if ep > 1:
            out_slabs = out_slabs.reshape(e_local, ep, cap, d)
            out_slabs = out_slabs.transpose(1, 0, 2, 3)    # [ep, e_local, C, D]
            out_slabs = lax.all_to_all(
                out_slabs, self.axis_name, split_axis=0, concat_axis=0,
                tiled=False,
            )
            out_slabs = out_slabs.reshape(E, cap, d)

        # -- combine: weighted gather back to token order ---------------------
        yt = jnp.einsum("nec,ecd->nd", dispatch, out_slabs) * gate[:, None]
        if ep > 1:
            pad = jnp.zeros((n_tok, d), jnp.float32)
            pad = lax.dynamic_update_slice_in_dim(pad, yt, me * chunk, 0)
            yt = psum_fwd_identity_bwd(pad, self.axis_name)
        return (yt.reshape(b, t, d).astype(x.dtype),
                {"aux": aux} if not state else {**state, "aux": aux})


@dataclasses.dataclass(frozen=True)
class DroplessMoE(L.Layer):
    """Top-k sigmoid-routed experts with a shared expert, no capacity and no
    dropped token (the ``E`` layer of ``HybridLM``), over tokens ``[N, D]``::

        s = sigmoid(W_r u)                     float32, all ``n_experts``
        selected = top_k(s + b_corr)           b_corr biases selection only
        w_i = route_scale * s_i / sum_selected s_j
        l = W_down u                           D -> latent
        r = sum_i w_i W2_i act(W1_i l)         latent -> expert_dim -> latent
        out = W_up r + V2 act(V1 u)            the shared expert sees every token

    ``router="softmax"``: ``s = softmax(W_r u)`` in float32 over all
    ``n_experts``, the ``top_k`` largest selected with no correction bias
    (no ``b_corr`` leaf) and their ``s`` renormalised to sum 1 (times
    ``route_scale``) — the Qwen3-MoE router with ``norm_topk_prob``.
    ``shared_dim=0``: no shared expert, no ``shared`` leaves, no product.

    ``latent=None``: no ``W_down`` / ``W_up``, the experts multiply at model
    width (``l = u``, ``out = r + ...``).  ``activation``, for the experts
    and the shared expert alike: ``"relu2"`` (``relu(h)^2``) or
    ``"silu_gated"`` (``silu(h[:F]) * h[F:]``: ``W1`` and ``V1`` are then
    ``2 F`` wide, gate and up halves side by side in ONE product).

    ``experts_held = (lo, hi)`` is this chip's share of an expert-parallel
    deployment: the router stays ``n_experts`` wide and selects over all of
    them, the stacked expert weights hold experts ``lo..hi-1`` only, and
    ``r`` sums the selected experts that are held.  What the absent experts
    would add is added by the chips that hold them; on one chip the layer
    runs without that exchange.  The shared expert and the latent
    projections are computed by every chip alike (``routed=False`` /
    ``shared=False`` give the two parts apart, for tests that add shares up).

    The expert products are grouped matrix products: the ``N * top_k``
    assignments are sorted by expert and each expert's rows are multiplied
    by that expert's weights, so the work is that of the rows there are and
    no ``[N, E, ...]`` mask is built.  Assignments to absent experts sort
    last, past every group, and are discarded by selection.

    ``products`` says what multiplies: ``"ragged_dot"``
    (``jax.lax.ragged_dot``: the default, what every CPU run and every shape
    the kernel's gate refuses takes), ``"kernel"``
    (:func:`theanompi_tpu.ops.pallas_grouped_matmul.grouped_matmul` compiled
    for the TPU: each held expert's weights read once, no read for an expert
    without a row) or ``"kernel_interpret"`` (the same kernel through the
    Pallas interpreter: the parity tests).  A serving engine resolves it
    once from its ``decode_kernel`` argument and
    :func:`~theanompi_tpu.ops.pallas_grouped_matmul.grouped_matmul_supported`
    (latent and expert widths whole numbers of 128, bf16), where it resolves
    the decode-attention path.  The kernel leaves rows past the last group
    unwritten, so nothing here may multiply them by zero to drop them.
    """

    dim: int
    n_experts: int
    top_k: int
    latent: int | None
    expert_dim: int
    shared_dim: int
    route_scale: float = 1.0
    experts_held: tuple[int, int] | None = None
    products: str = "ragged_dot"
    activation: str = "relu2"
    router: str = "sigmoid"

    def __post_init__(self):
        if self.activation not in ("relu2", "silu_gated"):
            raise ValueError(f"DroplessMoE activation={self.activation!r} "
                             f"not in ('relu2', 'silu_gated')")
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(f"DroplessMoE router={self.router!r} not in "
                             f"('sigmoid', 'softmax')")

    @property
    def width(self) -> int:
        """The width the experts multiply at: ``latent``, or ``dim``."""
        return self.dim if self.latent is None else self.latent

    @property
    def product_shapes(self) -> tuple:
        """``(K, N)`` of the layer's two grouped products."""
        up = self.expert_dim * (2 if self.activation == "silu_gated" else 1)
        return (self.width, up), (self.expert_dim, self.width)

    def _act(self, h):
        """``h`` ``[..., F]`` or (gated) ``[..., 2 F]`` -> ``[..., F]``."""
        if self.activation == "relu2":
            return jnp.square(jax.nn.relu(h))
        gate, up = jnp.split(h, 2, axis=-1)
        return jax.nn.silu(gate) * up

    @property
    def held(self) -> tuple[int, int]:
        lo, hi = self.experts_held or (0, self.n_experts)
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {(lo, hi)} outside 0..{self.n_experts}")
        return int(lo), int(hi)

    def init(self, key, in_shape):
        if in_shape[-1] != self.dim:
            raise ValueError(f"DroplessMoE dim {self.dim} != input {in_shape[-1]}")
        lo, hi = self.held
        ks = jax.random.split(key, 7)
        w02 = init_lib.normal(0.02)
        (k1, n1), (k2, n2) = self.product_shapes
        fan = n1 // self.expert_dim  # 2 where gate and up share a product
        params = {
            "router": {"w": w02(ks[0], (self.dim, self.n_experts))},
            "w1": w02(ks[2], (hi - lo, k1, n1)),
            "w2": w02(ks[3], (hi - lo, k2, n2)),
        }
        if self.router == "sigmoid":
            params["router"]["b_corr"] = jnp.zeros((self.n_experts,),
                                                   jnp.float32)
        if self.shared_dim:
            params["shared"] = {
                "v1": w02(ks[5], (self.dim, fan * self.shared_dim)),
                "v2": w02(ks[6], (self.shared_dim, self.dim))}
        if self.latent is not None:
            params["down"] = {"w": w02(ks[1], (self.dim, self.latent))}
            params["up"] = {"w": w02(ks[4], (self.latent, self.dim))}
        return params, {}, tuple(in_shape)

    def route(self, params, u):
        """-> (selected experts ``[N, top_k]`` int32, their weights
        ``[N, top_k]`` float32)."""
        with jax.named_scope("moe.route"):
            logits = (u.astype(jnp.float32)
                      @ params["router"]["w"].astype(jnp.float32))
            if self.router == "softmax":
                s = jax.nn.softmax(logits, axis=-1)
                _, idx = lax.top_k(s, self.top_k)
            else:
                s = jax.nn.sigmoid(logits)
                _, idx = lax.top_k(
                    s + params["router"]["b_corr"].astype(jnp.float32),
                    self.top_k)
            w = jnp.take_along_axis(s, idx, axis=-1)
            w = self.route_scale * w / jnp.sum(w, axis=-1, keepdims=True)
            return idx.astype(jnp.int32), w

    def _grouped_dot(self, sizes, m: int, dtype):
        """-> ``dot(rows [m, K], w [E, K, N], out dtype)`` over the groups
        ``sizes`` by the path ``products`` names; the kernel's schedule is
        made once for the layer's two products."""
        if self.products == "ragged_dot":
            return lambda rows, w, out: lax.ragged_dot(
                rows, w, sizes, preferred_element_type=out)
        if self.products not in ("kernel", "kernel_interpret"):
            raise ValueError(f"DroplessMoE products={self.products!r} not in "
                             f"('ragged_dot', 'kernel', 'kernel_interpret')")
        tm = gmm.row_tile(m, dtype)
        visits = gmm.group_visits(sizes, m, tm)
        return lambda rows, w, out: gmm.grouped_matmul(
            rows, w, sizes, tm=tm, out_dtype=out, visits=visits,
            interpret=self.products == "kernel_interpret")

    def apply_tokens(self, params, u, *, routed: bool = True,
                     shared: bool = True, active=None):
        """``u`` ``[N, D]`` -> (out ``[N, D]``, stats).  ``stats``:
        ``local_hits`` (selected experts that are held, summed over the
        tokens ``active`` marks; all where None) and ``load_peak`` (the
        largest number of those assignments any held expert received)."""
        n = u.shape[0]
        lo, hi = self.held
        e_held = hi - lo
        out = jnp.zeros((n, self.dim), u.dtype)
        stats = {"local_hits": jnp.int32(0), "load_peak": jnp.int32(0)}
        if routed:
            idx, w = self.route(params, u)
            with jax.named_scope("moe.experts"):
                lat = (u if self.latent is None
                       else u @ params["down"]["w"].astype(u.dtype))
                local = idx - lo
                is_held = (local >= 0) & (local < e_held)
                # absent experts sort past every group
                eid = jnp.where(is_held, local, e_held).reshape(-1)
                order = jnp.argsort(eid, stable=True)
                sizes = jnp.bincount(eid, length=e_held + 1)[:e_held]
                rows = jnp.take(lat, order // self.top_k, axis=0)
                dot = self._grouped_dot(sizes.astype(jnp.int32),
                                        rows.shape[0], u.dtype)
                h = dot(rows, params["w1"].astype(u.dtype), u.dtype)
                h = self._act(h.astype(jnp.float32))
                y = dot(h.astype(u.dtype), params["w2"].astype(u.dtype),
                        jnp.float32)
                # back to token order; rows past the last group belong to
                # no expert (the kernel never writes them) and are selected
                # away below, not multiplied away
                y = jnp.take(y, jnp.argsort(order), axis=0).reshape(
                    n, self.top_k, self.width)
                r = jnp.sum(jnp.where(is_held[..., None], y * w[..., None],
                                      0.0), axis=1).astype(u.dtype)
                out = out + (r if self.latent is None
                             else r @ params["up"]["w"].astype(u.dtype))
                counted = is_held if active is None else (
                    is_held & active[:, None])
                stats = {
                    "local_hits": jnp.sum(counted, dtype=jnp.int32),
                    "load_peak": jnp.max(jnp.bincount(
                        jnp.where(counted, local, e_held).reshape(-1),
                        length=e_held + 1)[:e_held]).astype(jnp.int32)}
        if shared and self.shared_dim:
            with jax.named_scope("moe.shared"):
                hs = self._act(u @ params["shared"]["v1"].astype(u.dtype))
                out = out + hs @ params["shared"]["v2"].astype(u.dtype)
        return out, stats

    def apply(self, params, state, x, *, train=False, rng=None):
        lead = x.shape[:-1]
        y, _ = self.apply_tokens(params, x.reshape(-1, self.dim))
        return y.reshape(*lead, self.dim), state
