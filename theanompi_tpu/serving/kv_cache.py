"""Paged KV cache: fixed-size blocks, per-sequence block tables, alloc/free
pool (the vLLM layout — Kwon et al., SOSP 2023 — at this repo's scale).

Why paged: a contiguous per-sequence KV buffer must be sized for the WORST
case (``max_batch x seq_len``), and continuous batching (Orca) makes the
resident set churn — sequences of wildly different lengths join and leave
every step.  Fixed-size blocks turn that into a heap problem: a sequence
holds ``ceil(len / block_size)`` blocks scattered anywhere in the pool, the
allocator hands blocks out and takes them back O(1), and the pool can be
deliberately oversubscribed (admission is bounded by actual tokens, not
worst-case reservations) with preemption as the pressure valve
(:mod:`theanompi_tpu.serving.scheduler`).

Layout: one pool per model, ``[L, num_blocks, block_size, H, Dh]`` for K and
V — a block id indexes the same slot in every layer, so one block table per
sequence serves the whole stack.  Block 0 is RESERVED as the null block:
inactive batch slots and prefill padding point their table entries at it, so
the fixed-shape decode step can scatter/gather unconditionally and the
garbage lands where nothing unmasked ever reads.

Attention here is the pure-JAX paged path (gather the table, mask by
length) — the CPU tier-1 reference semantics.  Prefill attention does NOT
go through this module at all: it runs inside the prompt through
``MultiHeadAttention.attend`` (:mod:`theanompi_tpu.ops.attention`), i.e. the
pallas flash kernels of ``ops/pallas_attention.py`` whenever the shape gate
admits them — on TPU the O(P²) half of serving rides the same kernels as
training.  The O(P) per-token decode has two implementations selected by
the static ``decode_impl`` field (ISSUE 18): the pure-JAX blockwise gather
below (``"fallback"``), and the fused pallas kernel of
``ops/pallas_paged_attention.py`` (``"kernel"``) whose block table drives
the DMA index_map directly: ``paged_attend_decode(k, v, layer, tables,
block_size, q, positions)``, one kernel per (layer, step) over the WHOLE
``[L, ...]`` pools with the layer in the index_map.  Both compute the
SAME blockwise online-softmax recurrence in the same op order, so they are bit-identical
on CPU (`interpret=True`) — the parity lock the HLO audit and
tests/test_paged_decode_kernel.py enforce.  A pool of FEWER K/V heads than
the queries handed in have heads (grouped K/V) has a pair of its own
(ISSUE 34): ``paged_attend_decode_grouped`` on a kernel tier — each slot's
blocks fetched by the table, many a step, one product for a K/V head's
whole group of queries — and the gather of ``_attend_decode_grouped`` on the
fallback, alike in operand and accumulation types and held to a tolerance
(tests/test_paged_decode_grouped_kernel.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.pallas_paged_attention import (
    paged_attend_decode,
    paged_attend_decode_grouped,
)

_NEG_INF = -1e30
#: sublanes of a TPU tile: a pool's K/V heads are its tiled second-minor
#: dimension, and XLA re-lays out a pool of fewer heads around a
#: whole-block scatter (:func:`prefill_write_form`)
_SUBLANES = 8
#: the widest context the grouped fallback gathers in one piece; a wider
#: table goes through in pieces of this many tokens (``_attend_decode_grouped``)
_GROUPED_CHUNK_TOKENS = 4096


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """The device-side half of the cache: K/V pools + per-slot block tables.

    A pytree (k/v/block_tables are leaves; ``block_size`` is static), so it
    threads through jit-compiled prefill/decode steps functionally — every
    write returns a new cache whose arrays XLA updates in place when the
    caller donates the old ones.  Host-side bookkeeping (which blocks are
    free, which slot maps to which request) lives in :class:`BlockPool` /
    the scheduler, never on device.
    """

    # ``L`` counts the pool's ENTRIES: one a layer that attends, or, for a
    # stack that is applied ``loops`` times, one per (loop step, layer).
    # Every method's ``layer`` is an entry: a Python int, or a traced int32
    # scalar inside a loop of the program (ISSUE 31)
    k: jax.Array             # [L, num_blocks, block_size, H, Dh]
    v: jax.Array             # [L, num_blocks, block_size, H, Dh]
    block_tables: jax.Array  # [max_batch, max_blocks_per_seq] int32
    block_size: int
    #: decode attention implementation, static: "fallback" (pure-JAX
    #: blockwise gather), "kernel" (compiled pallas paged decode) or
    #: "kernel_interpret" (same kernel, pallas interpreter — the CPU
    #: parity-lock mode).  Static aux, so each variant compiles its own
    #: program; compiled-vs-interpret is pinned here rather than sniffed
    #: from the backend at trace time so a CPU host can still lower the
    #: compiled variant for TPU (the HLO audit does exactly that).
    decode_impl: str = "fallback"
    #: per-slot recurrent state of the layers that keep one (a model's
    #: ``cache_spec()["state"]``): name -> ``[n_state_layers, max_batch,
    #: ...]``.  Not paged: a slot owns one fixed-size entry per layer, a
    #: prefill writes its slot's entry whole and a decode step updates
    #: every slot's.  Empty for a model whose layers all cache K/V.
    #: The window layers' K/V live here too (``cache_spec()["window"]``):
    #: ``window_k`` / ``window_v`` ``[window layers, max_batch, window,
    #: H, Dh]``, a ring a slot — the token at position ``p`` at ``p %
    #: window`` — that costs ``window`` tokens however long the sequence
    #: grows, is no part of the paged pool and is never read by the slot's
    #: next owner (:meth:`write_window_prefill`).
    state: dict = dataclasses.field(default_factory=dict)

    NULL_BLOCK = 0

    def tree_flatten(self):
        return ((self.k, self.v, self.block_tables, self.state),
                (self.block_size, self.decode_impl))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, tables, state = children
        return cls(k, v, tables, block_size=aux[0], decode_impl=aux[1],
                   state=state)

    # -- shape properties ----------------------------------------------------
    @property
    def n_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def max_context(self) -> int:
        return self.block_tables.shape[1] * self.block_size

    @classmethod
    def from_spec(cls, spec: dict, *, num_blocks: int, block_size: int,
                  max_batch: int, max_context: int, dtype=jnp.float32,
                  decode_impl: str = "fallback") -> "PagedKVCache":
        """The cache a model's ``cache_spec()`` asks for: K/V pools over its
        ``kv`` layers (``layers``, ``heads``, ``head_dim``) and, per entry
        of ``state`` (name -> (per-sequence shape, dtype)), a slot-indexed
        pool ``[state_layers, max_batch, *shape]`` of zeros; per ``window``
        layer (``layers``, ``size``, ``heads``, ``head_dim``) a ring of
        ``size`` tokens a slot, beside the paged pool."""
        kv = spec["kv"]
        cache = cls.create(kv["layers"], num_blocks, block_size, kv["heads"],
                           kv["head_dim"], max_batch, max_context, dtype,
                           decode_impl)
        state = {name: jnp.zeros((spec["state_layers"], max_batch, *shape), dt)
                 for name, (shape, dt) in spec["state"].items()}
        win = spec.get("window")
        if win:
            ring = (win["layers"], max_batch, win["size"], win["heads"],
                    win["head_dim"])
            state.update(window_k=jnp.zeros(ring, dtype),
                         window_v=jnp.zeros(ring, dtype))
        return dataclasses.replace(cache, state=state)

    @classmethod
    def create(cls, n_layers: int, num_blocks: int, block_size: int,
               heads: int, head_dim: int, max_batch: int,
               max_context: int, dtype=jnp.float32,
               decode_impl: str = "fallback") -> "PagedKVCache":
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved null block)")
        if decode_impl not in ("fallback", "kernel", "kernel_interpret"):
            raise ValueError(f"unknown decode_impl {decode_impl!r}")
        max_blocks_per_seq = -(-max_context // block_size)
        shape = (n_layers, num_blocks, block_size, heads, head_dim)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            block_tables=jnp.zeros((max_batch, max_blocks_per_seq),
                                   jnp.int32),
            block_size=block_size,
            decode_impl=decode_impl,
        )

    def with_tables(self, tables) -> "PagedKVCache":
        """New cache view with the given ``[max_batch, max_blocks]`` tables
        (the scheduler re-materializes these from host state each step)."""
        return dataclasses.replace(
            self, block_tables=jnp.asarray(tables, jnp.int32))

    # -- writes --------------------------------------------------------------
    def write_prefill(self, layer, k, v, table_row) -> "PagedKVCache":
        """Write a whole prompt's K/V for one layer: ``k``/``v``
        ``[1, P_pad, H, Dh]`` with ``P_pad`` a multiple of ``block_size``;
        ``table_row`` ``[P_pad // block_size]`` block ids (padding entries
        point at the null block — duplicate scatter indices are fine, the
        null block's content is never read unmasked).  The pool's shape
        picks the form (:func:`prefill_write_form`); both land the same
        bytes in the same blocks, and neither re-lays the pool out."""
        bs = self.block_size
        idx = jnp.asarray(table_row, jnp.int32)
        if prefill_write_form(self.k.shape[3]) == "token_rows":
            return dataclasses.replace(
                self, k=_write_token_rows(self.k, layer, k, idx, bs),
                v=_write_token_rows(self.v, layer, v, idx, bs))
        p_pad = k.shape[1]
        blocks_k = k[0].reshape(p_pad // bs, bs, *k.shape[2:])
        blocks_v = v[0].reshape(p_pad // bs, bs, *v.shape[2:])
        return dataclasses.replace(
            self, k=self.k.at[layer, idx].set(blocks_k.astype(self.k.dtype)),
            v=self.v.at[layer, idx].set(blocks_v.astype(self.v.dtype)))

    def write_decode(self, layer, k, v, positions) -> "PagedKVCache":
        """Append one token's K/V per batch slot: ``k``/``v`` ``[B, H, Dh]``
        at ``positions`` ``[B]`` (inactive slots' tables point at the null
        block, so their writes land in reserved garbage)."""
        b = k.shape[0]
        blk_idx = positions // self.block_size
        blk = jnp.take_along_axis(
            self.block_tables, blk_idx[:, None], axis=1)[:, 0]
        off = positions % self.block_size
        return dataclasses.replace(
            self, k=self.k.at[layer, blk, off].set(k.astype(self.k.dtype)),
            v=self.v.at[layer, blk, off].set(v.astype(self.v.dtype)))

    def write_block(self, layer, k, v, lengths) -> "PagedKVCache":
        """Write a block of ``n`` positions' K/V per slot: ``k``/``v`` ``[B,
        n, H, Dh]`` at positions ``lengths[b] .. lengths[b] + n - 1``, where
        ``lengths`` is a multiple of ``n`` and ``n`` divides ``block_size``,
        so a slot's block lies in one cache block (a block-diffusion pass)."""
        n = k.shape[1]
        blk = jnp.take_along_axis(
            self.block_tables, (lengths // self.block_size)[:, None], axis=1)
        off = lengths[:, None] % self.block_size + jnp.arange(n)[None, :]
        return dataclasses.replace(
            self, k=self.k.at[layer, blk, off].set(k.astype(self.k.dtype)),
            v=self.v.at[layer, blk, off].set(v.astype(self.v.dtype)))

    def attend_block(self, layer, q, lengths):
        """Every query of a slot's block over the keys ``[0, lengths[b] +
        n)`` — its cached context and the whole block, no mask inside it:
        ``q`` ``[B, n, H, Dh]`` (the block's K/V written already) -> context
        ``[B, n, H, Dh]``.  The block's ``n x H`` queries see the same keys,
        so they go through :meth:`attend_decode` as ``n x H`` query heads of
        one slot, K/V-head-major (row ``r`` reads K/V head ``r // (n H /
        Hkv)``), on whichever path it takes for a grouped pool."""
        b, n, h, d = q.shape
        hkv = self.k.shape[3]
        rows = q.reshape(b, n, hkv, h // hkv, d).transpose(0, 2, 1, 3, 4)
        ctx = self.attend_decode(layer, rows.reshape(b, n * h, d),
                                 lengths + n - 1)
        return ctx.reshape(b, hkv, n, h // hkv, d).transpose(
            0, 2, 1, 3, 4).reshape(b, n, h, d)

    # -- per-slot recurrent state ----------------------------------------------
    def write_state(self, layer: int, new: dict, slot) -> "PagedKVCache":
        """Replace state layer ``layer`` of ``slot`` whole (``new`` leaves
        as a sequence's state is laid out, a prefill: whatever a previous
        owner of the slot left there is gone).  A decode step hands the
        layer ``state`` itself, every layer and slot of it, and takes it
        back (``Mamba2.decode``)."""
        return dataclasses.replace(self, state={**self.state, **{
            name: self.state[name].at[layer, slot].set(
                x.astype(self.state[name].dtype)) for name, x in new.items()}})

    # -- window layers: a ring a slot ------------------------------------------
    def write_window_prefill(self, layer: int, k, v, true_len,
                             slot) -> "PagedKVCache":
        """Leave a prompt's last ``window`` tokens in ``slot``'s ring of
        window layer ``layer``: ``k``/``v`` ``[1, P_pad, H, Dh]``, the prompt
        ``true_len`` long.  Ring entry ``r`` takes the last position ``j <
        true_len`` with ``j % window == r``; where there is none (a prompt
        shorter than the window) it takes position 0's, which no decode step
        reads before it has written the entry itself.  The whole ring is
        written, so nothing of the slot's previous owner stays."""
        size = self.state["window_k"].shape[2]
        r = jnp.arange(size, dtype=jnp.int32)
        last = jnp.asarray(true_len, jnp.int32) - 1
        src = jnp.clip(last - (last - r) % size, 0, k.shape[1] - 1)
        return self.write_state(layer, {
            "window_k": jnp.take(k[0], src, axis=0),
            "window_v": jnp.take(v[0], src, axis=0)}, slot)

    def write_window_decode(self, layer: int, k, v, positions) -> "PagedKVCache":
        """One token's K/V per slot into its ring: ``k``/``v`` ``[B, H, Dh]``
        at ``positions`` ``[B]``.  A slot at position 0 is inactive and
        keeps what it holds (it may have been prefilled for the next step)."""
        pool_k, pool_v = self.state["window_k"], self.state["window_v"]
        b = jnp.arange(k.shape[0])
        at = positions % pool_k.shape[2]
        live = (positions > 0)[:, None, None]
        k = jnp.where(live, k.astype(pool_k.dtype), pool_k[layer, b, at])
        v = jnp.where(live, v.astype(pool_v.dtype), pool_v[layer, b, at])
        return dataclasses.replace(self, state={
            **self.state, "window_k": pool_k.at[layer, b, at].set(k),
            "window_v": pool_v.at[layer, b, at].set(v)})

    def attend_window_decode(self, layer: int, q, positions):
        """One query token per slot over its ring: ``q`` ``[B, H, Dh]`` at
        ``positions`` ``[B]`` (written already) -> context ``[B, H, Dh]``.
        The ring holds positions ``p - window < j <= p`` once ``p >= window
        - 1``; before that entry ``r`` is this sequence's only if ``r <=
        p``.  Grouped K/V heads, one masked float32 softmax, as
        :meth:`_attend_decode_grouped`."""
        return _grouped_attend(q, self.state["window_k"][layer],
                               self.state["window_v"][layer], positions)

    # -- paged attention (suffix prefill) --------------------------------------
    def attend_prefill(self, layer: int, q, table_row, prefix_len):
        """Masked attention of a SUFFIX of queries over one sequence's full
        cached context (the partial-prefill path, ISSUE 17): ``q``
        ``[1, S_pad, H, Dh]`` — the uncached suffix starting at absolute
        position ``prefix_len`` — attends over every position the row's
        blocks hold, cached-prefix K/V included.  ``table_row``
        ``[max_blocks_per_seq]`` block ids -> context ``[1, S_pad, H, Dh]``.

        Same fp32 softmax / ``_NEG_INF`` mask discipline as
        :meth:`attend_decode`; the causal mask admits absolute positions
        ``<= prefix_len + s`` for suffix query ``s``.  End-padding queries
        past the true suffix attend over masked-in garbage (null-block and
        unwritten positions) — finite, never NaN, and discarded: the engine
        samples only from the last REAL position's logits."""
        scale = q.shape[-1] ** -0.5
        # [nb, bs, H, Dh] -> [T_max, H, Dh]
        kb = jnp.take(self.k[layer], table_row, axis=0)
        vb = jnp.take(self.v[layer], table_row, axis=0)
        t_max = kb.shape[0] * self.block_size
        kb = kb.reshape(t_max, *kb.shape[2:])
        vb = vb.reshape(t_max, *vb.shape[2:])
        qf = q[0].astype(jnp.float32) * scale           # [S, H, Dh]
        s = jnp.einsum("shd,thd->sht", qf, kb.astype(jnp.float32))
        pos_q = prefix_len + jnp.arange(q.shape[1])     # absolute positions
        valid = jnp.arange(t_max)[None, :] <= pos_q[:, None]
        s = jnp.where(valid[:, None, :], s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.einsum("sht,thd->shd", p, vb.astype(jnp.float32))
        return ctx[None].astype(q.dtype)

    # -- paged attention (decode) --------------------------------------------
    def attend_decode(self, layer, q, positions):
        """Masked attention of one query token per slot over its cached
        context: ``q`` ``[B, H, Dh]``, ``positions`` ``[B]`` (the query's
        own 0-based position, already written) -> context ``[B, H, Dh]``.

        fp32 softmax like the training paths; the mask admits positions
        ``<= positions[b]``.  Inactive slots (position 0 pointing at the
        null block) attend over one garbage token — finite garbage out,
        discarded by the scheduler, and crucially never NaN (an all-masked
        softmax would poison the lane).

        ``decode_impl == "kernel"`` dispatches to a fused pallas kernel
        (:mod:`theanompi_tpu.ops.pallas_paged_attention`: one for a pool of
        as many K/V heads as ``q`` has heads, one for a grouped pool, which
        has its own fallback too); the default is
        the pure-JAX masked gather below, restructured (ISSUE 18) from one
        global softmax into the blockwise online-softmax recurrence so the
        two paths share an op-for-op schedule and stay BIT-identical on
        CPU (a fully-masked block is an exact no-op of the recurrence:
        correction ``exp(0) == 1``, masked probabilities underflow to 0 —
        so the kernel gating trailing null blocks off changes nothing).
        The recurrence equals the old single softmax to ~1e-7 (the
        running max ends at the global max; only the rounding association
        of the normalizer differs), which test_paged_decode_kernel.py pins
        against the verbatim old formula."""
        # a pool of fewer K/V heads than ``q`` has query heads: the grouped
        # kernel, or the grouped gather (what is handed in decides, so a
        # model's layers of different query-head counts share one pool)
        grouped = q.shape[1] != self.k.shape[3]
        if self.decode_impl != "fallback":
            # the whole pools, never ``self.k[layer]``: XLA copies a
            # sliced operand of a custom call (see the kernel's module)
            kernel = paged_attend_decode_grouped if grouped \
                else paged_attend_decode
            return kernel(
                self.k, self.v, layer, self.block_tables,
                self.block_size, q, jnp.asarray(positions, jnp.int32),
                interpret=(self.decode_impl == "kernel_interpret"))
        if grouped:
            return self._attend_decode_grouped(layer, q, positions)
        # [B, nb, bs, H, Dh]: gather each slot's blocks, then run the
        # recurrence over the block axis
        kb = jnp.take(self.k[layer], self.block_tables, axis=0)
        vb = jnp.take(self.v[layer], self.block_tables, axis=0)
        b, h, d = q.shape
        bs = self.block_size
        nb = self.block_tables.shape[1]
        qf = q.astype(jnp.float32) * (d ** -0.5)

        # multiply+reduce, NOT einsum/dot: gemm kernels change their
        # accumulation strategy with batching layout, which breaks
        # bit-parity with the pallas kernel's per-head products; sum/max
        # reductions over an explicit axis are order-stable (see the
        # kernel module docstring)
        def body(j, carry):
            m, l, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(kb, j, axis=1,
                                               keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vb, j, axis=1,
                                               keepdims=False)
            s = jnp.sum(k_j.astype(jnp.float32) * qf[:, None, :, :],
                        axis=-1)                               # [B, bs, H]
            t_abs = j * bs + jnp.arange(bs)
            valid = t_abs[None, :, None] <= positions[:, None, None]
            s = jnp.where(valid, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m - m_new)                          # [B, 1, H]
            p = jnp.exp(s - m_new)                             # [B, bs, H]
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            ctx = jnp.sum(p[..., None] * v_j.astype(jnp.float32),
                          axis=1)                              # [B, H, Dh]
            acc_new = acc * jnp.swapaxes(corr, 1, 2) + ctx
            return m_new, l_new, acc_new

        m0 = jnp.full((b, 1, h), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, 1, h), jnp.float32)
        a0 = jnp.zeros((b, h, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
        return (acc / jnp.swapaxes(l, 1, 2)).astype(q.dtype)

    def _attend_decode_grouped(self, layer, q, positions):
        """:meth:`attend_decode`'s fallback where the pool holds fewer K/V
        heads than ``q`` has query heads (query head ``h`` reads K/V head
        ``h // (H // Hkv)``): one masked fp32 softmax over each slot's
        gathered context, the K/V heads never repeated.  On a kernel tier
        such a pool is read by a kernel of its own
        (``paged_attend_decode_grouped``) that sums in another order, so no
        bit-parity is owed and the products are plain einsums.  A table
        wider than ``_GROUPED_CHUNK_TOKENS`` (and a
        whole number of such pieces) is gathered a piece at a time, the
        pieces joined by the online-softmax recurrence: the gather and its
        transposed copy are held for one piece, not for the whole width
        (4.3 GB at 32 slots x 16 384 tokens x 8 heads x 128, ISSUE 33)."""
        b, h, d = q.shape
        hkv = self.k.shape[3]
        nb = self.block_tables.shape[1]
        t_max = nb * self.block_size
        per = _GROUPED_CHUNK_TOKENS // self.block_size
        if nb <= per or nb % per:
            kb = jnp.take(self.k[layer], self.block_tables, axis=0)
            vb = jnp.take(self.v[layer], self.block_tables, axis=0)
            return _grouped_attend(q, kb.reshape(b, t_max, hkv, d),
                                   vb.reshape(b, t_max, hkv, d), positions)
        if h % hkv:
            raise ValueError(f"{h} query heads over {hkv} K/V heads")
        t = per * self.block_size
        qg = (q.astype(jnp.float32) * d ** -0.5).reshape(b, hkv, h // hkv, d)

        def piece(c, carry):
            m, l, acc = carry
            rows = jax.lax.dynamic_slice_in_dim(self.block_tables, c * per,
                                                per, axis=1)
            kb = jnp.take(self.k[layer], rows, axis=0).reshape(b, t, hkv, d)
            vb = jnp.take(self.v[layer], rows, axis=0).reshape(b, t, hkv, d)
            s = jnp.einsum("bgrd,btgd->bgrt", qg.astype(kb.dtype), kb,
                           preferred_element_type=jnp.float32)
            valid = (c * t + jnp.arange(t))[None, :] <= positions[:, None]
            valid = valid[:, None, None, :]
            s = jnp.where(valid, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # a piece wholly past a slot's context: exp(0) = 1 a key
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            ctx = jnp.einsum("bgrt,btgd->bgrd", p.astype(vb.dtype), vb,
                             preferred_element_type=jnp.float32)
            return (m_new, l * corr + jnp.sum(p, axis=-1),
                    acc * corr[..., None] + ctx)

        shape = (b, hkv, h // hkv)
        _, l, acc = jax.lax.fori_loop(
            0, nb // per, piece,
            (jnp.full(shape, _NEG_INF, jnp.float32),
             jnp.zeros(shape, jnp.float32),
             jnp.zeros((*shape, d), jnp.float32)))
        return (acc / l[..., None]).reshape(b, h, d).astype(q.dtype)


def prefill_write_form(heads: int) -> str:
    """How :meth:`PagedKVCache.write_prefill` writes a prompt into a pool of
    ``heads`` K/V heads: ``"token_rows"`` — a token row at a time,
    ``(block, offset)`` a row, as :meth:`~PagedKVCache.write_decode` does —
    where ``heads`` is fewer than a tile's sublanes, else ``"whole_blocks"``
    — one ``[block_size, H, Dh]`` update a block.  On a v5e XLA copies a
    pool of 2–7 heads to another layout and back around a whole-block
    scatter (four pool-sized copies a prefill: ~20 ms over ``sdar30``'s six
    layers of 4 heads, 1.61 GB a pool, against 1–4 ms for the rows); from 8
    heads on it scatters the blocks in place, 3–4.5x faster than the rows."""
    return "token_rows" if heads < _SUBLANES else "whole_blocks"


def _write_token_rows(pool, layer, x, table_row, block_size):
    """``pool`` with ``x`` ``[1, P_pad, H, Dh]`` written a token row at a
    time at ``pool[layer, table_row[t // block_size], t % block_size]``."""
    p_pad = x.shape[1]
    blk = jnp.repeat(table_row, block_size, total_repeat_length=p_pad)
    off = jnp.tile(jnp.arange(block_size, dtype=jnp.int32),
                   p_pad // block_size)
    return pool.at[layer, blk, off].set(x[0].astype(pool.dtype))


def _grouped_attend(q, kb, vb, positions):
    """``q`` ``[B, H, Dh]`` over each slot's keys and values ``[B, T, Hkv,
    Dh]``, entry ``t`` of them where ``t <= positions[b]``: one masked
    float32 softmax, the K/V heads never repeated."""
    b, h, d = q.shape
    hkv = kb.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} K/V heads")
    qg = (q.astype(jnp.float32) * d ** -0.5).reshape(b, hkv, h // hkv, d)
    s = jnp.einsum("bgrd,btgd->bgrt", qg.astype(kb.dtype), kb,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(kb.shape[1])[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bgrt,btgd->bgrd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, h, d).astype(q.dtype)


def decode_parity(heads: int, head_dim: int, *, block_size: int = 16,
                  max_batch: int = 8, max_context: int = 2048,
                  dtype=jnp.bfloat16, decode_impl: str = "kernel",
                  kv_heads: int | None = None, seed: int = 0) -> dict:
    """One decode-attention step through ``decode_impl`` and through the
    pure-JAX fallback over the same random pools; -> the largest absolute
    difference and the tolerance it is held to.

    The slots sit at ragged positions (first token, a block boundary, a
    boundary of the grouped kernel's steps, the last position of the
    context) with scattered block tables, one slot inactive.  The pool has
    two layers of different values and both paths attend at layer 1, so the
    kernel's layer index is under the check too (ISSUE 26).  ``kv_heads``
    (fewer than ``heads``) makes the pool a grouped one: the grouped kernel
    against the grouped gather (ISSUE 34).  Either pair of paths runs one
    online-softmax recurrence over the same operand types and differs by
    rounding only, so the bound is four rounding steps of the OUTPUT dtype
    at the output's magnitude — the check ``chip_smoke.py`` runs on the
    chip at the served head geometries, and tier-1 runs under the
    interpreter.
    """
    import numpy as np

    nb = blocks_for(max_context, block_size)
    num_blocks = max_batch * nb + 1
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (2, num_blocks, block_size, kv_heads or heads, head_dim)
    k = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    v = jax.random.normal(kv, shape, jnp.float32).astype(dtype)
    q = jax.random.normal(kq, (max_batch, heads, head_dim),
                          jnp.float32).astype(dtype)
    rng = np.random.RandomState(seed)
    edges = [0, block_size - 1, block_size, max_context // 2,
             max_context - 1, max_context // 4 - 1, max_context // 4]
    positions = np.asarray(
        [edges[i % len(edges)] for i in range(max_batch)], np.int32)
    tables = np.zeros((max_batch, nb), np.int32)
    for i, p in enumerate(positions[:-1]):      # last slot stays inactive
        n = int(p) // block_size + 1
        tables[i, :n] = rng.choice(np.arange(1, num_blocks), n,
                                   replace=False)
    positions[-1] = 0

    def attend(impl):
        fn = jax.jit(lambda k, v, t, q, p: PagedKVCache(
            k, v, t, block_size, decode_impl=impl).attend_decode(1, q, p))
        return np.array(fn(k, v, jnp.asarray(tables), q,
                           jnp.asarray(positions)).astype(jnp.float32))

    got, ref = attend(decode_impl), attend("fallback")
    tol = 4 * float(jnp.finfo(dtype).eps) * max(1.0, float(np.abs(ref).max()))
    err = float(np.abs(got - ref).max())
    return {"decode_impl": decode_impl, "heads": heads,
            "kv_heads": kv_heads or heads, "max_context": max_context,
            "head_dim": head_dim, "dtype": jnp.dtype(dtype).name,
            "shape": list(got.shape), "finite": bool(np.isfinite(got).all()),
            "max_abs_err": err, "tolerance": tol,
            "ok": bool(np.isfinite(got).all() and err <= tol)}


class BlockPool:
    """Host-side refcounted allocator over the pool's block ids.

    Block 0 (the null block) is never handed out.  ``alloc`` is
    all-or-nothing: a request that cannot get every block it asked for gets
    none (the scheduler then preempts or defers — partial grants would
    deadlock two half-admitted sequences against each other).

    **Refcounts (ISSUE 17)**: an ``alloc``'d block starts at refcount 1;
    ``acquire`` bumps blocks another holder already owns (the prefix cache
    handing cached blocks to a new request); ``free`` decrements and only
    returns a block to the free list when its count reaches zero — so
    evicting or preempting ONE holder of a shared prefix never invalidates
    another.  The free *set* mirrors the free stack for O(1) double-free
    detection (the old ``b in list`` scan was O(pool) per freed block)."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() -> low ids
        self._free_set = set(self._free)
        self._refs: dict[int, int] = {}  # held block -> holder count

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def ref(self, block: int) -> int:
        """Current holder count of ``block`` (0 = on the free list)."""
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> list[int] | None:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._free_set.discard(b)
            self._refs[b] = 1
        return out

    def acquire(self, blocks) -> None:
        """Bump the refcount of blocks another holder already owns (they
        must be live — acquiring a free block would hand out K/V nobody is
        keeping coherent)."""
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"acquiring block {b} outside pool "
                                 f"(1..{self.num_blocks - 1})")
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"acquiring free block {b} (acquire only "
                                 f"bumps blocks a holder already owns)")
            self._refs[b] += 1

    def free(self, blocks) -> None:
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"freeing block {b} outside pool "
                                 f"(1..{self.num_blocks - 1})")
            if b in self._free_set or b not in self._refs:
                raise ValueError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)
                self._free_set.add(b)


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks a sequence of ``n_tokens`` occupies (ceil division)."""
    return -(-n_tokens // block_size)
