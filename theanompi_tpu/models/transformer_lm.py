"""Decoder-only transformer LM with dp × tp × sp sharding.

Beyond-reference extension (SURVEY.md §5 marks long-context absent upstream)
that exercises the framework's full parallelism surface: data parallelism
(the rules), tensor parallelism (Megatron-style column/row splits over the
``model`` axis — :mod:`theanompi_tpu.parallel.tensor`), and sequence/context
parallelism (ring attention over the ``seq`` axis —
:mod:`theanompi_tpu.parallel.ring_attention`), all inside one BSP step.

Config: ``dim``/``heads``/``n_layers``/``seq_len``; ``seq_parallel=True``
shards batches ``P(data, seq)`` and adds ``seq`` to the gradient reduction.
Trains on PTB (or the synthetic bigram stream) like the LSTM LM.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from theanompi_tpu.models.contract import SupervisedModel
from theanompi_tpu.models.lstm import PTBData
from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import quant
from theanompi_tpu.ops.attention import MultiHeadAttention, PositionEmbedding
from theanompi_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
from theanompi_tpu.parallel.tensor import (
    TP_RULES,
    ColumnParallelDense,
    RowParallelDense,
    specs_from_rules,
)
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class _Block(L.Layer):
    """Pre-norm transformer block: LN→MHA→res, LN→FFN→res.

    The FFN half is a hook (``_ffn_subs``/``_apply_ffn``) so variants
    (:class:`_MoEBlock`) swap only that segment instead of copying the
    residual/LN/dropout wiring."""

    dim: int
    heads: int
    dropout: float = 0.0
    attn_impl: str = "auto"

    def _ffn_subs(self):
        w02 = init_lib.normal(0.02)
        return (
            ("up", ColumnParallelDense(4 * self.dim, w_init=w02)),
            ("down", RowParallelDense(self.dim, w_init=w02)),
        )

    def _subs(self):
        return (
            ("ln1", L.LayerNorm()),
            ("attn", MultiHeadAttention(self.dim, self.heads, causal=True,
                                        impl=self.attn_impl)),
            ("ln2", L.LayerNorm()),
            *self._ffn_subs(),
        )

    def _apply_ffn(self, subs, params, state, h, train):
        """-> (h, ffn_state); the MLP default carries no state."""
        h, _ = subs["up"].apply(params["up"], {}, h)
        h = jax.nn.gelu(h)
        h, _ = subs["down"].apply(params["down"], {}, h)
        return h, {}

    def init(self, key, in_shape):
        params, state = {}, {}
        subs = self._subs()
        ffn_names = {n for n, _ in self._ffn_subs()}
        keys = jax.random.split(key, len(subs))
        shape = tuple(in_shape)  # chained through the FFN segment only
        for (name, layer), k in zip(subs, keys):
            p, s, out = layer.init(k, shape if name in ffn_names else in_shape)
            if name in ffn_names:
                shape = out
            if p:
                params[name] = p
            if s:
                state[name] = s
        return params, state, tuple(in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        subs = dict(self._subs())
        rngs = (
            jax.random.split(rng, 2) if rng is not None else (None, None)
        )
        drop = L.Dropout(self.dropout)

        # device scopes (ISSUE 25): every op of a block carries
        # block/attn or block/mlp in its name, each half with its LayerNorm
        with jax.named_scope("block"):
            with jax.named_scope("attn"):
                h, _ = subs["ln1"].apply(params["ln1"], {}, x)
                h, _ = subs["attn"].apply(params["attn"], {}, h, train=train)
                h, _ = drop.apply({}, {}, h, train=train, rng=rngs[0])
                x = x + h
            with jax.named_scope("mlp"):
                h, _ = subs["ln2"].apply(params["ln2"], {}, x)
                h, ffn_state = self._apply_ffn(subs, params, state, h, train)
                h, _ = drop.apply({}, {}, h, train=train, rng=rngs[1])
                return x + h, ffn_state

    # -- serving path (ISSUE 6) ----------------------------------------------
    # Both steps reuse the training block's exact sub-layers (same params,
    # same LN/residual wiring, dropout off); only the attention context
    # comes from the duck-typed paged KV cache the serving engine passes in
    # (:class:`theanompi_tpu.serving.kv_cache.PagedKVCache` — models never
    # import serving, so the dependency edge stays serving -> models).

    def prefill_step(self, params, x, cache, layer_idx, table_row):
        """Full-prompt forward of one block: writes this layer's K/V into
        the cache, attends causally *within* the prompt via the training
        attention dispatch (pallas flash on TPU when the shape gate admits).
        ``x`` ``[1, P_pad, D]`` -> (y, cache')."""
        subs = dict(self._subs())
        attn = subs["attn"]
        with jax.named_scope("block"):
            with jax.named_scope("attn"):
                h, _ = subs["ln1"].apply(params["ln1"], {}, x)
                q, k, v = attn.project_qkv(params["attn"], h)
                cache = cache.write_prefill(layer_idx, k, v, table_row)
                ctx = attn.attend(q, k, v)
                h = attn.project_out(
                    params["attn"], ctx.reshape(x.shape[0], x.shape[1], -1))
                x = x + h
            return self._serve_mlp(subs, params, x), cache

    def prefill_suffix_step(self, params, x, cache, layer_idx, suffix_row,
                            full_row, prefix_len):
        """Partial-prefill forward of one block (ISSUE 17): ``x``
        ``[1, S_pad, D]`` holds only the UNCACHED suffix (absolute
        positions ``prefix_len..``); this layer's suffix K/V is written
        into ``suffix_row``'s blocks and the queries attend over the
        sequence's FULL row — the cached-prefix blocks included — via the
        paged gather.  -> (y, cache')."""
        subs = dict(self._subs())
        attn = subs["attn"]
        with jax.named_scope("block"):
            with jax.named_scope("attn"):
                h, _ = subs["ln1"].apply(params["ln1"], {}, x)
                q, k, v = attn.project_qkv(params["attn"], h)
                cache = cache.write_prefill(layer_idx, k, v, suffix_row)
                ctx = cache.attend_prefill(layer_idx, q, full_row,
                                           prefix_len)
                h = attn.project_out(
                    params["attn"], ctx.reshape(x.shape[0], x.shape[1], -1))
                x = x + h
            return self._serve_mlp(subs, params, x), cache

    def decode_step(self, params, x, cache, layer_idx, positions):
        """One-token incremental forward of one block: appends this layer's
        K/V at ``positions`` and attends over the cached context.
        ``x`` ``[B, 1, D]``, ``positions`` ``[B]`` -> (y, cache')."""
        subs = dict(self._subs())
        attn = subs["attn"]
        with jax.named_scope("block"):
            with jax.named_scope("attn"):
                h, _ = subs["ln1"].apply(params["ln1"], {}, x)
                q, k, v = attn.project_qkv(params["attn"], h)
                cache = cache.write_decode(layer_idx, k[:, 0], v[:, 0],
                                           positions)
                ctx = cache.attend_decode(layer_idx, q[:, 0], positions)
                h = attn.project_out(
                    params["attn"], ctx.reshape(x.shape[0], 1, -1))
                x = x + h
            return self._serve_mlp(subs, params, x), cache

    @jax.named_scope("mlp")
    def _serve_mlp(self, subs, params, x):
        """The FFN half of a serving step: LN, FFN, residual."""
        h, _ = subs["ln2"].apply(params["ln2"], {}, x)
        h, _ = self._apply_ffn(subs, params, {}, h, False)
        return x + h


@dataclasses.dataclass(frozen=True)
class _MoEBlock(_Block):
    """:class:`_Block` with a switch-routed MoE FFN; the MoE's load-balance
    aux loss rides in state under ``moe.aux``."""

    n_experts: int = 8
    capacity_factor: float = 1.25

    def _ffn_subs(self):
        from theanompi_tpu.ops.moe import MoEFFN

        return (("moe", MoEFFN(self.dim, self.n_experts,
                               capacity_factor=self.capacity_factor)),)

    def _apply_ffn(self, subs, params, state, h, train):
        h, moe_state = subs["moe"].apply(
            params["moe"], state.get("moe", {}), h, train=train
        )
        return h, {"moe": moe_state}


class TransformerLM(SupervisedModel):
    default_config = {
        "batch_size": 8,
        "n_epochs": 10,
        "lr": 1e-3,
        "momentum": 0.9,
        "grad_clip": 1.0,
        "seq_len": 256,
        "dim": 256,
        "heads": 8,
        "n_layers": 4,
        "dropout": 0.1,
        "seq_parallel": False,
        # "auto": pallas flash attention when shapes allow (TPU-compiled,
        # interpreted on CPU); "blockwise"/"pallas" force a path
        "attn_impl": "auto",
        # lax.scan unroll factors — a per-op profile at V=32k attributed
        # ~27 % of the step to while self-time (measured in round r4 under
        # jax 0.4.3x; not re-measured), all of it the fused-loss chunk
        # scans in this base model (the trunk is a Python-loop
        # Sequential, not a scan).  loss_unroll lets XLA
        # software-pipeline the loss chunks; layers_unroll applies ONLY to
        # PipelineTransformerLM's stacked-layer scan.  1 = the r4 behavior.
        "layers_unroll": 1,
        "loss_unroll": 1,
        # "stream" (or any stream_sources/stream_dir config) switches the
        # data plane to the checkpointable multi-source token stream
        # (models/data/stream.py); default stays the PTB-style chopped set
        "dataset": "ptb",
    }

    def build_data(self):
        cfg = self.config
        if (cfg.get("dataset") == "stream" or cfg.get("stream_sources")
                or cfg.get("stream_dir")):
            from theanompi_tpu.models.data.stream import StreamTokenDataset

            if cfg.get("stream_dir") and not cfg.get("stream_sources"):
                import os

                root = cfg["stream_dir"]
                cfg = dict(cfg)
                cfg["stream_sources"] = [
                    {"name": d, "path": os.path.join(root, d)}
                    for d in sorted(os.listdir(root))
                    if os.path.isdir(os.path.join(root, d))
                ]
            return StreamTokenDataset(cfg)
        return PTBData(self.config)

    def _make_block(self) -> L.Layer:
        """Block factory hook — MoE variant overrides with :class:`_MoEBlock`."""
        cfg = self.config
        return _Block(cfg["dim"], cfg["heads"], dropout=cfg["dropout"],
                      attn_impl=cfg["attn_impl"])

    def build_net(self):
        """The TRUNK only (embed … final LN).  The LM head lives outside the
        Sequential so the loss can fuse the head matmul into a chunked
        cross entropy (``ops.losses.fused_lm_xent``) instead of
        materializing ``[B, T, V]`` fp32 logits — ruinous at real vocab.

        **Checkpoint format break** (documented per ADVICE r3 #3): the head
        moved from the Sequential's trailing Dense (leaf
        ``NN_dense/{w,b}``) to a top-level ``head`` key, so transformer
        checkpoints written before this change no longer restore.  No shim
        is kept — prior-round checkpoints were test artifacts, and the
        restore fails loudly (``KeyError: 'head/w'``) rather than silently
        mismapping."""
        cfg = self.config
        layers: list[L.Layer] = [
            L.Embedding(self.data.vocab, cfg["dim"],
                        w_init=init_lib.normal(0.02)),
            PositionEmbedding(cfg["seq_len"], cfg["dim"]),
        ]
        for _ in range(cfg["n_layers"]):
            layers.append(self._make_block())
        layers.append(L.LayerNorm())
        self._head = L.Dense(self.data.vocab, w_init=init_lib.glorot_normal)
        return L.Sequential(layers), (cfg["seq_len"],)

    def init_params(self, rng):
        k_trunk, k_head = jax.random.split(rng)
        params, state, out_shape = self.net.init(k_trunk, self.in_shape)
        self._out_shape = out_shape
        head_p, _, _ = self._head.init(k_head, out_shape)
        # flat Sequential tree + a top-level "head" key: TP rules and tests
        # address trunk leaves by their Sequential names unchanged
        params["head"] = head_p
        return params, state

    def apply_trunk(self, params, state, x, *, train, rng):
        """-> (hidden states [B, T, D], new_state); variants (pipeline)
        override this, keeping head+loss in one shared path."""
        trunk = {k: v for k, v in params.items() if k != "head"}
        return self.net.apply(trunk, state, x, train=train, rng=rng)

    def fused_loss_enabled(self) -> bool:
        mode = self.config.get("fused_loss", "auto")
        if mode == "auto":
            return self.data.vocab >= 8192
        return bool(mode)

    def attention_impl(self, t: int) -> str:
        """The attention path a length-``t`` sequence takes on an
        unsharded seq axis: ``pallas`` (compiled flash kernels),
        ``pallas_interpret`` (the same kernels under the interpreter —
        ``flash_attention``'s off-TPU mode) or ``blockwise``."""
        from theanompi_tpu.ops.attention import resolve_attn_impl

        cfg = self.config
        impl = resolve_attn_impl(cfg["attn_impl"], t,
                                 cfg["dim"] // cfg["heads"])
        if impl == "pallas" and jax.default_backend() != "tpu":
            impl = "pallas_interpret"
        return impl

    @property
    def weight_dtype(self):
        """The dtype a serving engine holds the weights in: the one the
        serving entry points read them in (``cast_to_compute``), so the
        engine rounds the tree once and their ``recast`` of it lowers to
        nothing.  Training never reads this: a trainer keeps fp32 masters."""
        return self.precision.compute_dtype

    def resolved_paths(self) -> dict:
        cfg = self.config
        attn = ("ring" if cfg["seq_parallel"]
                else self.attention_impl(cfg["seq_len"]))
        return {**super().resolved_paths(), "attention": attn,
                "fused_loss": self.fused_loss_enabled()}

    # -- serving path (ISSUE 6) ----------------------------------------------
    def _serving_layers(self):
        """(name, layer) pairs of the trunk Sequential, in order — the
        serving engine drives the SAME param tree the trainer checkpoints,
        so a verified restore plugs straight in."""
        if self.net is None or not hasattr(self.net, "layers"):
            raise NotImplementedError(
                f"{type(self).__name__} has no serving decode path (the "
                f"pipeline variant stacks its blocks for GPipe; export the "
                f"checkpoint to the plain TransformerLM layout to serve it)")
        return [(f"{i:02d}_{layer.name}", layer)
                for i, layer in enumerate(self.net.layers)]

    def cache_spec(self) -> dict:
        """What a serving cache must hold for this model (the engine builds
        its cache from this, for every model alike): paged K/V at every
        layer, one K/V head per query head, no per-slot state."""
        cfg = self.config
        return {"kv": {"layers": cfg["n_layers"], "heads": cfg["heads"],
                       "head_dim": cfg["dim"] // cfg["heads"]},
                "state": {}, "state_layers": 0}

    @jax.named_scope("head")
    def _head_logits(self, cp, h):
        y = quant.matmul_any(h, cp["head"]["w"])
        if "b" in cp["head"]:
            y = y + cp["head"]["b"].astype(h.dtype)
        return y.astype(jnp.float32)

    def apply_logits(self, params, state, tokens):
        """Full-sequence forward to per-position logits ``[B, T, V]`` —
        the batched reference the serving parity/smoke tests compare
        incremental decode against (and the plain eval entry point the
        fused loss path deliberately avoids materializing in training)."""
        cp = self.precision.cast_to_compute(params)
        h, _ = self.apply_trunk(cp, state, tokens, train=False, rng=None)
        return self._head_logits(cp, h)

    def apply_prefill(self, params, state, kv_cache, table_row, tokens):
        """Prompt prefill for ONE sequence: ``tokens`` ``[1, P_pad]`` (end-
        padded to a whole number of cache blocks — causal masking keeps the
        padding out of every real position's context), ``table_row`` the
        sequence's block table.  -> (logits ``[1, P_pad, V]`` fp32, cache').
        """
        del state
        with jax.named_scope("recast"):
            cp = self.precision.cast_to_compute(params)
        x, li = None, 0
        for name, layer in self._serving_layers():
            p = cp.get(name, {})
            if isinstance(layer, L.Embedding):
                x, _ = layer.apply(p, {}, tokens)
            elif isinstance(layer, PositionEmbedding):
                x, _ = layer.apply(p, {}, x)
            elif isinstance(layer, _Block):
                x, kv_cache = layer.prefill_step(p, x, kv_cache, li,
                                                 table_row)
                li += 1
            else:
                x, _ = layer.apply(p, {}, x)
        return self._head_logits(cp, x), kv_cache

    def apply_prefill_partial(self, params, state, kv_cache, suffix_row,
                              full_row, tokens, prefix_len):
        """Partial prefill (ISSUE 17): forward ONLY the uncached suffix of
        one sequence — ``tokens`` ``[1, S_pad]`` are the prompt's tokens
        from absolute position ``prefix_len`` on (end-padded to whole
        cache blocks) — while attending over the cached-prefix blocks the
        radix cache matched into ``full_row``.  ``suffix_row`` names the
        fresh blocks the suffix K/V lands in.  -> (logits ``[1, S_pad, V]``
        fp32, cache').

        Position embeddings index at ``prefix_len + s`` (clipped into the
        table for end-padding positions, whose lanes are masked garbage by
        the same causal contract as full prefill's end-padding)."""
        del state
        with jax.named_scope("recast"):
            cp = self.precision.cast_to_compute(params)
        x, li = None, 0
        for name, layer in self._serving_layers():
            p = cp.get(name, {})
            if isinstance(layer, L.Embedding):
                x, _ = layer.apply(p, {}, tokens)
            elif isinstance(layer, PositionEmbedding):
                with jax.named_scope("embed"):
                    idx = jnp.clip(prefix_len + jnp.arange(tokens.shape[1]),
                                   0, p["pos"].shape[0] - 1)
                    pos = jnp.take(p["pos"], idx, axis=0).astype(x.dtype)
                    x = x + pos[None]
            elif isinstance(layer, _Block):
                x, kv_cache = layer.prefill_suffix_step(
                    p, x, kv_cache, li, suffix_row, full_row, prefix_len)
                li += 1
            else:
                x, _ = layer.apply(p, {}, x)
        return self._head_logits(cp, x), kv_cache

    def apply_decode(self, params, state, kv_cache, positions, tokens):
        """One incremental decode step for a fixed batch of sequences:
        ``tokens`` ``[B]`` (the token AT ``positions``), ``positions``
        ``[B]`` 0-based.  Appends each layer's K/V to the paged cache and
        attends over the cached context.  -> (logits ``[B, V]`` fp32,
        cache').  Inactive batch slots ride along with their block tables
        pointed at the cache's reserved null block."""
        del state
        # the is_leaf fence keeps the precision policy out of int8
        # QuantizedTensor leaves (their fp32 scales must not cast to the
        # compute dtype) — the serving fast path feeds them through here
        # to the fused matmul kernel (ISSUE 18)
        with jax.named_scope("recast"):
            cp = self.precision.cast_to_compute(
                params,
                is_leaf=lambda x: isinstance(x, quant.QuantizedTensor))
        x, li = None, 0
        for name, layer in self._serving_layers():
            p = cp.get(name, {})
            if isinstance(layer, L.Embedding):
                x, _ = layer.apply(p, {}, tokens)
                x = x[:, None, :]
            elif isinstance(layer, PositionEmbedding):
                with jax.named_scope("embed"):
                    pos = jnp.take(p["pos"], positions,
                                   axis=0).astype(x.dtype)
                    x = x + pos[:, None, :]
            elif isinstance(layer, _Block):
                x, kv_cache = layer.decode_step(p, x, kv_cache, li,
                                                positions)
                li += 1
            else:
                x, _ = layer.apply(p, {}, x)
        return self._head_logits(cp, x[:, 0, :]), kv_cache

    # -- sharding ------------------------------------------------------------
    def _head_specs(self, params):
        """Head placement: vocab-parallel (Megatron parallel CE) whenever
        the fused loss is on — w ``P(None, model)``, b ``P(model)`` — so
        under TP no rank ever sees more than ``[chunk, V/tp]`` scores.  On
        a size-1 model axis this degrades to replicated, and the plain
        fused/naive paths read the full head."""
        from theanompi_tpu.parallel.mesh import MODEL_AXIS

        if not self.fused_loss_enabled():
            return jax.tree.map(lambda _: P(), params["head"])
        specs = {"w": P(None, MODEL_AXIS)}
        if "b" in params["head"]:
            specs["b"] = P(MODEL_AXIS)
        return specs

    def param_specs(self, params):
        specs = specs_from_rules(params, TP_RULES)
        specs["head"] = self._head_specs(params)
        return specs

    def batch_partition(self) -> P:
        if self.config["seq_parallel"]:
            return P(DATA_AXIS, SEQ_AXIS)
        return P(DATA_AXIS)

    def grad_reduce_axes(self) -> tuple[str, ...]:
        if self.config["seq_parallel"]:
            return (DATA_AXIS, SEQ_AXIS)
        return (DATA_AXIS,)

    def loss_fn(self, params, state, batch, rng, train: bool):
        from theanompi_tpu.ops.losses import fused_lm_xent, fused_lm_xent_vp
        from theanompi_tpu.parallel.mesh import MODEL_AXIS
        from theanompi_tpu.parallel.tensor import axis_bound

        from theanompi_tpu.ops import softmax_cross_entropy, top_k_error

        cp = self.precision.cast_to_compute(params)
        h, new_state = self.apply_trunk(cp, state, batch["x"],
                                        train=train, rng=rng)
        w, b = cp["head"]["w"], cp["head"].get("b")
        if self.fused_loss_enabled():
            unroll = int(self.config.get("loss_unroll", 1) or 1)
            if axis_bound(MODEL_AXIS) and jax.lax.axis_size(MODEL_AXIS) > 1:
                # w/b are this shard's vocab slice (see _head_specs)
                loss, err1, err5 = fused_lm_xent_vp(h, w, b, batch["y"],
                                                    MODEL_AXIS,
                                                    unroll=unroll)
            else:
                loss, err1, err5 = fused_lm_xent(h, w, b, batch["y"],
                                                 unroll=unroll)
        else:
            with jax.named_scope("head"):
                logits, _ = self._head.apply(cp["head"], {}, h)
            with jax.named_scope("loss"):
                loss = softmax_cross_entropy(logits, batch["y"])
                err1 = top_k_error(logits, batch["y"], k=1)
                err5 = (top_k_error(logits, batch["y"], k=5)
                        if logits.shape[-1] >= 5
                        else jnp.zeros((), jnp.float32))
        if self.config.get("l2", 0.0):
            loss = loss + self.config["l2"] * self.l2_sq_norm(params)
        metrics = {"cost": loss, "error": err1, "error_top5": err5,
                   "perplexity": jnp.exp(loss)}
        return loss, (new_state, metrics)


class MoETransformerLM(TransformerLM):
    """Mixture-of-experts LM: dp × tp × **ep** (SURVEY.md-beyond).

    Every block's FFN is a switch-routed :class:`~theanompi_tpu.ops.moe
    .MoEFFN` with ``n_experts`` global experts sharded over the ``model``
    mesh axis (expert parallelism shares the axis with the attention's
    tensor parallelism — the standard pairing).  The Switch load-balance
    auxiliary loss joins the training objective at ``moe_aux_weight``.
    """

    default_config = {
        **TransformerLM.default_config,
        "n_experts": 8,
        "capacity_factor": 1.25,
        "moe_aux_weight": 0.01,
    }

    def _make_block(self) -> L.Layer:
        cfg = self.config
        return _MoEBlock(
            cfg["dim"], cfg["heads"], dropout=cfg["dropout"],
            attn_impl=cfg["attn_impl"], n_experts=cfg["n_experts"],
            capacity_factor=cfg["capacity_factor"],
        )

    def param_specs(self, params):
        from theanompi_tpu.parallel.mesh import MODEL_AXIS

        base = specs_from_rules(params, TP_RULES)
        expert_keys = ("up_w", "up_b", "down_w", "down_b")

        def walk(p_sub, b_sub, in_moe, key):
            if isinstance(p_sub, dict):
                return {k: walk(p_sub[k], b_sub[k], in_moe or k == "moe", k)
                        for k in p_sub}
            if in_moe and key in expert_keys:
                return P(MODEL_AXIS)  # stacked experts shard dim 0
            return b_sub

        return walk(params, base, False, "")

    def loss_fn(self, params, state, batch, rng, train: bool):
        import jax.tree_util as jtu

        loss, (new_state, metrics) = super().loss_fn(
            params, state, batch, rng, train
        )
        auxes = [
            leaf for path, leaf in jtu.tree_flatten_with_path(new_state)[0]
            if getattr(path[-1], "key", None) == "aux"
        ]
        if auxes:
            a = sum(auxes) / len(auxes)
            metrics = {**metrics, "moe_aux": a}
            if train:
                loss = loss + self.config["moe_aux_weight"] * a
        return loss, (new_state, metrics)


class PipelineTransformerLM(TransformerLM):
    """Pipeline-parallel variant: dp × pp × tp (SURVEY.md-beyond, scale
    contract — the composition a real pod LM run needs).

    The ``n_layers`` blocks are *stacked* — every block-param leaf carries a
    leading ``[n_layers, ...]`` axis sharded over the ``pipe`` mesh axis —
    and the forward runs the GPipe collective-permute schedule
    (:func:`theanompi_tpu.parallel.pipeline.pipeline_apply`) with
    ``n_micro`` microbatches.  Embedding/positions/final-LN/head are
    replicated; their cross-pipe gradient correctness comes from the
    pinned-VJP collectives inside ``pipeline_apply``.  With pipe size 1
    (or no mesh) this is numerically the plain stacked transformer.

    **Tensor parallelism composes structurally**: the stacked block leaves
    keep their Megatron column/row specs over ``model`` BEHIND the leading
    ``pipe`` axis (``P(pipe, None, model)`` on a stacked column-parallel
    weight), so inside ``shard_map`` each device holds its pipe-stage's
    slice of its tp-shard, and the blocks' f/g collectives psum over
    ``model`` within every pipe rank exactly as in the unstacked model.
    The two pinned-VJP families compose because they act on disjoint axes:
    pipeline's f/g pin ``pipe`` (stage-0 injection / last-stage output),
    Megatron's f/g pin ``model`` (column inputs / row outputs) — each
    collective is an identity over the other's axis.

    **Sequence parallelism composes too** (VERDICT r3 #5 lifted the old
    refusal): ring attention's ppermutes ride the ``seq`` axis only and the
    GPipe schedule's ride ``pipe`` only, and because every device traces the
    SAME SPMD program, each pipeline schedule step runs the full KV ring
    (and, in reverse, the full backward ring) in lockstep across seq peers
    at every pipe rank — there is no cross-axis hop interleaving to get
    wrong.  The ring's custom VJP pins ``seq`` (dk/dv land home after a
    full lap), pipeline's pins ``pipe``, Megatron's pins ``model``: three
    disjoint-axis families.  Verified by the pp2×sp2 ≡ single-device
    multi-step test (``tests/test_pipeline.py``).
    """

    default_config = {
        **TransformerLM.default_config,
        "n_micro": 4,       # microbatches per step (must divide batch_size)
        "seq_parallel": False,
    }

    def build_net(self):
        cfg = self.config
        t, d = cfg["seq_len"], cfg["dim"]
        self._block = _Block(cfg["dim"], cfg["heads"], cfg["dropout"],
                             attn_impl=cfg["attn_impl"])
        self._embed = L.Embedding(self.data.vocab, d,
                                  w_init=init_lib.normal(0.02))
        self._pos = PositionEmbedding(t, d)
        self._ln_f = L.LayerNorm()
        self._head = L.Dense(self.data.vocab, w_init=init_lib.glorot_normal)
        return None, (t,)

    def init_params(self, rng):
        cfg = self.config
        t, d = cfg["seq_len"], cfg["dim"]
        k_embed, k_pos, k_blocks, k_ln, k_head = jax.random.split(rng, 5)
        pe, _, _ = self._embed.init(k_embed, (t,))
        pp, _, _ = self._pos.init(k_pos, (t, d))
        block_keys = jax.random.split(k_blocks, cfg["n_layers"])

        def one(k):
            p, _, _ = self._block.init(k, (t, d))
            return p

        stacked = jax.vmap(one)(block_keys)  # leaves [n_layers, ...]
        pl_, _, _ = self._ln_f.init(k_ln, (t, d))
        ph, _, _ = self._head.init(k_head, (t, d))
        return {"embed": pe, "pos": pp, "blocks": stacked,
                "ln_f": pl_, "head": ph}, {}

    def param_specs(self, params):
        from theanompi_tpu.parallel.mesh import PIPE_AXIS

        # stacked block leaves shard their leading stage axis over `pipe`;
        # behind it each leaf keeps its Megatron spec over `model` (rule
        # paths are matched as "blocks/attn/q/w" etc., same regexes as the
        # unstacked model)
        tp = specs_from_rules({"blocks": params["blocks"]}, TP_RULES)["blocks"]
        stacked = jax.tree.map(
            lambda spec: P(PIPE_AXIS, *spec),
            tp, is_leaf=lambda x: isinstance(x, P),
        )
        return {
            "embed": jax.tree.map(lambda _: P(), params["embed"]),
            "pos": jax.tree.map(lambda _: P(), params["pos"]),
            "blocks": stacked,
            "ln_f": jax.tree.map(lambda _: P(), params["ln_f"]),
            # vocab-parallel under tp when the fused loss is on
            "head": self._head_specs(params),
        }

    def apply_trunk(self, params, state, x, *, train, rng):
        """The pipelined forward up to the final LN; head+loss stay in the
        shared ``loss_fn`` path (l2 over the pipe-sharded blocks is handled
        by the spec-aware ``l2_sq_norm``)."""
        from theanompi_tpu.parallel.pipeline import pipeline_apply
        from theanompi_tpu.parallel.tensor import axis_bound

        cfg = self.config
        # tensor AND sequence parallelism compose (disjoint pinned-VJP
        # axes — see class docstring); the blocks' ring attention runs its
        # seq-axis KV laps inside every GPipe schedule step
        emb, _ = self._embed.apply(params["embed"], {}, x)
        emb, _ = self._pos.apply(params["pos"], {}, emb)

        def stage_fn(chunk, act, t):
            if rng is None:
                key0 = None
            else:
                key0 = jax.random.fold_in(rng, t)
                if axis_bound("pipe"):
                    key0 = jax.random.fold_in(
                        key0, jax.lax.axis_index("pipe"))

            def one(carry, bp):
                a, key = carry
                kb = None
                if key is not None:
                    key = jax.random.fold_in(key, 7)
                    kb = key
                y, _ = self._block.apply(bp, {}, a, train=train, rng=kb)
                return (y, key), None

            (act, _), _ = jax.lax.scan(
                one, (act, key0), chunk,
                unroll=int(cfg.get("layers_unroll", 1) or 1))
            return act

        h = pipeline_apply(stage_fn, params["blocks"], emb, cfg["n_micro"])
        h, _ = self._ln_f.apply(params["ln_f"], {}, h)
        return h, state
