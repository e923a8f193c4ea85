"""Hybrid decoder LM for serving: a per-layer pattern of mixers.

Every layer is ``x <- x + mixer(RMSNorm(x))`` with ONE mixer, chosen by the
layer's letter in ``pattern`` (the Nemotron-H family's
``hybrid_override_pattern``):

- ``M``: a Mamba-2 mixer (:mod:`theanompi_tpu.ops.mamba2`): a fixed-size
  recurrent state per sequence, no K/V;
- ``E``: dropless top-k latent experts with a shared expert
  (:class:`theanompi_tpu.ops.moe.DroplessMoE`), of which this process may
  hold a share (``experts_held``); its grouped products go through
  ``lax.ragged_dot`` or, where a serving engine resolves so, the kernel of
  :mod:`theanompi_tpu.ops.pallas_grouped_matmul`;
- ``*``: causal attention with grouped K/V heads and no positional term
  (:class:`theanompi_tpu.ops.attention.GroupedQueryAttention`): paged K/V.

then a final RMSNorm and an untied head; no bias except the convolution's.
``vocab`` is the number of vocabulary rows held (embedding and head alike).

**Serving only.**  The model exposes what
:class:`theanompi_tpu.serving.engine.InferenceEngine` calls —
``apply_prefill``, ``apply_decode``, ``apply_logits`` and ``cache_spec()``,
which says which layers hold paged K/V at how many heads and which hold
per-slot state of what shapes — and ``loss_fn`` refuses: the chunked scan
has no backward here.  Device scopes: ``embed``, ``mamba``, ``moe.route``,
``moe.experts``, ``moe.shared``, ``attn``, ``head``.

    tmserve --modelfile theanompi_tpu.models.hybrid_lm --modelclass HybridLM \\
        --set pattern="'MEM*E'" --set dim=256 ...
"""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp

from theanompi_tpu.models.contract import Model
from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops.attention import GroupedQueryAttention
from theanompi_tpu.ops.mamba2 import Mamba2
from theanompi_tpu.ops.moe import DroplessMoE

_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


class HybridLM(Model):
    default_config = {
        "pattern": "MEM*E",
        "dim": 256,
        "vocab": 1024,
        "seq_len": 256,
        "norm_eps": 1e-5,
        # ``*``
        "heads": 8,
        "kv_heads": 2,
        "head_dim": 32,
        "attn_impl": "auto",
        # ``M``
        "mamba_heads": 16,
        "mamba_head_dim": 32,
        "state_size": 32,
        "n_groups": 4,
        "conv_kernel": 4,
        "chunk_size": 128,
        # ``E``: the router is ``n_experts`` wide whatever share is held
        "n_experts": 16,
        "experts_held": None,
        "top_k": 4,
        "latent": 64,
        "expert_dim": 128,
        "shared_dim": 256,
        "route_scale": 1.0,
        # the dtype the engine keeps the weights in ("bf16" or "fp32")
        "weights": "bf16",
        "verbose": False,
    }

    def __init__(self, config=None):
        super().__init__(config)
        cfg = self.config
        bad = set(cfg["pattern"]) - set(_KINDS)
        if bad or not cfg["pattern"]:
            raise ValueError(f"pattern {cfg['pattern']!r}: letters are "
                             f"{sorted(_KINDS)} (M Mamba-2, E experts, "
                             f"* attention)")
        #: the dtype a serving engine holds the weights in
        self.weight_dtype = (jnp.bfloat16 if cfg["weights"] == "bf16"
                             else jnp.float32)
        self._norm = L.RMSNorm(eps=cfg["norm_eps"])
        held = cfg["experts_held"]
        self._mixers = {
            "mamba": Mamba2(cfg["dim"], cfg["mamba_heads"],
                            cfg["mamba_head_dim"], cfg["state_size"],
                            cfg["n_groups"], cfg["conv_kernel"],
                            cfg["chunk_size"], cfg["norm_eps"]),
            "moe": DroplessMoE(cfg["dim"], cfg["n_experts"], cfg["top_k"],
                               cfg["latent"], cfg["expert_dim"],
                               cfg["shared_dim"], float(cfg["route_scale"]),
                               tuple(held) if held is not None else None),
            "attn": GroupedQueryAttention(cfg["dim"], cfg["heads"],
                                          cfg["kv_heads"], cfg["head_dim"],
                                          cfg["attn_impl"]),
        }
        #: (param-tree name, kind) per layer, in order
        self.layers = [(f"{i:02d}_{_KINDS[c]}", _KINDS[c])
                       for i, c in enumerate(cfg["pattern"])]

    @property
    def expert_layer(self):
        """The ``E`` layers' mixer (they share one), None without an ``E``."""
        kinds = [k for _, k in self.layers]
        return self._mixers["moe"] if "moe" in kinds else None

    @property
    def expert_products(self) -> int:
        """Grouped products in one forward pass: two an ``E`` layer."""
        return 2 * [k for _, k in self.layers].count("moe")

    def set_expert_products(self, products: str) -> None:
        """What multiplies in the expert layers from now on
        (:class:`DroplessMoE`'s ``products``).  A serving engine calls this
        once, before it builds a program."""
        self._mixers["moe"] = dataclasses.replace(self._mixers["moe"],
                                                  products=products)

    def build_data(self):
        # no dataset: serving draws its own ids; the CLI reads ``vocab``
        return types.SimpleNamespace(vocab=int(self.config["vocab"]))

    def cache_spec(self) -> dict:
        """What a serving cache must hold for this model: ``kv`` — paged
        K/V over the attention layers only; ``state`` — per slot and per
        ``M`` layer, name -> (shape, dtype); ``state_layers`` their count."""
        cfg = self.config
        kinds = [k for _, k in self.layers]
        return {"kv": {"layers": max(kinds.count("attn"), 1),
                       "heads": cfg["kv_heads"], "head_dim": cfg["head_dim"]},
                "state": (self._mixers["mamba"].state_shapes()
                          if "mamba" in kinds else {}),
                "state_layers": kinds.count("mamba")}

    # -- parameters ------------------------------------------------------------
    def init_params(self, rng):
        cfg = self.config
        keys = jax.random.split(rng, len(self.layers) + 2)
        w02 = init_lib.normal(0.02)
        d = cfg["dim"]
        params = {"embed": {"w": w02(keys[0], (cfg["vocab"], d))},
                  "norm_f": self._norm.init(None, (d,))[0],
                  "head": {"w": w02(keys[1], (d, cfg["vocab"]))}}
        for (name, kind), k in zip(self.layers, keys[2:]):
            params[name] = {"norm": self._norm.init(None, (d,))[0],
                            "mixer": self._mixers[kind].init(k, (d,))[0]}
        return params, {}

    def loss_fn(self, params, state, batch, rng, train: bool):
        raise NotImplementedError(
            "HybridLM is serving-only: the Mamba-2 chunked scan has no "
            "backward path here and the dropless expert layer no balance "
            "loss; train the plain TransformerLM, or serve this model "
            "through tmserve / InferenceEngine")

    # -- the spine ---------------------------------------------------------------
    def _normed(self, p, x):
        return self._norm.apply(p["norm"], {}, x)[0]

    @jax.named_scope("head")
    def _head_logits(self, cp, x):
        x, _ = self._norm.apply(cp["norm_f"], {}, x)
        return (x @ cp["head"]["w"].astype(x.dtype)).astype(jnp.float32)

    @jax.named_scope("embed")
    def _embed(self, cp, tokens):
        return jnp.take(cp["embed"]["w"], tokens, axis=0).astype(
            self.precision.compute_dtype)

    def _attn_prefill(self, p, u, cache, li, table_row):
        attn = self._mixers["attn"]
        with jax.named_scope("attn"):
            q, k, v = attn.project_qkv(p, u[None])
            if cache is not None:
                cache = cache.write_prefill(li, k, v, table_row)
            ctx = attn.attend(q, k, v)
            return attn.project_out(p, ctx.reshape(u.shape[0], -1)), cache

    def apply_prefill(self, params, state, kv_cache, table_row, tokens,
                      true_len=None, slot=None):
        """One sequence's prompt: ``tokens`` ``[1, P_pad]`` end-padded,
        ``true_len`` its real length (None = all of it), ``slot`` the batch
        slot whose recurrent state the prompt leaves behind.  -> (logits
        ``[1, P_pad, V]`` fp32, cache').  Causal attention keeps padding
        out of real positions by itself; the ``M`` layers are told
        ``true_len``.  ``kv_cache=None`` runs the same spine with nothing
        kept (:meth:`apply_logits`)."""
        del state
        cp = self.precision.cast_to_compute(params)
        toks = tokens[0]
        if true_len is None:
            true_len = jnp.int32(toks.shape[0])
        x = self._embed(cp, toks)
        n_kv = n_state = 0
        for name, kind in self.layers:
            p = cp[name]
            u = self._normed(p, x)
            if kind == "mamba":
                y, s = self._mixers[kind].prefill(p["mixer"], u, true_len)
                if kv_cache is not None:
                    with jax.named_scope("mamba"):  # the state's write-back
                        kv_cache = kv_cache.write_state(n_state, s, slot)
                n_state += 1
            elif kind == "moe":
                y, _ = self._mixers[kind].apply_tokens(p["mixer"], u)
            else:
                y, kv_cache = self._attn_prefill(p["mixer"], u, kv_cache,
                                                 n_kv, table_row)
                n_kv += 1
            x = x + y
        return self._head_logits(cp, x)[None], kv_cache

    def apply_logits(self, params, state, tokens):
        """Full-sequence forward, ``tokens`` ``[B, T]`` -> logits
        ``[B, T, V]``, nothing cached: what incremental decoding is
        compared against."""
        rows = [self.apply_prefill(params, state, None, None, t[None])[0][0]
                for t in tokens]
        return jnp.stack(rows)

    def apply_decode(self, params, state, kv_cache, positions, tokens):
        """One token for each slot of the fixed batch: ``tokens`` ``[B]`` at
        ``positions`` ``[B]``.  -> (logits ``[B, V]`` fp32, cache', stats);
        ``stats`` sums the expert layers' ``local_hits`` and takes the
        largest ``load_peak`` over them, counting slots at ``positions > 0``
        (an inactive slot rides along at position 0 on the null block)."""
        del state
        cp = self.precision.cast_to_compute(params)
        attn = self._mixers["attn"]
        active = positions > 0
        hits, peak = jnp.int32(0), jnp.int32(0)
        x = self._embed(cp, tokens)
        n_kv = n_state = 0
        for name, kind in self.layers:
            p = cp[name]
            u = self._normed(p, x)
            if kind == "mamba":
                y, pools = self._mixers[kind].decode(p["mixer"], u,
                                                     kv_cache.state, n_state)
                kv_cache = dataclasses.replace(kv_cache, state=pools)
                n_state += 1
            elif kind == "moe":
                y, st = self._mixers[kind].apply_tokens(p["mixer"], u,
                                                        active=active)
                hits = hits + st["local_hits"]
                peak = jnp.maximum(peak, st["load_peak"])
            else:
                with jax.named_scope("attn"):
                    q, k, v = attn.project_qkv(p["mixer"], u[:, None])
                    kv_cache = kv_cache.write_decode(n_kv, k[:, 0], v[:, 0],
                                                     positions)
                    ctx = kv_cache.attend_decode(n_kv, q[:, 0], positions)
                    y = attn.project_out(p["mixer"],
                                         ctx.reshape(ctx.shape[0], -1))
                n_kv += 1
            x = x + y
        stats = {"moe_local_hits": hits, "moe_load_peak": peak}
        return self._head_logits(cp, x), kv_cache, stats

    # -- what the serving entry points ask of a model ------------------------------
    def attention_impl(self, t: int) -> str:
        from theanompi_tpu.ops.attention import resolve_attn_impl

        return resolve_attn_impl(self.config["attn_impl"], t,
                                 self.config["head_dim"])

    def resolved_paths(self) -> dict:
        """``state_update``: what runs an ``M`` layer's decode state update
        (``kernel`` | ``plain``), where the pattern has such a layer."""
        paths = {"attention": self.attention_impl(self.config["seq_len"]),
                 "experts": self._mixers["moe"].products}
        if "mamba" in [k for _, k in self.layers]:
            paths["state_update"] = self._mixers["mamba"].state_update_impl()
        return paths
