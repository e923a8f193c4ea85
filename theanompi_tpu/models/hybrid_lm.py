"""Hybrid decoder LM for serving: a per-layer pattern of mixers.

Every layer is ``x <- x + mixer(RMSNorm(x))`` with ONE mixer, chosen by the
layer's letter in ``pattern`` (the Nemotron-H family's
``hybrid_override_pattern``):

- ``M``: a Mamba-2 mixer (:mod:`theanompi_tpu.ops.mamba2`): a fixed-size
  recurrent state per sequence, no K/V;
- ``E``: dropless top-k experts with a shared expert
  (:class:`theanompi_tpu.ops.moe.DroplessMoE`), of which this process may
  hold a share (``experts_held``): behind a ``latent`` projection or
  (``latent=None``) at model width, ``expert_act`` ``relu2`` or
  ``silu_gated``; its grouped products go through ``lax.ragged_dot`` or,
  where a serving engine resolves so, the kernel of
  :mod:`theanompi_tpu.ops.pallas_grouped_matmul`;
- ``*``: causal attention with grouped K/V heads
  (:class:`theanompi_tpu.ops.attention.GroupedQueryAttention`): paged K/V;
  no positional term, or with ``rope_theta`` rotary positions at that
  base (the pool then holds rotated keys) over ``rope_share`` of the head,
  YaRN-scaled with ``rope_yarn``;
- ``w``: the same layer over a band — position ``i`` attends ``i - window <
  j <= i`` — with query heads (``window_heads``) and a rotary rule
  (``window_rope_theta``, ``window_rope_share``, ``window_rope_yarn``) of
  its own and the ``*`` layers' K/V heads.  Its K/V is NOT paged: a ring
  of ``window`` tokens a slot, whatever the context (``cache_spec()``'s
  ``window``), so admission and preemption count the ``*`` layers' blocks
  only.  ``attn_gate`` gives both kinds a sigmoid gate a head on the
  context, read from the layer's input;
- ``-``: a bias-free gated feed-forward of ``ffn_dim``
  (:class:`theanompi_tpu.ops.layers.GatedFFN`);
- ``L``: latent attention (:class:`theanompi_tpu.ops.attention.
  LatentAttention`) of ``heads`` heads: queries through a ``q_lora``
  bottleneck, keys and values through ONE cached row a token — ``c_kv``
  (``kv_lora``) and a rotary key of ``qk_rope`` shared by every head, at
  ``rope_theta``, adjacent pairs with ``rope_interleave`` — decompressed to
  ``qk_nope`` + ``qk_rope`` keys and ``v_head`` values a head.  The paged
  pool holds the rows (``cache_spec()["kv"]["latent"]``); a prefill
  decompresses, a decode step absorbs ``W_kvb`` into its query and its
  context.  No ``*``, ``w``, loop or block diffusion beside it.

``qk_norm`` gives the ``*`` and ``w`` layers a per-head RMSNorm on queries
and keys before rotary (the Qwen3 block's); ``router`` ``"softmax"`` and
``shared_dim`` 0 give the ``E`` layers a softmax router with renormalised
top-k weights and no shared expert (Qwen3-MoE's).

then a final RMSNorm and an untied head; no bias except the convolution's
and the exit gate's.  ``vocab`` is the number of vocabulary rows held
(embedding and head alike).  ``post_norm`` gives every layer a second scale
and norms the mixer's output too: ``x + RMSNorm(mixer(RMSNorm(x)))``.

**A looped stack** (``loops`` > 1): the whole pattern is applied ``loops``
times with the SAME parameters; the final norm closes every step — its
output ``u_t`` is the step's result and the next step's input — and an exit
gate ``lam_t = sigmoid(u_t . w + b)`` (``exit_gate``, float32) gives the
step's exit probability ``p_t = lam_t prod_{j<t} (1 - lam_j)`` (the last
step takes what is left).  The head reads ``u_t*`` at the first step whose
cumulated ``p`` reaches ``exit_threshold`` (at 1.0: the last step, unless a
gate saturates); every step is computed whatever ``t*`` is.  The loop is a
loop of the program (``lax.fori_loop``: a program holds the pattern once),
and each (step, ``*`` layer) has K/V of its own: ``cache_spec()`` asks for
``loops x attention layers`` entries, entry ``t * attention layers + l``
for layer ``l`` of step ``t`` — ``2 x loops x attention layers x kv_heads
x head_dim x itemsize`` bytes of cache a token, which is what
``--num-blocks`` x ``--block-size`` tokens must be sized by.  A pattern with
``M`` or ``w`` refuses ``loops`` > 1 (their slot-owned pools have one entry
a layer).

**Block diffusion** (``block_len`` B; patterns of ``*``, ``E`` and ``-``
only, no loop): positions are cut into blocks of B aligned from position 0,
and a block is generated by passes over all of it at once —
:meth:`apply_block` runs each slot's B positions ``[L, L + B)`` (``L`` the
tokens cached, a multiple of B) against the cached K/V of ``[0, L)`` and
the block's own, bidirectional inside the block, and writes the block's
K/V; :meth:`commit_block` then fixes the ``ceil(B / BLOCK_PASSES)``
masked positions (``mask_id``) whose greedy token is the most probable,
ties to the lower position.  ``BLOCK_PASSES`` passes fix a block whose B
positions start masked; one more pass over its final tokens writes the K/V
the next block reads.  Position ``i``'s logits predict position ``i``'s token (no
shift).  A prefill is block-causal too (position ``i`` sees ``j < (i // B
+ 1) B``) and covers whole blocks only (:meth:`apply_prefill_blocks`).

**Serving only.**  The model exposes what
:class:`theanompi_tpu.serving.engine.InferenceEngine` calls —
``apply_prefill``, ``apply_decode``, ``apply_logits`` and ``cache_spec()``,
which says which layers hold paged K/V at how many heads and which hold
per-slot state of what shapes — and ``loss_fn`` refuses: the chunked scan
has no backward here, nor has the loop a multi-exit loss.  It has no
partial prefill, so the scheduler's prefix cache refuses it.  A prefill's
head reads ONE position (``head_at``: the one that is sampled), not the
bucket's every row; ``apply_logits`` reads them all.  Device
scopes: ``embed``, ``mamba``, ``moe.route``, ``moe.experts``,
``moe.shared``, ``attn``, ``attn.window`` (the gate inside either),
``attn.latent`` (with ``attn.latent.absorb`` and
``attn.latent.decompress`` inside it),
``mlp``, ``loop.exit`` (a step's final norm, gate and read-out), ``head``.

    tmserve --modelfile theanompi_tpu.models.hybrid_lm --modelclass HybridLM \\
        --set pattern="'MEM*E'" --set dim=256 ...
    # a looped stack of attention + gated FFN, rotary, sandwich norm:
    tmserve ... --set pattern="'*-*-'" --set loops=4 --set post_norm=True \\
        --set rope_theta=1e6 --set ffn_dim=704 --num-blocks <tokens / block>
    # window and full attention of different head counts over one cache, a
    # gate a head, partial YaRN rotary, gated experts at model width:
    tmserve ... --set pattern="'*-wEwEwE*E'" --set heads=48 \
        --set window_heads=64 --set window=512 --set attn_gate=True \
        --set rope_theta=5e5 --set rope_share=0.5 \
        --set rope_yarn="{'factor': 64, 'original_max_position': 4096, \
            'beta_fast': 64, 'beta_slow': 1}" --set window_rope_theta=1e4 \
        --set latent=None --set expert_act="'silu_gated'"
"""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from theanompi_tpu.models.contract import Model
from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops.attention import GroupedQueryAttention, LatentAttention
from theanompi_tpu.ops.mamba2 import Mamba2
from theanompi_tpu.ops.moe import DroplessMoE

_KINDS = {"M": "mamba", "E": "moe", "*": "attn", "w": "attn_w", "-": "mlp",
          "L": "mla"}
#: the device scope of each attention kind
_ATTN_SCOPES = {"attn": "attn", "attn_w": "attn.window"}
#: block diffusion's denoising passes a block, the static schedule: half the
#: passes of committing one position a pass, the parallel decoding a block
#: diffusion model is served for
BLOCK_PASSES = 2


def _row_major(cache):
    """``cache`` with its K/V pools held to the layout they arrive in.  A
    loop-carried array's layout is the compiler's to choose, and for a
    prefill's scatter it chose the keys' own head-major order: two copies
    of the whole pools around the loop, 7.5 GB at the served size, where a
    program without a loop is held to its parameters' layout (ISSUE 31)."""
    if cache is None:
        return None
    order = Layout(major_to_minor=tuple(range(cache.k.ndim)))
    return dataclasses.replace(cache, k=with_layout_constraint(cache.k, order),
                               v=with_layout_constraint(cache.v, order))


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
        "rope_theta": None,
        "rope_share": 1.0,
        "rope_yarn": None,
        # ``L``: the query bottleneck, the cached latent, each head's
        # unrotated and rotary key dims, its value dims, the rotary pairing
        "q_lora": 96,
        "kv_lora": 64,
        "qk_nope": 32,
        "qk_rope": 16,
        "v_head": 32,
        "rope_interleave": False,
        # ``*`` and ``w``: a sigmoid gate a head on the context
        "attn_gate": False,
        # ``w``: the band, its query heads (None: ``heads``), its rotary rule
        "window": 512,
        "window_heads": None,
        "window_rope_theta": None,
        "window_rope_share": 1.0,
        "window_rope_yarn": None,
        # ``-``
        "ffn_dim": 512,
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
        "expert_act": "relu2",
        "router": "sigmoid",
        # ``*`` and ``w``: a per-head RMSNorm on q and k before rotary
        "qk_norm": False,
        # block diffusion (None: one token a slot a step): positions a
        # block, the mask token's id
        "block_len": None,
        "mask_id": None,
        # the spine: a second norm a layer, after the mixer; the pattern
        # applied ``loops`` times (``exit_threshold`` is read when > 1)
        "post_norm": False,
        "loops": 1,
        "exit_threshold": 1.0,
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
                             f"* attention, w window attention, - gated "
                             f"FFN, L latent attention)")
        if "L" in cfg["pattern"] and (
                set(cfg["pattern"]) & set("*w") or cfg["loops"] != 1
                or cfg["block_len"] is not None):
            raise ValueError(
                f"pattern {cfg['pattern']!r}, loops={cfg['loops']}, "
                f"block_len={cfg['block_len']}: latent attention's pool of "
                f"rows is the model's one paged pool, one entry a layer")
        if cfg["loops"] < 1 or (cfg["loops"] > 1
                                and set(cfg["pattern"]) & set("Mw")):
            raise ValueError(
                f"loops={cfg['loops']} with pattern {cfg['pattern']!r}: a "
                f"looped stack needs loops >= 1 and no M or w layer (a "
                f"slot-owned pool holds one entry a layer, not one a loop "
                f"step)")
        #: block diffusion's positions a pass (None: one token a step)
        self.block_len = cfg["block_len"]
        if self.block_len is not None:
            if (set(cfg["pattern"]) - set("*E-") or cfg["loops"] != 1
                    or not 0 <= int(cfg["mask_id"]) < cfg["vocab"]):
                raise ValueError(
                    f"block_len={self.block_len} with pattern "
                    f"{cfg['pattern']!r}, loops={cfg['loops']}, mask_id="
                    f"{cfg['mask_id']}: block diffusion takes *, E and - "
                    f"layers, no loop and a mask token inside the "
                    f"vocabulary")
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
                               tuple(held) if held is not None else None,
                               activation=cfg["expert_act"],
                               router=cfg["router"]),
            "attn": GroupedQueryAttention(
                cfg["dim"], cfg["heads"], cfg["kv_heads"], cfg["head_dim"],
                cfg["attn_impl"], cfg["rope_theta"],
                fused_qkv=cfg["loops"] == 1, rope_share=cfg["rope_share"],
                rope_yarn=cfg["rope_yarn"], gate=cfg["attn_gate"],
                qk_norm=cfg["qk_norm"], norm_eps=cfg["norm_eps"]),
            "attn_w": GroupedQueryAttention(
                cfg["dim"], cfg["window_heads"] or cfg["heads"],
                cfg["kv_heads"], cfg["head_dim"], cfg["attn_impl"],
                cfg["window_rope_theta"],
                rope_share=cfg["window_rope_share"],
                rope_yarn=cfg["window_rope_yarn"], window=int(cfg["window"]),
                gate=cfg["attn_gate"], qk_norm=cfg["qk_norm"],
                norm_eps=cfg["norm_eps"]),
            "mlp": L.GatedFFN(cfg["ffn_dim"]),
            "mla": LatentAttention(
                cfg["dim"], cfg["heads"], cfg["q_lora"], cfg["kv_lora"],
                cfg["qk_nope"], cfg["qk_rope"], cfg["v_head"],
                float(cfg["rope_theta"] or 1e4),
                bool(cfg["rope_interleave"]), cfg["norm_eps"],
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

    @property
    def paged_layers(self) -> int:
        """Layers of one forward pass that attend through the paged pool: a
        ``*`` layer once a loop step."""
        return self.config["loops"] * self.config["pattern"].count("*")

    @property
    def latent_layers(self) -> int:
        """``L`` layers: each reads its latent rows out of the paged pool."""
        return self.config["pattern"].count("L")

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
        K/V, an entry for each ``*`` layer of each loop step (a pattern
        without a ``*`` holds one entry for nobody), or for a pattern of
        ``L`` layers a row of ``latent`` numbers a token and layer whose
        first ``values`` are the values; ``state``
        — per slot and per ``M`` layer, name -> (shape, dtype);
        ``state_layers`` their count; ``window`` (where the pattern has a
        ``w``) — per slot and per ``w`` layer a ring of ``size`` tokens of
        K/V, apart from the paged pool."""
        cfg = self.config
        kinds = [k for _, k in self.layers]
        mla = self._mixers["mla"]
        spec = {"kv": ({"layers": self.latent_layers, "latent": mla.latent,
                        "values": mla.kv_lora} if self.latent_layers else
                       {"layers": max(self.paged_layers, 1),
                        "heads": cfg["kv_heads"],
                        "head_dim": cfg["head_dim"]}),
                "state": (self._mixers["mamba"].state_shapes()
                          if "mamba" in kinds else {}),
                "state_layers": kinds.count("mamba")}
        if "attn_w" in kinds:
            spec["window"] = {"layers": kinds.count("attn_w"),
                              "size": int(cfg["window"]),
                              "heads": cfg["kv_heads"],
                              "head_dim": cfg["head_dim"]}
        return spec

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
            if cfg["post_norm"]:
                params[name]["post_norm"] = self._norm.init(None, (d,))[0]
        if cfg["loops"] > 1:
            params["exit_gate"] = {
                "w": w02(jax.random.fold_in(rng, 1), (d,)),
                "b": jnp.zeros((), jnp.float32)}
        return params, {}

    def loss_fn(self, params, state, batch, rng, train: bool):
        raise NotImplementedError(
            "HybridLM is serving-only: the Mamba-2 chunked scan has no "
            "backward path here, the dropless expert layer no balance "
            "loss and a looped stack no multi-exit loss; train the plain "
            "TransformerLM, or serve this model through tmserve / "
            "InferenceEngine")

    # -- the spine ---------------------------------------------------------------
    def _normed(self, p, x):
        return self._norm.apply(p["norm"], {}, x)[0]

    def _residual(self, p, x, y):
        """``x + y``, ``y`` a mixer's output: normed first under ``post_norm``."""
        if self.config["post_norm"]:
            y = self._norm.apply(p["post_norm"], {}, y)[0]
        return x + y

    @jax.named_scope("mlp")
    def _mlp(self, p, u):
        return self._mixers["mlp"].apply(p, {}, u)[0]

    @jax.named_scope("head")
    def _head_logits(self, cp, x):
        if self.config["loops"] == 1:  # a looped stack norms inside its loop
            x, _ = self._norm.apply(cp["norm_f"], {}, x)
        return (x @ cp["head"]["w"].astype(x.dtype)).astype(jnp.float32)

    @jax.named_scope("embed")
    def _embed(self, cp, tokens):
        return jnp.take(cp["embed"]["w"], tokens, axis=0).astype(
            self.precision.compute_dtype)

    @jax.named_scope("loop.exit")
    def _close_step(self, cp, x, ex, t):
        """The end of loop step ``t``: -> (``u_t``, the exit state with this
        step's gate counted).  ``ex``: ``chosen`` the state the head will
        read, ``cdf`` the exit distribution so far, ``survive`` ``prod (1 -
        lam)``, ``t_star`` the 1-based exit step (0 = none yet)."""
        last = t == self.config["loops"] - 1
        u, _ = self._norm.apply(cp["norm_f"], {}, x)
        g = cp["exit_gate"]
        lam = jax.nn.sigmoid(
            jnp.sum(u.astype(jnp.float32) * g["w"].astype(jnp.float32), -1)
            + g["b"].astype(jnp.float32))
        cdf = ex["cdf"] + jnp.where(last, ex["survive"], lam * ex["survive"])
        hit = (ex["t_star"] == 0) & (
            (cdf >= float(self.config["exit_threshold"])) | last)
        return u, {"chosen": jnp.where(hit[..., None], u, ex["chosen"]),
                   "cdf": cdf, "survive": ex["survive"] * (1.0 - lam),
                   "t_star": jnp.where(hit, t + 1, ex["t_star"])}

    def _stack(self, cp, x, cache, acc, once):
        """The pattern over ``x``, ``loops`` times: ``once(x, cache, acc,
        t) -> (x, cache, acc)`` is one pass, ``t`` its loop step (the
        Python int 0 without a loop, a traced int32 inside one).  -> (the
        state the head reads, cache, acc, each token's exit step or None)."""
        loops = self.config["loops"]
        if loops == 1:
            return (*once(x, cache, acc, 0), None)

        def body(t, carry):
            x, cache, acc, ex = carry
            x, cache, acc = once(x, cache, acc, t)
            u, ex = self._close_step(cp, x, ex, t)
            with jax.named_scope("loop.exit"):
                cache = _row_major(cache)
            return u, cache, acc, ex

        rows = x.shape[:-1]
        ex = {"chosen": jnp.zeros_like(x), "cdf": jnp.zeros(rows, jnp.float32),
              "survive": jnp.ones(rows, jnp.float32),
              "t_star": jnp.zeros(rows, jnp.int32)}
        _, cache, acc, ex = jax.lax.fori_loop(0, loops, body,
                                              (x, cache, acc, ex))
        return ex["chosen"], cache, acc, ex["t_star"]

    @jax.named_scope("attn.latent")
    def _latent_prefill(self, p, u, positions, kv_cache, entry, table_row):
        """An ``L`` layer over a prompt ``u`` ``[P, D]``: its rows written
        to pool entry ``entry``, attention decompressed -> (out, cache)."""
        mla = self._mixers["mla"]
        row, q_nope, q_pe = mla.project_latent(p, u[None], positions)
        if kv_cache is not None:
            kv_cache = kv_cache.write_prefill(entry, row, None, table_row)
        ctx = mla.attend(p, row, q_nope, q_pe)
        return mla.project_out(p, ctx.reshape(u.shape[0], -1)), kv_cache

    @jax.named_scope("attn.latent")
    def _latent_decode(self, p, u, positions, kv_cache, entry):
        """An ``L`` layer for one token a slot, ``u`` ``[B, D]``: its row
        written, the absorbed query over the slots' rows -> (out, cache)."""
        mla = self._mixers["mla"]
        row, q_nope, q_pe = mla.project_latent(p, u[:, None],
                                               positions[:, None])
        kv_cache = kv_cache.write_decode(entry, row[:, 0], None, positions)
        o_lat = kv_cache.attend_latent(
            entry, mla.absorb(p, q_nope[:, 0], q_pe[:, 0]), positions,
            values=mla.kv_lora, scale=mla.scale)
        ctx = mla.expand(p, o_lat)
        return mla.project_out(p, ctx.reshape(ctx.shape[0], -1)), kv_cache

    def _entry(self, t, n_kv: int):
        """The K/V pool entry of attention layer ``n_kv`` at loop step ``t``."""
        return t * self.config["pattern"].count("*") + n_kv

    def _prefill(self, params, kv_cache, table_row, tokens, true_len, slot,
                 head_at=None, head=True):
        """:meth:`apply_prefill` and each position's exit step beside it
        (``head=False``: no head, no logits)."""
        cp = self.precision.cast_to_compute(params)
        toks = tokens[0]
        if true_len is None:
            true_len = jnp.int32(toks.shape[0])
        positions = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]

        def once(x, kv_cache, acc, t):
            n_kv = n_win = n_state = n_lat = 0
            for name, kind in self.layers:
                p = cp[name]
                u = self._normed(p, x)
                if kind == "mla":
                    y, kv_cache = self._latent_prefill(
                        p["mixer"], u, positions, kv_cache, n_lat, table_row)
                    n_lat += 1
                elif kind == "mamba":
                    y, s = self._mixers[kind].prefill(p["mixer"], u, true_len)
                    if kv_cache is not None:
                        with jax.named_scope("mamba"):  # the state's write-back
                            kv_cache = kv_cache.write_state(n_state, s, slot)
                    n_state += 1
                elif kind == "moe":
                    y, _ = self._mixers[kind].apply_tokens(p["mixer"], u)
                elif kind == "mlp":
                    y = self._mlp(p["mixer"], u)
                else:
                    attn = self._mixers[kind]
                    with jax.named_scope(_ATTN_SCOPES[kind]):
                        q, k, v = attn.project_qkv(p["mixer"], u[None],
                                                   positions)
                        if kv_cache is not None:
                            kv_cache = (
                                kv_cache.write_prefill(self._entry(t, n_kv),
                                                       k, v, table_row)
                                if kind == "attn" else
                                kv_cache.write_window_prefill(
                                    n_win, k, v, true_len, slot))
                        ctx = attn.gated(
                            p["mixer"], u[None],
                            attn.attend(q, k, v, block=self.block_len))
                        y = attn.project_out(p["mixer"],
                                             ctx.reshape(u.shape[0], -1))
                    n_kv += kind == "attn"
                    n_win += kind == "attn_w"
                x = self._residual(p, x, y)
            return x, kv_cache, acc

        x, kv_cache, _, t_star = self._stack(cp, self._embed(cp, toks),
                                             kv_cache, (), once)
        if not head:
            return None, kv_cache, t_star
        if head_at is not None:
            x = jax.lax.dynamic_slice_in_dim(x, head_at, 1, axis=0)
        return self._head_logits(cp, x)[None], kv_cache, t_star

    #: :meth:`apply_prefill` takes ``head_at`` (what an engine asks before
    #: it hands one over)
    prefill_head_at = True

    def apply_prefill(self, params, state, kv_cache, table_row, tokens,
                      true_len=None, slot=None, head_at=None):
        """One sequence's prompt: ``tokens`` ``[1, P_pad]`` end-padded,
        ``true_len`` its real length (None = all of it), ``slot`` the batch
        slot whose recurrent state and window rings the prompt leaves
        behind.  -> (logits ``[1, P_pad, V]`` fp32, or with ``head_at`` (a
        position, traced) that one position's ``[1, 1, V]``; cache').
        Causal attention keeps padding out of real positions by itself; the
        ``M`` and ``w`` layers are told ``true_len``.  ``kv_cache=None``
        runs the same spine with nothing kept (:meth:`apply_logits`)."""
        del state
        return self._prefill(params, kv_cache, table_row, tokens, true_len,
                             slot, head_at)[:2]

    def apply_logits(self, params, state, tokens, exit_steps: bool = False):
        """Full-sequence forward, ``tokens`` ``[B, T]`` -> logits
        ``[B, T, V]``, nothing cached: what incremental decoding is
        compared against.  ``exit_steps``: -> (logits, each position's
        1-based exit step ``[B, T]``) of a looped stack."""
        del state
        rows = [self._prefill(params, None, None, t[None], None, None)
                for t in tokens]
        logits = jnp.stack([r[0][0] for r in rows])
        if exit_steps:
            return logits, jnp.stack([r[2] for r in rows])
        return logits

    def apply_prefill_blocks(self, params, state, kv_cache, table_row,
                             tokens):
        """A block-diffusion prompt's whole blocks: ``tokens`` ``[1,
        P_pad]``, the first ``P`` of them real and ``P`` a whole number of
        blocks, end-padded (block-causal attention keeps padding out of
        every real position).  Their K/V written, nothing read out: ->
        cache'.  The prompt's tail (``len % block_len`` tokens) is the first
        block's committed part, for :meth:`apply_block`."""
        del state
        return self._prefill(params, kv_cache, table_row, tokens, None, None,
                             head=False)[1]

    def apply_block(self, params, state, kv_cache, lengths, tokens):
        """One block-diffusion pass for each slot of the fixed batch:
        ``tokens`` ``[B, n]`` (``n`` = ``block_len``; ``mask_id`` where a
        position is not committed yet) at positions ``lengths[b] ..
        lengths[b] + n - 1``, ``lengths`` ``[B]`` the tokens cached (a
        multiple of ``n``).  Writes the block's K/V at those positions and
        attends each query over ``[0, lengths[b] + n)``.  -> (logits ``[B,
        n, V]`` fp32, cache', stats) — ``stats`` as :meth:`apply_decode`'s,
        over the rows of slots at ``lengths > 0``."""
        del state
        cp = self.precision.cast_to_compute(params)
        b, n = tokens.shape
        positions = lengths[:, None] + jnp.arange(n, dtype=jnp.int32)
        active = jnp.repeat(lengths > 0, n)
        x = self._embed(cp, tokens.reshape(-1))
        hits = peak = jnp.int32(0)
        n_kv = 0
        for name, kind in self.layers:
            p = cp[name]
            u = self._normed(p, x)
            if kind == "moe":
                y, st = self._mixers[kind].apply_tokens(p["mixer"], u,
                                                        active=active)
                hits = hits + st["local_hits"]
                peak = jnp.maximum(peak, st["load_peak"])
            elif kind == "mlp":
                y = self._mlp(p["mixer"], u)
            else:
                attn = self._mixers[kind]
                with jax.named_scope("attn"):
                    q, k, v = attn.project_qkv(p["mixer"], u.reshape(b, n, -1),
                                               positions)
                    kv_cache = kv_cache.write_block(n_kv, k, v, lengths)
                    ctx = kv_cache.attend_block(n_kv, q, lengths)
                    ctx = attn.gated(p["mixer"], u, ctx.reshape(b * n,
                                                                *q.shape[2:]))
                    y = attn.project_out(p["mixer"],
                                         ctx.reshape(b * n, -1))
                n_kv += 1
            x = self._residual(p, x, y)
        logits = self._head_logits(cp, x).reshape(b, n, -1)
        return logits, kv_cache, {"moe_local_hits": hits, "moe_load_peak": peak}

    @property
    def commits_per_pass(self) -> int:
        """Masked positions a denoising pass commits (fewer where fewer are
        left): ``ceil(block_len / BLOCK_PASSES)``."""
        return -(-self.block_len // BLOCK_PASSES)

    @jax.named_scope("diffusion.select")
    def commit_block(self, logits, tokens, masked):
        """The selection of a block-diffusion pass: of each slot's
        ``masked`` positions (``[B, n]`` bool), the :attr:`commits_per_pass`
        whose greedy token has the highest softmax probability (ties to the
        lower position) take that token.  -> (tokens ``[B, n]`` with the
        commits in, each position's state ``[B, n]`` int32: 0 still masked,
        1 committed before this pass, 2 committed by it, and each
        position's greedy log-probability ``[B, n]`` float32, the confidence
        the selection ranked by)."""
        n = tokens.shape[1]
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # the greedy token's log-probability: max - logsumexp
        top = jnp.max(logits, axis=-1)
        conf = -jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), axis=-1))
        rank = jnp.where(masked, conf, -jnp.inf)
        i = jnp.arange(n)
        ahead = (rank[:, None, :] > rank[:, :, None]) | (
            (rank[:, None, :] == rank[:, :, None]) & (i[None, :] < i[:, None]))
        take = masked & (jnp.sum(ahead, axis=-1) < jnp.minimum(
            self.commits_per_pass, jnp.sum(masked, axis=-1))[:, None])
        state = jnp.where(take, 2, jnp.where(masked, 0, 1)).astype(jnp.int32)
        return jnp.where(take, best, tokens), state, conf

    def apply_decode(self, params, state, kv_cache, positions, tokens):
        """One token for each slot of the fixed batch: ``tokens`` ``[B]`` at
        ``positions`` ``[B]``.  -> (logits ``[B, V]`` fp32, cache', stats);
        ``stats`` sums the expert layers' ``local_hits`` and takes the
        largest ``load_peak`` over them, counting slots at ``positions > 0``
        (an inactive slot rides along at position 0 on the null block); a
        looped stack adds ``loop_exit_steps``, the sum of those slots' exit
        steps."""
        del state
        cp = self.precision.cast_to_compute(params)
        active = positions > 0

        def once(x, kv_cache, acc, t):
            hits, peak = acc
            n_kv = n_win = n_state = n_lat = 0
            for name, kind in self.layers:
                p = cp[name]
                u = self._normed(p, x)
                if kind == "mla":
                    y, kv_cache = self._latent_decode(
                        p["mixer"], u, positions, kv_cache, n_lat)
                    n_lat += 1
                elif kind == "mamba":
                    y, pools = self._mixers[kind].decode(
                        p["mixer"], u, kv_cache.state, n_state)
                    kv_cache = dataclasses.replace(
                        kv_cache, state={**kv_cache.state, **pools})
                    n_state += 1
                elif kind == "moe":
                    y, st = self._mixers[kind].apply_tokens(p["mixer"], u,
                                                            active=active)
                    hits = hits + st["local_hits"]
                    peak = jnp.maximum(peak, st["load_peak"])
                elif kind == "mlp":
                    y = self._mlp(p["mixer"], u)
                else:
                    attn = self._mixers[kind]
                    with jax.named_scope(_ATTN_SCOPES[kind]):
                        entry = self._entry(t, n_kv)  # of a ``*`` layer
                        q, k, v = attn.project_qkv(p["mixer"], u[:, None],
                                                   positions[:, None])
                        if kind == "attn":
                            kv_cache = kv_cache.write_decode(
                                entry, k[:, 0], v[:, 0], positions)
                            ctx = kv_cache.attend_decode(entry, q[:, 0],
                                                         positions)
                        else:
                            kv_cache = kv_cache.write_window_decode(
                                n_win, k[:, 0], v[:, 0], positions)
                            ctx = kv_cache.attend_window_decode(
                                n_win, q[:, 0], positions)
                        ctx = attn.gated(p["mixer"], u, ctx)
                        y = attn.project_out(p["mixer"],
                                             ctx.reshape(ctx.shape[0], -1))
                    n_kv += kind == "attn"
                    n_win += kind == "attn_w"
                x = self._residual(p, x, y)
            return x, kv_cache, (hits, peak)

        x, kv_cache, (hits, peak), t_star = self._stack(
            cp, self._embed(cp, tokens), kv_cache,
            (jnp.int32(0), jnp.int32(0)), once)
        stats = {"moe_local_hits": hits, "moe_load_peak": peak}
        if t_star is not None:
            stats["loop_exit_steps"] = jnp.sum(jnp.where(active, t_star, 0))
        return self._head_logits(cp, x), kv_cache, stats

    # -- what the serving entry points ask of a model ------------------------------
    def attention_impl(self, t: int) -> str:
        from theanompi_tpu.ops.attention import resolve_attn_impl

        head = (self.config["qk_nope"] + self.config["qk_rope"]
                if self.latent_layers else self.config["head_dim"])
        return resolve_attn_impl(self.config["attn_impl"], t, head)

    def resolved_paths(self) -> dict:
        """``state_update``: what runs an ``M`` layer's decode state update
        (``kernel`` | ``plain``), where the pattern has such a layer;
        ``window_attention``: where a ``w`` layer's K/V lives and what its
        decode step reads, where the pattern has one."""
        kinds = [k for _, k in self.layers]
        paths = {"attention": self.attention_impl(self.config["seq_len"]),
                 "experts": self._mixers["moe"].products}
        if "mamba" in kinds:
            paths["state_update"] = self._mixers["mamba"].state_update_impl()
        if "attn_w" in kinds:
            paths["window_attention"] = (
                f"slot ring of {int(self.config['window'])} tokens, masked "
                f"grouped softmax")
        if self.latent_layers:
            mla = self._mixers["mla"]
            paths["latent_attention"] = (
                f"one paged row of {mla.latent} a token and layer, "
                f"absorbed decode, decompressed prefill")
        if self.block_len is not None:
            paths["block_diffusion"] = (
                f"blocks of {self.block_len}, {BLOCK_PASSES} "
                f"denoising passes + 1 that writes the block")
        return paths
