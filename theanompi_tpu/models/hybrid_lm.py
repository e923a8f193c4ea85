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
  (:class:`theanompi_tpu.ops.layers.GatedFFN`).

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
from theanompi_tpu.ops.attention import GroupedQueryAttention
from theanompi_tpu.ops.mamba2 import Mamba2
from theanompi_tpu.ops.moe import DroplessMoE

_KINDS = {"M": "mamba", "E": "moe", "*": "attn", "w": "attn_w", "-": "mlp"}
#: the device scope of each attention kind
_ATTN_SCOPES = {"attn": "attn", "attn_w": "attn.window"}


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
                             f"FFN)")
        if cfg["loops"] < 1 or (cfg["loops"] > 1
                                and set(cfg["pattern"]) & set("Mw")):
            raise ValueError(
                f"loops={cfg['loops']} with pattern {cfg['pattern']!r}: a "
                f"looped stack needs loops >= 1 and no M or w layer (a "
                f"slot-owned pool holds one entry a layer, not one a loop "
                f"step)")
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
                               activation=cfg["expert_act"]),
            "attn": GroupedQueryAttention(
                cfg["dim"], cfg["heads"], cfg["kv_heads"], cfg["head_dim"],
                cfg["attn_impl"], cfg["rope_theta"],
                fused_qkv=cfg["loops"] == 1, rope_share=cfg["rope_share"],
                rope_yarn=cfg["rope_yarn"], gate=cfg["attn_gate"]),
            "attn_w": GroupedQueryAttention(
                cfg["dim"], cfg["window_heads"] or cfg["heads"],
                cfg["kv_heads"], cfg["head_dim"], cfg["attn_impl"],
                cfg["window_rope_theta"],
                rope_share=cfg["window_rope_share"],
                rope_yarn=cfg["window_rope_yarn"], window=int(cfg["window"]),
                gate=cfg["attn_gate"]),
            "mlp": L.GatedFFN(cfg["ffn_dim"]),
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
        without a ``*`` holds one entry for nobody); ``state``
        — per slot and per ``M`` layer, name -> (shape, dtype);
        ``state_layers`` their count; ``window`` (where the pattern has a
        ``w``) — per slot and per ``w`` layer a ring of ``size`` tokens of
        K/V, apart from the paged pool."""
        cfg = self.config
        kinds = [k for _, k in self.layers]
        spec = {"kv": {"layers": max(self.paged_layers, 1),
                       "heads": cfg["kv_heads"], "head_dim": cfg["head_dim"]},
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

    def _entry(self, t, n_kv: int):
        """The K/V pool entry of attention layer ``n_kv`` at loop step ``t``."""
        return t * self.config["pattern"].count("*") + n_kv

    def _prefill(self, params, kv_cache, table_row, tokens, true_len, slot,
                 head_at=None):
        """:meth:`apply_prefill` and each position's exit step beside it."""
        cp = self.precision.cast_to_compute(params)
        toks = tokens[0]
        if true_len is None:
            true_len = jnp.int32(toks.shape[0])
        positions = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]

        def once(x, kv_cache, acc, t):
            n_kv = n_win = n_state = 0
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
                        ctx = attn.gated(p["mixer"], u[None],
                                         attn.attend(q, k, v))
                        y = attn.project_out(p["mixer"],
                                             ctx.reshape(u.shape[0], -1))
                    n_kv += kind == "attn"
                    n_win += kind == "attn_w"
                x = self._residual(p, x, y)
            return x, kv_cache, acc

        x, kv_cache, _, t_star = self._stack(cp, self._embed(cp, toks),
                                             kv_cache, (), once)
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
            n_kv = n_win = n_state = 0
            for name, kind in self.layers:
                p = cp[name]
                u = self._normed(p, x)
                if kind == "mamba":
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

        return resolve_attn_impl(self.config["attn_impl"], t,
                                 self.config["head_dim"])

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
        return paths
