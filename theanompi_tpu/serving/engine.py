"""Inference engine: compiled prefill/decode steps for a serving model
(``TransformerLM``, ``HybridLM``).

The engine is the pure-compute half of serving (the policy half — admission,
preemption, batching — is :mod:`theanompi_tpu.serving.scheduler`): it owns
the paged KV pools, the (optionally int8-quantized) params, and two jitted
step functions driven against the model's serving path
(``apply_prefill``/``apply_decode`` — the SAME block stack and param tree
the trainer checkpoints, see :mod:`theanompi_tpu.models.transformer_lm`):

- **prefill**: one sequence, the whole prompt in one forward.  Prompts pad
  to power-of-two block multiples (bounded compile count: at most
  ``log2(max_blocks_per_seq)+1`` prefill programs); causal masking keeps
  end-padding out of every real position's context, and the first output
  token samples from the last REAL position's logits.
- **decode**: one token for every slot of a FIXED ``max_batch`` — the
  continuous-batching invariant.  Inactive slots ride along masked (their
  block tables point at the cache's null block); the step is compiled once.

Sampling runs inside the step under explicit PRNG keys derived from
``(request id, position)`` only — so a preempted-and-recomputed sequence
resamples identically, and greedy (``temperature=0``) is pure argmax.

Each host call is a span of the process's ring
(:mod:`theanompi_tpu.telemetry.spans`): ``serve.prefill`` around a whole
prefill, ``serve.decode`` around a whole decode call with its four parts
beneath it — ``.place`` (the host-to-device puts), ``.dispatch`` (the
jitted call), ``.wait`` (a launch's tokens reach the host: that device
step is over), ``.fetch`` (what else comes with them: the step's device
counters and, for a direct caller, the logits).  Under a scheduler
(``run_ahead_for``) the launch a call reads is the PREVIOUS call's, so
the wait is for a step the device was given a whole call earlier.  A
prefill has the same four and, between its dispatch and its wait, a
``.drain`` where a decode step was still running as it went out: the
wait then starts with the prefill program and is its device time.  On the
device, ``recast`` and
``sample`` scopes name the weight cast and the sampler beside the model's
own ``embed`` / ``block`` / ``attn`` / ``mlp`` / ``head`` (``HybridLM``:
``mamba`` / ``moe.route`` / ``moe.experts`` / ``moe.shared`` /
``loop.exit``).

**Block diffusion** (a model with a ``block_len``, ``HybridLM``'s): the one
decode program is a block pass (``_block_impl``) — every slot's block of B
positions through the model at once, the pass's commits chosen in the
program (:meth:`HybridLM.commit_block` under the ``diffusion.select``
scope) — and a prefill writes the prompt's whole blocks and samples
nothing.  ``decode`` keeps its arguments: ``lengths`` the tokens cached a
slot (the block's first position), ``tokens`` ``[max_batch, 2 + B]`` a
row a slot — the pass's index in its block, the positions still masked
at it (both the scheduler's count), then the block's tokens with
``mask_id`` where a position is masked, or ``-1`` to take the block as the
previous pass left it on the device.  What comes back is a row a slot,
``[L, pass, B tokens, B states, B confidences]`` (states 0 masked, 1
committed before the pass, 2 committed by it; a confidence is the greedy
token's log-probability, the float32's bits).  The schedule and both
layouts live here (``block_row``, ``advance_block_row``, ``block_result``),
and ``serve.decode`` carries ``committed`` (counted on
the device), ``store_slots`` (slots whose pass only writes a finished
block's K/V) and ``masked_rows`` beside ``kv_tokens``, the keys the pass
reads (``L + B`` a slot).

The cache is whatever the model's ``cache_spec()`` asks for: paged K/V
pools over the layers that attend (an entry per loop step and layer for a
stack that is applied several times: the pools ride through the
program's loop in place, donated as ever) and, for layers that keep a recurrent
state instead, a slot-indexed state pool ``[layers, max_batch, ...]``
donated through the steps like the K/V pools.  A prefill writes its
slot's state whole, taken at the prompt's TRUE length (padding to a bucket
is invisible to causal attention but not to a recurrence); a decode step
updates every slot's in place.

A model states the dtype its serving programs read weights in
(``weight_dtype``; both families do — ``HybridLM`` its ``weights`` key,
``TransformerLM`` its precision policy's compute dtype), and the engine
casts the tree it is handed to it ONCE, here and at every ``swap_params``,
and holds it so: the ``recast`` at the top of each program is then a no-op
that lowers to nothing, where a float32 tree under a bf16 policy was read
whole and rounded again in every decode step and every prefill.  The same
operand bits either way (on the CPU the same logits to the bit; on a TPU
XLA fuses the two programs differently, so bf16 sums round in another
order); ``resolved_paths()["weights_held"]`` names the dtype.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.pallas_grouped_matmul import grouped_matmul_supported
from theanompi_tpu.ops.pallas_paged_attention import (
    SCOPE,
    SCOPE_GROUPED,
    paged_decode_grouped_supported,
    paged_decode_supported,
)
from theanompi_tpu.ops.quant import int8_matmul_supported
from theanompi_tpu.serving.kv_cache import (
    PagedKVCache,
    blocks_for,
    prefill_write_form,
)
from theanompi_tpu.serving.quant import (
    QuantizedTensor,
    dequantize_tree,
    is_quantized_tree,
    quantize_tree,
)
from theanompi_tpu.telemetry import spans
from theanompi_tpu.telemetry.metrics import (
    SERVE_COLLECT_SPAN,
    SERVE_DECODE_SPANS,
    SERVE_PREFILL_SPANS,
    SERVE_SPANS,
)

_SPAN_PREFILL, _SPAN_DECODE = SERVE_SPANS
_SPAN_PLACE, _SPAN_DISPATCH, _SPAN_WAIT, _SPAN_FETCH = SERVE_DECODE_SPANS
(_SPAN_PREFILL_PLACE, _SPAN_PREFILL_DISPATCH, _SPAN_PREFILL_DRAIN,
 _SPAN_PREFILL_WAIT, _SPAN_PREFILL_FETCH) = SERVE_PREFILL_SPANS


def _is_quantized(leaf) -> bool:
    return isinstance(leaf, QuantizedTensor)


def _is_float_weight(leaf) -> bool:
    """A floating leaf of a tree flattened with ``is_leaf=_is_quantized``:
    a weight, not an int8 leaf (whose float32 scales stay as they are)."""
    return not _is_quantized(leaf) and jnp.issubdtype(leaf.dtype, jnp.floating)


class _Launch:
    """One decode step as launched: its outputs, still on the device
    (``logits`` None where nobody will read them)."""

    __slots__ = ("nxt", "logits", "stats", "owner", "overrun")

    def __init__(self, nxt, logits, stats, owner):
        self.nxt, self.logits, self.stats = nxt, logits, stats
        #: who left it unread (``InferenceEngine.run_ahead_for``)
        self.owner = owner
        #: slots of this step that ran for a request which had already
        #: ended (the scheduler finds out one step late: ``overrun``)
        self.overrun = 0


#: what a call reads where no launch was unread: no tokens, no counters
_NO_LAUNCH = _Launch(np.zeros((0,), np.int32), None, {}, None)


def sample_tokens(logits, temps, keys, top_k: int = 0):
    """Per-row sampling: argmax where ``temps <= 0``, else temperature
    softmax sampling (optionally over the top-``top_k`` logits).  ``logits``
    ``[B, V]`` fp32, ``temps`` ``[B]``, ``keys`` ``[B]`` PRNG keys."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k and top_k < logits.shape[-1]:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    sampled = jax.vmap(
        lambda l, k: jax.random.categorical(k, l))(scaled, keys)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _sample_key(base_key, rid, position):
    """The (request, position)-only key derivation: preemption recompute
    replays the identical sampling stream."""
    return jax.random.fold_in(jax.random.fold_in(base_key, rid), position)


class InferenceEngine:
    """Compiled serving steps + cache state for one ``TransformerLM``.

    ``num_blocks`` deliberately admits oversubscription: sized below
    ``max_batch * blocks_per_seq + 1`` the pool can run out mid-decode,
    which is the scheduler's preemption trigger (and the smoke test's).
    """

    def __init__(self, model, params, *, block_size: int = 16,
                 num_blocks: int | None = None, max_batch: int = 8,
                 quantize_int8: bool = False, quant_chunk: int = 1024,
                 top_k: int = 0, seed: int = 0,
                 decode_kernel: str = "auto"):
        cfg = model.config
        self.model = model
        self.max_batch = int(max_batch)
        self.block_size = int(block_size)
        self.max_context = int(cfg["seq_len"])
        self.max_blocks_per_seq = blocks_for(self.max_context, block_size)
        if num_blocks is None:
            num_blocks = max_batch * self.max_blocks_per_seq + 1
        self.num_blocks = int(num_blocks)
        self.top_k = int(top_k)
        self._base_key = jax.random.PRNGKey(seed)
        self.quant_stats = None
        # the serving fast path (ISSUE 18): "auto" takes the pallas paged
        # decode kernel + fused int8 matmuls on TPU when the shape gate
        # admits them, the pure-JAX paths otherwise; "on" forces the
        # kernels (interpreter off-TPU — the parity locks run exactly
        # this); "off" forces the fallback.
        if decode_kernel not in ("auto", "on", "off"):
            raise ValueError(f"decode_kernel={decode_kernel!r} not in "
                             f"('auto', 'on', 'off')")
        self.decode_kernel = decode_kernel
        spec = model.cache_spec()
        heads, head_dim = spec["kv"]["heads"], spec["kv"]["head_dim"]
        on_tpu = jax.default_backend() == "tpu"
        # two kernels, each with a shape gate of its own: one K/V head a
        # query head, or a pool of fewer (grouped) K/V heads (ISSUE 34)
        dtype = model.precision.compute_dtype
        #: block diffusion's positions a pass, None for one token a step
        self.block_len = getattr(model, "block_len", None)
        if self.block_len is not None and self.block_size % self.block_len:
            raise ValueError(f"block_len {self.block_len} does not divide "
                             f"the cache's {self.block_size}-token blocks")
        if self.block_len is not None:
            #: the token a position still to fill holds, and the masked
            #: positions a denoising pass commits: the static schedule
            self.mask_id = int(model.config["mask_id"])
            self.commits_per_pass = int(model.commits_per_pass)
        # a block pass hands the kernel a slot's ``block_len x heads``
        # queries as the query heads of one slot (``attend_block``)
        q_heads = cfg["heads"] * (self.block_len or 1)
        grouped = heads != q_heads
        fits = (paged_decode_grouped_supported(q_heads, heads, head_dim,
                                               self.block_size, dtype)
                if grouped else paged_decode_supported(heads, head_dim, dtype))
        use_kernel = decode_kernel == "on" or (
            decode_kernel == "auto" and on_tpu and fits)
        #: resolved decode-attention variant — "kernel" (compiled pallas,
        #: TPU), "kernel_interpret" (same kernel through the pallas
        #: interpreter, the off-TPU "on" mode the parity locks run) or
        #: "fallback".  SERVE.json and the serve.decode_kernel gauge
        #: report whether the kernel tier is active.
        kernel = "kernel" if on_tpu else "kernel_interpret"
        self.decode_impl = kernel if use_kernel else "fallback"
        #: the kernel's custom call by the name a trace shows it under,
        #: None on the fallback
        self.decode_call = ((SCOPE_GROUPED if grouped else SCOPE)
                            if use_kernel else None)
        #: resolved expert-product variant — "kernel", "kernel_interpret",
        #: "ragged_dot", or None for a model without an expert layer
        self.expert_impl = None
        #: the ``moe_products`` / ``moe_kernel_products`` tags of
        #: ``serve.decode`` and ``serve.prefill``: grouped products in a
        #: program, and those of them the kernel runs
        self._moe_tags: dict = {}
        # the expert layer's grouped products, where the model has such a
        # layer, by the same argument and a gate of their own (ISSUE 28);
        # the engine sets its model's path, once, before it builds a program
        experts = getattr(model, "expert_layer", None)
        if experts is not None:
            use_grouped = decode_kernel == "on" or (
                decode_kernel == "auto" and on_tpu and all(
                    grouped_matmul_supported(k, n,
                                             model.precision.compute_dtype)
                    for k, n in experts.product_shapes))
            self.expert_impl = kernel if use_grouped else "ragged_dot"
            model.set_expert_products(self.expert_impl)
            n = model.expert_products
            self._moe_tags = {"moe_products": n,
                              "moe_kernel_products": n if use_grouped else 0}
        #: the ``paged_layers`` / ``paged_kernel_layers`` tags of
        #: ``serve.decode`` and ``serve.prefill``: layers of the decode
        #: program that attend through the paged pool (a looped stack's once
        #: a loop step), and those of them a Pallas kernel runs
        n = getattr(model, "paged_layers", spec["kv"]["layers"])
        self._paged_tags = {"paged_layers": n,
                            "paged_kernel_layers": n if use_kernel else 0}
        #: the ``pool_writes`` / ``pool_writes_in_place`` tags of
        #: ``serve.prefill``: paged layers the prefill program writes (loop
        #: steps counted), and those written without re-laying the pool
        #: out — all of them, the pool's K/V heads picking the form that
        #: keeps its layout (``prefill_write_form``)
        self._write_tags = {"pool_writes": n, "pool_writes_in_place": n}
        #: what runs the decode step's recurrent-state update, as the model's
        #: op resolved it from platform and shape — "kernel",
        #: "kernel_interpret", "plain", or None for a model without such a
        #: layer — and ``serve.decode``'s tags for it: state layers in the
        #: program, and those of them the kernel runs (ISSUE 30)
        self.state_update_impl = model.resolved_paths().get("state_update")
        self._state_tags: dict = {}
        if self.state_update_impl is not None:
            n = spec["state_layers"]
            self._state_tags = {
                "state_updates": n, "state_kernel_updates":
                    0 if self.state_update_impl == "plain" else n}
        #: the window layers' band, where the model has such layers: their
        #: K/V is a ring a slot beside the pool (``serve.decode``'s tags
        #: ``kv_full_tokens`` / ``kv_window_tokens``), else None
        self.window = (spec.get("window") or {}).get("size")
        #: the model's prefill reads its head at the one position sampled
        self._head_at = bool(getattr(model, "prefill_head_at", False))
        # int8 leaves the fused matmul can consume stay quantized inside
        # the decode step; the rest (odd-vocab head, MoE stacks)
        # dequantize as before.  None = dequantize everything: the
        # fallback, and a grouped pool's model (the fused matmul is in
        # ``TransformerLM``'s layers, whose pool is never grouped).
        self._keep_quant = (
            (lambda qt: int8_matmul_supported(
                qt.shape, int(qt.q.shape[1]), compiled=on_tpu))
            if use_kernel and not grouped else None)
        # kept for swap_params: a live weight rollout must re-quantize the
        # incoming tree EXACTLY as __init__ did (same key, same chunking)
        self._quantize_int8 = bool(quantize_int8)
        self._quant_key = jax.random.PRNGKey(seed ^ 0x51)
        self._quant_chunk = int(quant_chunk)
        if quantize_int8:
            params, self.quant_stats = quantize_tree(
                params, self._quant_key, quant_chunk)
        self.params = self._held(params)
        cache = PagedKVCache.from_spec(
            spec, num_blocks=self.num_blocks, block_size=block_size,
            max_batch=max_batch, max_context=self.max_context,
            dtype=model.precision.compute_dtype,
            decode_impl=self.decode_impl)
        self._k, self._v, self._state = cache.k, cache.v, cache.state
        # k/v pools are donated: the step's .at[].set() writes update the
        # pool buffers in place instead of copying two [L, blocks, bs, H,
        # Dh] arrays per generated token (the cache docstring's contract).
        # The state pool, where the model has one, rides as the trailing
        # argument and is donated the same way; a model without one passes
        # nothing there (a decode step: the empty dict) and donates nothing.
        self._donate = (1, 2, 9) if self._state else (1, 2)
        self._decode_fn = jax.jit(
            self._decode_impl if self.block_len is None else self._block_impl,
            donate_argnums=self._donate)
        self._prefill_fns: dict[int, object] = {}
        # partial-prefill programs, keyed on PADDED SUFFIX length (same
        # power-of-two bucketing as full prefill -> same log2 bound on
        # program count per bucket family)
        self._prefill_suffix_fns: dict[int, object] = {}
        # bumped on every swap_params/restore_params: cached prefix K/V was
        # computed under the OLD weights, so the scheduler's prefix cache
        # stamps itself against this and invalidates on mismatch (ISSUE 17)
        self.params_version = 0
        #: decode steps run so far: the ``step`` tag of ``serve.decode``
        self.n_decodes = 0
        #: set by a scheduler around its decode call, to itself: the call
        #: then leaves its launch unread and returns the PREVIOUS launch's
        #: tokens (see :meth:`decode`).  None: a direct caller, whose call
        #: reads its own launch.
        self.run_ahead_for = None
        self._unread: _Launch | None = None
        # what a step with no launch before it takes for ``carry``
        self._no_carry = jax.device_put(np.zeros(
            (self.max_batch,) if self.block_len is None
            else (self.max_batch, 2 + 3 * self.block_len), np.int32))

    @property
    def quantized(self) -> bool:
        return is_quantized_tree(self.params)

    @property
    def stateful(self) -> bool:
        """The model keeps per-slot recurrent state (which no block-level
        prefix cache can share)."""
        return bool(self._state)

    @property
    def partial_prefill(self) -> bool:
        """The model can prefill a suffix over cached K/V
        (``apply_prefill_partial``): what a prefix-cache hit runs."""
        return hasattr(self.model, "apply_prefill_partial")

    def _held(self, params):
        """``params`` as the engine keeps them: cast once to the model's
        stated ``weight_dtype`` (a model that states none, or float32,
        keeps the tree as handed in).  An int8 leaf stays whole — its
        float32 scales are not weights — and the cast goes a leaf at a
        time: the caller's tree is alive beside the copy until the caller
        drops it, and a whole-tree temporary on top of both need not fit."""
        dtype = getattr(self.model, "weight_dtype", None)
        if dtype is None or dtype == jnp.float32:
            return params
        return jax.tree.map(
            lambda x: x.astype(dtype) if _is_float_weight(x) else x,
            params, is_leaf=_is_quantized)

    def resolved_paths(self) -> dict:
        """Which implementation serves each hot op of this engine — the
        gates pick from platform and shape, so SERVE.json states the
        outcome: the decode-attention variant (and, on a kernel tier, which
        of the two kernels: the pool's K/V heads decide), how many int8 leaves the
        decode step feeds to the fused matmul vs dequantizes (prefill
        always dequantizes), and the attention path of every prefill
        bucket compiled so far."""
        out: dict = {"decode_attention": self.decode_impl,
                     "prefill_kv_write": prefill_write_form(self._k.shape[3])}
        # the dtype of the floating leaves as held (see :meth:`_held`)
        out["weights_held"] = "/".join(sorted({
            jnp.dtype(leaf.dtype).name for leaf in jax.tree.leaves(
                self.params, is_leaf=_is_quantized)
            if _is_float_weight(leaf)}))
        if self.decode_call is not None:
            out["decode_attention_call"] = self.decode_call
        if self.expert_impl is not None:
            out["expert_products"] = self.expert_impl
        if self.state_update_impl is not None:
            out["state_update"] = self.state_update_impl
        if self.window is not None:
            out["window_attention"] = \
                self.model.resolved_paths()["window_attention"]
        if self.block_len is not None:
            out["block_diffusion"] = \
                self.model.resolved_paths()["block_diffusion"]
        if self.quantized:
            leaves = [leaf for leaf in jax.tree.leaves(
                self.params, is_leaf=_is_quantized) if _is_quantized(leaf)]
            fused = sum(1 for leaf in leaves
                        if self._keep_quant is not None
                        and self._keep_quant(leaf))
            out["int8_matmul"] = {"decode_fused": fused,
                                  "decode_dequantized": len(leaves) - fused,
                                  "prefill": "dequantize"}
        out["prefill_attention"] = {
            str(t): self.model.attention_impl(t)
            for t in sorted(self._prefill_fns)}
        return out

    def swap_params(self, params):
        """Hot-swap the serving weights (ISSUE 14 live rollout); -> the
        PREVIOUS engine-format param tree (the rollback token: pass it
        back to :meth:`restore_params` to undo the swap exactly).

        The new tree re-quantizes with the key/chunking ``__init__`` used,
        so it lands in the same engine format; identical shapes mean the
        jitted prefill/decode programs are reused — no recompile, and the
        swap is a host-side pointer update between scheduler steps.  The
        KV cache is NOT touched: the caller (the rollout watcher) preempts
        active sequences first, since their cache was computed under the
        old weights.
        """
        prev = self.params
        if self._quantize_int8:
            params, self.quant_stats = quantize_tree(
                params, self._quant_key, self._quant_chunk)
        self.params = self._held(params)
        self.params_version += 1
        return prev

    def restore_params(self, engine_params) -> None:
        """Reinstall a tree previously returned by :meth:`swap_params`
        (already in engine format — never re-quantized).  Bumps
        ``params_version`` too: the rollback is a THIRD weight state as far
        as cached K/V is concerned (entries cached during probation were
        computed under the rolled-back-FROM weights)."""
        self.params = engine_params
        self.params_version += 1

    # -- compiled bodies -----------------------------------------------------
    def _decode_impl(self, params, k, v, tables, lengths, tokens, temps,
                     rids, base_key, state=None, carry=None):
        # ``carry``: the previous step's ``nxt``, as it left the device; a
        # slot whose ``tokens`` entry is negative is fed from it, so a
        # sequence's next step needs nothing from the host.  (The engine
        # always passes one: it builds ONE decode program.  Lowered without,
        # as the audits do, the step is the same less this select.)
        if carry is not None:
            tokens = jnp.where(tokens < 0, carry, tokens)
        # fast path keeps kernel-consumable int8 leaves quantized; the
        # fallback dequantizes everything exactly as before (the PR 9
        # argmax-agreement lock rides on that path staying bit-stable)
        with jax.named_scope("recast"):
            params = dequantize_tree(params, keep=self._keep_quant)
        cache = PagedKVCache(k, v, tables, self.block_size,
                             decode_impl=self.decode_impl, state=state or {})
        # the incoming token's 0-based position == tokens already cached
        positions = lengths
        # (logits, cache) and, from a model that counts them, the step's
        # small device counters (the ``serve.decode`` span's tags)
        logits, cache, *stats = self.model.apply_decode(
            params, {}, cache, positions, tokens)
        with jax.named_scope("sample"):
            keys = jax.vmap(functools.partial(_sample_key, base_key))(
                rids, positions + 1)
            nxt = sample_tokens(logits, temps, keys, self.top_k)
        return (nxt, logits, cache.k, cache.v, cache.state,
                stats[0] if stats else {})

    def block_row(self, tail=()) -> np.ndarray:
        """A block's first pass, as ``decode`` takes it: ``[0, masked,
        tail..., mask...]`` — ``tail`` the block's positions already
        committed (a prompt's last ``len(prompt) % block_len`` tokens)."""
        n, tail = self.block_len, list(tail)
        return np.array([0, n - len(tail), *tail,
                         *[self.mask_id] * (n - len(tail))], np.int32)

    def advance_block_row(self, row) -> bool:
        """A slot's ``row`` as it was just launched, in place to the slot's
        next pass under the static schedule: a denoising pass leaves
        ``commits_per_pass`` fewer positions masked and its block on the
        device (``-1``).  -> whether the launched pass was the one that
        writes a finished block's K/V: ``L`` then advances by
        ``block_len``, and ``row`` opens the next block all masked."""
        if row[1] == 0:
            row[:] = self.block_row()
            return True
        row[0] += 1
        row[1] = max(row[1] - self.commits_per_pass, 0)
        row[2:] = -1
        return False

    def block_result(self, out_row):
        """One slot's row of a pass's output -> (``L``, the pass's index,
        the block's tokens, their states, their confidences)."""
        n = self.block_len
        return (int(out_row[0]), int(out_row[1]), out_row[2:2 + n],
                out_row[2 + n:2 + 2 * n],
                np.asarray(out_row[2 + 2 * n:], np.int32).view(np.float32))

    def _block_impl(self, params, k, v, tables, lengths, tokens, temps, rids,
                    base_key, state=None, carry=None):
        """One block-diffusion pass over the fixed batch (module docstring):
        ``tokens`` ``[B, 2 + n]``; ``carry`` the previous pass's output, from
        which a row whose tokens are ``-1`` takes its block.  -> (``[B, 2 +
        3 n]`` rows ``[L, pass, tokens, states, confidences]``, None, k, v,
        state, stats with ``committed``).  Greedy: ``temps``, ``rids`` and
        ``base_key`` are not read."""
        del temps, rids, base_key
        n = self.block_len
        block = tokens[:, 2:]
        masked = block == self.model.config["mask_id"]
        if carry is not None:
            fed = block[:, :1] < 0
            block = jnp.where(fed, carry[:, 2:2 + n], block)
            masked = jnp.where(fed, carry[:, 2 + n:2 + 2 * n] == 0, masked)
        with jax.named_scope("recast"):
            params = dequantize_tree(params, keep=self._keep_quant)
        cache = PagedKVCache(k, v, tables, self.block_size,
                             decode_impl=self.decode_impl, state=state or {})
        logits, cache, stats = self.model.apply_block(params, {}, cache,
                                                      lengths, block)
        block, states, conf = self.model.commit_block(logits, block, masked)
        stats["committed"] = jnp.sum((states == 2) & (lengths > 0)[:, None],
                                     dtype=jnp.int32)
        out = jnp.concatenate(
            [lengths[:, None], tokens[:, :1], block, states,
             jax.lax.bitcast_convert_type(conf, jnp.int32)], axis=1)
        return out, None, cache.k, cache.v, cache.state, stats

    def _prefill_blocks_impl(self, params, k, v, table_row, tokens):
        """A block-diffusion prompt's whole blocks into the pool; -> (a
        scalar that is ready when they are, k, v)."""
        with jax.named_scope("recast"):
            params = dequantize_tree(params)
        cache = PagedKVCache(
            k, v, jnp.zeros((1, self.max_blocks_per_seq), jnp.int32),
            self.block_size)
        cache = self.model.apply_prefill_blocks(params, {}, cache, table_row,
                                                tokens[None, :])
        return tokens[0], cache.k, cache.v

    def _prefill_impl(self, params, k, v, table_row, tokens, true_len,
                      temp, rid, base_key, state=None, slot=None):
        with jax.named_scope("recast"):
            params = dequantize_tree(params)
        cache = PagedKVCache(
            k, v, jnp.zeros((1, self.max_blocks_per_seq), jnp.int32),
            self.block_size, state=state or {})
        # a model with per-slot state is told where the prompt really ends
        # and whose state it leaves behind
        own = {} if slot is None else {"true_len": true_len, "slot": slot}
        if self._head_at:  # one row of logits, not the bucket's every row
            own["head_at"] = true_len - 1
        logits, cache = self.model.apply_prefill(
            params, {}, cache, table_row, tokens[None, :], **own)
        with jax.named_scope("sample"):
            last = (logits[0, 0] if self._head_at
                    else jnp.take(logits[0], true_len - 1, axis=0))
            key = _sample_key(base_key, rid, true_len)
            nxt = sample_tokens(last[None], temp[None], key[None],
                                self.top_k)
        return nxt[0], last, cache.k, cache.v, cache.state

    def _prefill_suffix_impl(self, params, k, v, full_row, suffix_row,
                             tokens, prefix_len, true_len, temp, rid,
                             base_key):
        """Partial prefill (ISSUE 17): ``tokens`` ``[S_pad]`` is the
        UNCACHED suffix only; K/V and logits are computed for it alone,
        attending over the full row (cached prefix included) via the paged
        gather.  ``full_row`` is fixed at ``[max_blocks_per_seq]`` so the
        program shape depends on the SUFFIX bucket only.  Sampling keys
        stay absolute-position-derived — a partial prefill samples the
        identical stream a full prefill (or a decode at the same position)
        would."""
        with jax.named_scope("recast"):
            params = dequantize_tree(params)
        cache = PagedKVCache(
            k, v, jnp.zeros((1, self.max_blocks_per_seq), jnp.int32),
            self.block_size)
        logits, cache = self.model.apply_prefill_partial(
            params, {}, cache, suffix_row, full_row, tokens[None, :],
            prefix_len)
        with jax.named_scope("sample"):
            last = jnp.take(logits[0], true_len - prefix_len - 1, axis=0)
            key = _sample_key(base_key, rid, true_len)
            nxt = sample_tokens(last[None], temp[None], key[None],
                                self.top_k)
        return nxt[0], last, cache.k, cache.v

    # -- host API (the scheduler's surface) ----------------------------------
    def pad_len(self, n_tokens: int) -> int:
        """Prompt bucket: the smallest power-of-two number of blocks that
        holds ``n_tokens`` (>= one block), capped at the max context."""
        nb = 1
        while nb * self.block_size < n_tokens:
            nb *= 2
        return min(nb, self.max_blocks_per_seq) * self.block_size

    def prefill(self, table_row, tokens, temperature: float = 0.0,
                rid: int = 0, prefix_len: int = 0, slot: int = 0):
        """Prefill one sequence; -> (first generated token: int, last-
        position logits ``[V]`` np).  ``table_row``: the block ids backing
        the prompt (padded internally with the null block).  ``slot``: the
        batch slot the sequence will decode in; a model with per-slot state
        has that slot's state replaced by the prompt's (so a slot's
        previous owner, or this request's own state from before a
        preemption, leaves nothing behind).

        ``prefix_len > 0`` (ISSUE 17): the first ``prefix_len`` tokens'
        K/V already sit in ``table_row``'s leading blocks (a prefix-cache
        hit); only the suffix is computed, in a program bucketed on the
        padded SUFFIX length.  ``prefix_len`` must be a whole number of
        blocks (the cache shares full blocks only) and must leave at least
        one uncached token to produce the next-token logits.

        A block-diffusion model's prefill writes the prompt's whole blocks
        only and samples nothing: -> (the prompt's tail, ``len(tokens) %
        block_len`` tokens that open the first block, None)."""
        p = len(tokens)
        if p > self.max_context:
            raise ValueError(f"prompt of {p} tokens > max context "
                             f"{self.max_context}")
        if self.block_len is not None:
            return self._prefill_blocks(table_row, tokens, rid, prefix_len)
        # the whole call, fenced by the host int it returns; ``bucket`` is
        # the padded length (of the uncached part) that picks the program
        with spans.span(_SPAN_PREFILL, request=rid, prompt=p,
                        tokens=p - prefix_len,
                        bucket=self.pad_len(p - prefix_len),
                        prefix_len=prefix_len, **self._moe_tags,
                        **self._paged_tags, **self._write_tags):
            if prefix_len:
                if self._state:
                    raise ValueError(
                        "a cached prefix holds K/V blocks but no recurrent "
                        "state: this model cannot prefill from one")
                if not self.partial_prefill:
                    raise ValueError(
                        "this model has no partial prefill "
                        "(apply_prefill_partial): it cannot prefill from a "
                        "cached prefix")
                return self._prefill_suffix(table_row, tokens, temperature,
                                            rid, prefix_len)
            p_pad = self.pad_len(p)
            if p_pad < p:
                raise ValueError(f"prompt {p} > padded bucket {p_pad}")
            fn = self._prefill_fns.get(p_pad)
            if fn is None:
                fn = self._prefill_fns[p_pad] = jax.jit(
                    self._prefill_impl, donate_argnums=self._donate)
            with spans.span(_SPAN_PREFILL_PLACE):
                row = list(table_row) + [PagedKVCache.NULL_BLOCK] * (
                    p_pad // self.block_size - len(table_row))
                toks = np.zeros((p_pad,), np.int32)
                toks[:p] = tokens
                args = (jnp.asarray(row, jnp.int32), jnp.asarray(toks),
                        jnp.asarray(p, jnp.int32),
                        jnp.asarray(temperature, jnp.float32),
                        jnp.asarray(rid, jnp.int32))
                own = ((self._state, jnp.asarray(slot, jnp.int32))
                       if self._state else ())
            with spans.span(_SPAN_PREFILL_DISPATCH):
                nxt, last, self._k, self._v, self._state = fn(
                    self.params, self._k, self._v, *args, self._base_key,
                    *own)
            return self._prefill_read(nxt, last)

    def _prefill_blocks(self, table_row, tokens, rid, prefix_len):
        """:meth:`prefill` for a block-diffusion model, in a ``serve.prefill``
        span of its own (tag ``block_tail``: the tokens left for the first
        block).  A prompt shorter than one block runs no program."""
        if prefix_len:
            raise ValueError("block diffusion has no partial prefill: it "
                             "cannot prefill from a cached prefix")
        n = len(tokens) - len(tokens) % self.block_len
        with spans.span(_SPAN_PREFILL, request=rid, prompt=len(tokens),
                        tokens=n, bucket=self.pad_len(n) if n else 0,
                        prefix_len=0, block_tail=len(tokens) - n,
                        **self._moe_tags, **self._paged_tags,
                        **self._write_tags):
            if n:
                p_pad = self.pad_len(n)
                fn = self._prefill_fns.get(p_pad)
                if fn is None:
                    fn = self._prefill_fns[p_pad] = jax.jit(
                        self._prefill_blocks_impl, donate_argnums=(1, 2))
                with spans.span(_SPAN_PREFILL_PLACE):
                    row = list(table_row[:p_pad // self.block_size]) + [
                        PagedKVCache.NULL_BLOCK] * (
                        p_pad // self.block_size - len(table_row))
                    toks = np.zeros((p_pad,), np.int32)
                    toks[:n] = tokens[:n]
                    args = jax.device_put((np.array(row, np.int32), toks))
                with spans.span(_SPAN_PREFILL_DISPATCH):
                    done, self._k, self._v = fn(self.params, self._k, self._v,
                                                *args)
                self._prefill_read(done, None)
            return list(tokens[n:]), None

    def _prefill_suffix(self, table_row, tokens, temperature, rid,
                        prefix_len):
        """The ``prefix_len > 0`` half of :meth:`prefill`, inside its
        ``serve.prefill`` span."""
        p = len(tokens)
        if prefix_len % self.block_size:
            raise ValueError(f"prefix_len {prefix_len} is not a whole "
                             f"number of {self.block_size}-token blocks")
        if not 0 < prefix_len < p:
            raise ValueError(f"prefix_len {prefix_len} outside (0, {p}) — "
                             f"at least one token must stay uncached")
        s = p - prefix_len
        s_pad = self.pad_len(s)
        fn = self._prefill_suffix_fns.get(s_pad)
        if fn is None:
            fn = self._prefill_suffix_fns[s_pad] = jax.jit(
                self._prefill_suffix_impl, donate_argnums=(1, 2))
        with spans.span(_SPAN_PREFILL_PLACE):
            # the full row at FIXED width: program shape keyed on s_pad only
            full_row = list(table_row) + [PagedKVCache.NULL_BLOCK] * (
                self.max_blocks_per_seq - len(table_row))
            n_prefix = prefix_len // self.block_size
            suffix_row = list(table_row[n_prefix:]) + [
                PagedKVCache.NULL_BLOCK] * (
                s_pad // self.block_size - (len(table_row) - n_prefix))
            toks = np.zeros((s_pad,), np.int32)
            toks[:s] = tokens[prefix_len:]
            args = (jnp.asarray(full_row, jnp.int32),
                    jnp.asarray(suffix_row, jnp.int32), jnp.asarray(toks),
                    jnp.asarray(prefix_len, jnp.int32),
                    jnp.asarray(p, jnp.int32),
                    jnp.asarray(temperature, jnp.float32),
                    jnp.asarray(rid, jnp.int32))
        with spans.span(_SPAN_PREFILL_DISPATCH):
            nxt, last, self._k, self._v = fn(
                self.params, self._k, self._v, *args, self._base_key)
        return self._prefill_read(nxt, last)

    def _prefill_read(self, nxt, last):
        """The ``.drain``, ``.wait`` and ``.fetch`` of a prefill that has
        gone out: -> (its sampled token, its last position's logits), on
        the host; ``last`` None (a block prefill): no ``.fetch``."""
        if self._step_running():
            # the prefill queued behind that step: the host would wait it
            # out inside ``int(nxt)`` anyway.  Nothing is read — the launch
            # stays unread for its owner
            with spans.span(_SPAN_PREFILL_DRAIN):
                # lint: host-sync-ok — this span IS the rest of that step
                self._unread.nxt.block_until_ready()
        with spans.span(_SPAN_PREFILL_WAIT):
            # lint: host-sync-ok — this span IS the prefill program's run
            tok = int(nxt)
        if last is None:
            return tok, None
        with spans.span(_SPAN_PREFILL_FETCH, bytes=last.nbytes):
            # lint: host-sync-ok — this span IS the copy to the host
            # lint: donated-escape-ok — prefill outputs are fresh XLA result
            # buffers; only the k/v pools are donated, never sampled tokens
            return tok, np.asarray(last)

    def decode(self, tables, lengths, tokens, temps, rids):
        """One decode step over the fixed batch; -> (next tokens ``[B]``
        np.int32, logits ``[B, V]`` np) — for a block-diffusion model (module
        docstring) one pass: -> (rows ``[B, 2 + 3 n]``, None).  All
        arguments are host arrays of
        length ``max_batch``; inactive slots pass table rows of nulls and
        length 0 (their outputs are garbage by contract).  The arrays are
        copied on the way in: the caller may change them once this returns.

        A direct caller gets "launch, then read that same launch".  With
        ``run_ahead_for`` set (a :class:`~theanompi_tpu.serving.scheduler
        .Scheduler` sets it, to itself, around its call) the call launches
        step n and then reads step n-1, which the device finished while the
        host prepared this one: -> (step n-1's tokens, None) — the tokens
        reach the host one step after they are computed, the logits never
        do — or an empty token array where no launch was unread.  A slot
        that continues passes a NEGATIVE token: step n takes its input on
        the device from step n-1's output, the same program either way.
        :meth:`collect` reads the launch still out without launching
        another (a drain).  A launch is read by whoever left it unread:
        anyone else's call drops it (a loop that was abandoned)."""
        lengths = np.asarray(lengths)
        active = np.flatnonzero(lengths)
        owner = self.run_ahead_for
        prev = self._unread
        if prev is not None and prev.owner is not owner:
            prev = None
        if prev is None and (np.asarray(tokens)[active] < 0).any():
            raise ValueError("a slot asks for the previous step's token and "
                             "no launch of this caller's is unread")
        # ``kv_tokens``: the tokens this step's attention reads, each
        # active slot's context with the token it writes (a block pass: with
        # the whole block).  ``step``, ``batch``, ``kv_tokens`` and
        # ``requests`` are the launched step's; the device counters the span
        # is tagged with below are those of the step it READ, one behind
        # where the call runs ahead
        context = lengths[active] + (self.block_len or 1)
        block_tags = {} if self.block_len is None else {
            "store_slots": int((np.asarray(tokens)[active, 1] == 0).sum()),
            "masked_rows": int(np.asarray(tokens)[active, 1].sum())}
        # a model with window layers: the keys a full layer attends, and
        # the same capped at the window a slot (what a window layer does)
        window_tags = {} if self.window is None else {
            "kv_full_tokens": int(context.sum()),
            "kv_window_tokens": int(np.minimum(context, self.window).sum())}
        with spans.span(_SPAN_DECODE, step=self.n_decodes, batch=len(active),
                        kv_tokens=int(context.sum()),
                        requests=np.asarray(rids)[active].tolist(),
                        launched=1, ran_ahead=int(prev is not None),
                        **self._moe_tags, **self._state_tags,
                        **self._paged_tags, **window_tags,
                        **block_tags) as span:
            self.n_decodes += 1
            with spans.span(_SPAN_PLACE):
                args = jax.device_put((np.array(tables, np.int32),
                                       np.array(lengths, np.int32),
                                       np.array(tokens, np.int32),
                                       np.array(temps, np.float32),
                                       np.array(rids, np.int32)))
            span.tag(starved=int(not self._step_running()))
            with spans.span(_SPAN_DISPATCH):
                nxt, logits, self._k, self._v, self._state, stats = \
                    self._decode_fn(
                        self.params, self._k, self._v, *args, self._base_key,
                        self._state,
                        self._no_carry if prev is None else prev.nxt)
                # the tokens and counters start for the host as soon as the
                # step ends, ahead of whatever is launched after it
                for out in (nxt, *stats.values()):
                    out.copy_to_host_async()
            if owner is None:  # a direct caller: this launch, logits and all
                self._unread = None
                return self._read(_Launch(nxt, logits, stats, None), span)
            self._unread = _Launch(nxt, None, stats, owner)
            return self._read(prev or _NO_LAUNCH, span)

    def _step_running(self) -> bool:
        """A launch is out (its reader's or not) and the device has not
        finished it: one non-blocking query."""
        out = self._unread
        return out is not None and not out.nxt.is_ready()

    def _read(self, launch, span):
        """The ``.wait`` and ``.fetch`` of one call: ``launch``'s tokens,
        its device counters (tags of ``span``) and, where it kept them,
        its logits reach the host."""
        with spans.span(_SPAN_WAIT):
            # lint: host-sync-ok — this span IS the wait for the device
            # lint: donated-escape-ok — decode outputs are fresh XLA
            # result buffers; only the k/v pools are donated
            nxt = np.asarray(launch.nxt)
        rest = [*launch.stats.values()]
        if launch.logits is not None:
            rest.append(launch.logits)
        with spans.span(_SPAN_FETCH, bytes=sum(x.nbytes for x in rest)):
            # the model's step counters (moe_local_hits, moe_load_peak,
            # loop_exit_steps): scalars that come with the tokens
            span.tag(overrun_slots=launch.overrun,
                     **{name: int(x) for name, x in launch.stats.items()})
            if launch.logits is None:
                return nxt, None
            # lint: host-sync-ok — this span IS the copy to the host
            # lint: donated-escape-ok — as above: never tokens/logits
            return nxt, np.asarray(launch.logits)

    def collect(self):
        """Read the launch a decode call left unread, launching
        nothing (the scheduler's drain); -> its tokens ``[B]``, or None
        where no launch is unread.  A ``serve.collect`` span (tag
        ``overrun_slots``); the step's device counters go unreported."""
        launch, self._unread = self._unread, None
        if launch is None:
            return None
        with spans.span(SERVE_COLLECT_SPAN, overrun_slots=launch.overrun):
            # lint: host-sync-ok — this span IS the wait for the device
            # lint: donated-escape-ok — a decode's sampled tokens, as above
            return np.asarray(launch.nxt)

    def overrun(self) -> None:
        """A slot of the unread launch ran for a request that had already
        ended (its stop token was read after the launch went out): counted
        in the ``overrun_slots`` tag of the span that reads the launch."""
        self._unread.overrun += 1

    def fence(self):
        """Block until the cache state is materialized (honest timing)."""
        jax.block_until_ready((self._k, self._v, self._state))
