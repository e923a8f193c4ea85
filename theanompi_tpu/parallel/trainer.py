"""Shared trainer/rule scaffolding for the three parallel rules.

Reference (unverified — SURVEY.md §2.1): the per-rule worker scripts
(``bsp_worker.py``, ``easgd_worker.py``/``easgd_server.py``,
``gosgd_worker.py``) share their epoch/validation/recording skeleton and
differ in how parameters are exchanged.  Here the skeleton is
:class:`BaseTrainer` (compile → iterate → validate → record) and each rule
supplies the compiled step + parameter layout:

- BSP: one replicated parameter set, exchange fused into the step;
- EASGD/GOSGD: *per-worker divergent* parameter sets, stored stacked along a
  leading axis sharded over the ``data`` mesh axis, with periodic host-driven
  exchange steps (the SPMD reformulation of the reference's async MPI
  messages — see each module's docstring).
"""

from __future__ import annotations

import os
import sys
import time
from contextlib import nullcontext
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.resilience import (
    NonFiniteLossError,
    PreemptGuard,
    PreemptionExit,
    PreemptionRequested,
    ResilienceConfig,
    SentinelRollback,
)

from theanompi_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    make_mesh,
    replica_rng,
    replicated,
)
from theanompi_tpu.telemetry import spans
from theanompi_tpu.telemetry.metrics import TRAIN_SPANS
from theanompi_tpu.utils.helper_funcs import import_model, shard_batch
from theanompi_tpu.utils.recorder import Recorder

(_SPAN_STEP,) = TRAIN_SPANS

from theanompi_tpu.parallel.exchanger import (  # noqa: E402
    EXCHANGE_RNG_TAG as _EXCH_RNG_TAG,
    fused_pmean,
)


def pmean_floats(tree, axis_name):
    """pmean every inexact leaf; pass ints (counters etc.) through.

    Fused: one collective per dtype instead of one per leaf (a BN-state
    tree of 16 running-stat buffers costs ONE all-reduce) — part of the
    bucketed-exchange HLO budget ``tests/test_lint_collectives.py`` locks.
    """
    return fused_pmean(tree, axis_name)


def unstack(tree):
    """Drop the per-shard leading worker axis of size 1 (inside shard_map)."""
    return jax.tree.map(lambda x: x[0], tree)


def restack(tree):
    return jax.tree.map(lambda x: x[None], tree)


def make_local_step(model, opt, base_key, exchanger=None, stacked=False,
                    param_specs=None, sentinel_skip=False):
    """The per-worker train step shared by every rule.

    ``exchanger`` set (BSP): gradients are mean-reduced across the data axis
    before the update, and metrics/state are pmean'd so the outputs are
    replicated.  ``stacked`` (EASGD/GOSGD): parameter trees carry a leading
    worker axis of size 1 per shard, the step is collective-free, and metrics
    come back per-worker (stacked) — averaging happens on host at print time.
    ``param_specs`` (tensor parallelism) makes gradient clipping's global
    norm exact across model shards (see :func:`ops.opt.global_sq_norm`).

    ``n_subb`` in the model config (reference contract: the file-batch was
    trained in ``n_subb`` sub-batches with cumulative gradients —
    SURVEY.md §2.3/§2.4.1) enables gradient accumulation: the per-worker
    batch is split into ``n_subb`` micro-batches and a ``lax.scan`` runs
    forward+backward per micro-batch, summing gradients and threading
    model state sequentially, with ONE exchange and ONE optimizer update
    per step.  Activation memory is per-micro-batch — on TPU this is the
    lever for large effective batches at fixed HBM.  Numerics: with
    per-example normalization (LN) the accumulated mean gradient equals
    the full-batch gradient exactly; with batch-statistic layers (BN)
    statistics are per-micro-batch, the same semantics the reference's
    sub-batched training had.
    """
    n_subb = int(model.config.get("n_subb", 1) or 1)

    # models with a non-standard update (e.g. the GAN two-optimizer step)
    # supply the whole inner step; the rule still owns layout and reduction
    custom = getattr(model, "make_custom_step", None)
    inner = custom(opt, base_key, exchanger) if custom is not None else None
    if inner is not None and n_subb > 1:
        raise ValueError(
            f"n_subb={n_subb} requires the standard grad step; "
            f"{type(model).__name__} supplies make_custom_step"
        )
    if inner is not None and exchanger is not None and exchanger.fuses_update:
        raise ValueError(
            f"exch_strategy 'zero1' requires the standard grad step; "
            f"{type(model).__name__} supplies make_custom_step"
        )
    if inner is not None and sentinel_skip:
        raise ValueError(
            f"sentinel policy 'skip_batch' requires the standard grad step; "
            f"{type(model).__name__} supplies make_custom_step "
            f"(use sentinel_policy='abort' or 'rollback')"
        )

    def local_step(params, state, opt_state, batch, lr, step):
        if stacked:
            params, state, opt_state = (
                unstack(params), unstack(state), unstack(opt_state)
            )
        if inner is not None:
            new_params, new_state, new_opt_state, metrics = inner(
                params, state, opt_state, batch, lr, step
            )
        else:
            # fold every batch-sharding axis (dropout must differ per data
            # AND seq shard; it must NOT differ across model shards, whose
            # activations are jointly one logical tensor)
            axes = exchanger.axis_name if exchanger is not None else DATA_AXIS
            rng = replica_rng(jax.random.fold_in(base_key, step), axes)

            if n_subb == 1:
                def lossw(p):
                    return model.loss_fn(p, state, batch, rng, train=True)

                (_, (new_state, metrics)), grads = jax.value_and_grad(
                    lossw, has_aux=True
                )(params)
            else:
                new_state, metrics, grads = _accumulated_grads(
                    model, params, state, batch, rng, n_subb
                )
            ok = None
            if sentinel_skip:
                # the non-finite guard (ISSUE 4): ok iff loss AND the local
                # grad-norm² are finite on EVERY worker — the indicator is
                # psum'd across the exchange axes so replicas select the
                # same branch (critical for zero1, whose local grads may be
                # non-finite on only one shard)
                gsq = jnp.float32(0)
                for g in jax.tree.leaves(grads):
                    if jnp.issubdtype(g.dtype, jnp.inexact):
                        gsq = gsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
                bad = jnp.logical_not(jnp.isfinite(gsq)).astype(jnp.float32)
                c = metrics.get("cost") if isinstance(metrics, dict) else None
                if c is not None:
                    bad = jnp.maximum(bad, jnp.logical_not(
                        jnp.all(jnp.isfinite(c))).astype(jnp.float32))
                if exchanger is not None:
                    bad = jax.lax.psum(bad, exchanger.axis_name)
                ok = bad == 0
            if exchanger is not None and exchanger.fuses_update:
                # zero1: the exchange IS the update — reduce-scatter grad
                # buckets, shard-local optimizer step, all-gather params
                # (opt_state lives in the exchanger's sharded bucket layout)
                with jax.named_scope("exchange"):
                    new_params, new_opt_state = (
                        exchanger.exchange_and_update(
                            grads, opt_state, params, lr, opt,
                            rng=jax.random.fold_in(rng, _EXCH_RNG_TAG),
                            step=step,
                        ))
            else:
                if exchanger is not None:
                    # a distinct stream from dropout's: ring_int8 seeds its
                    # stochastic rounding from this key.  step anchors the
                    # overlap fence chain (exch_overlap; unused otherwise)
                    with jax.named_scope("exchange"):
                        grads = exchanger.exchange(
                            grads,
                            rng=jax.random.fold_in(rng, _EXCH_RNG_TAG),
                            step=step)
                # gradient clipping is the optimizer's first act: its ops
                # read optimizer/clip (ops/opt.py)
                with jax.named_scope("optimizer"):
                    new_params, new_opt_state = opt.update(
                        grads, opt_state, params, lr,
                        param_specs=param_specs
                    )
            if ok is not None:
                # skip_batch: a poisoned step costs one skipped update —
                # keep the old params/state/opt state wholesale
                def keep(new, old):
                    return jax.tree.map(lambda a, b: jnp.where(ok, a, b),
                                        new, old)

                new_params = keep(new_params, params)
                new_opt_state = keep(new_opt_state, opt_state)
                new_state = keep(new_state, state)
                if isinstance(metrics, dict):
                    # the host-side Sentinel pops this flag and enforces
                    # the bounded skip budget at fenced boundaries
                    metrics = dict(metrics)
                    metrics["_sentinel_skip"] = 1.0 - ok.astype(jnp.float32)
        if stacked:
            return (
                restack(new_params),
                restack(new_state),
                restack(new_opt_state),
                jax.tree.map(lambda m: m[None], metrics),
            )
        axes = exchanger.axis_name if exchanger is not None else DATA_AXIS
        with jax.named_scope("exchange"):
            metrics = pmean_floats(metrics, axes)
            # keep non-learned state consistent across replicas (already
            # identical under sync-BN; pmean repairs drift otherwise)
            new_state = pmean_floats(new_state, axes)
        if isinstance(metrics, dict):
            # the donated-device-step contract: train_iter pops this and
            # feeds it back as the next step argument, so the counter never
            # re-crosses the host boundary (one H2D transfer per run, not
            # per step)
            metrics = dict(metrics)
            metrics["_next_step"] = step + jnp.int32(1)
        return new_params, new_state, new_opt_state, metrics

    return local_step


def _accumulated_grads(model, params, state, batch, rng, n_subb):
    """Micro-batched forward+backward: -> (new_state, metrics, mean grads).

    One compiled ``lax.scan`` over ``n_subb`` micro-batches — activations
    live only for the current micro-batch; the gradient accumulator is one
    params-sized tree.  State (BN running stats) threads sequentially
    through the scan; float metrics come back micro-batch-averaged.
    """
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(batch)}
    if any(b % n_subb for b in leading):
        raise ValueError(
            f"n_subb={n_subb} must divide the per-worker batch "
            f"(got leading dims {sorted(leading)})"
        )

    def split(x):
        return x.reshape(n_subb, x.shape[0] // n_subb, *x.shape[1:])

    micro = jax.tree.map(split, batch)

    def one(carry, xs):
        st, gsum = carry
        mb, i = xs

        def lossw(p):
            # a fresh fold per micro-batch: dropout masks must differ
            # across micro-batches like they do across examples
            return model.loss_fn(p, st, mb, jax.random.fold_in(rng, i),
                                 train=True)

        (_, (new_st, m)), g = jax.value_and_grad(lossw, has_aux=True)(params)
        return (new_st, jax.tree.map(jnp.add, gsum, g)), m

    gsum0 = jax.tree.map(jnp.zeros_like, params)
    (new_state, gsum), mstk = jax.lax.scan(
        one, (state, gsum0), (micro, jnp.arange(n_subb))
    )
    grads = jax.tree.map(lambda g: g / n_subb, gsum)
    metrics = jax.tree.map(
        lambda m: (jnp.mean(m, axis=0)
                   if jnp.issubdtype(m.dtype, jnp.inexact) else m[-1]),
        mstk,
    )
    # perplexity is exp(loss): mean-of-exp over micro-batches would bias it
    # high vs an n_subb=1 run (Jensen) — re-derive from the averaged cost,
    # which is exactly what the unaccumulated path reports
    if isinstance(metrics, dict) and {"perplexity", "cost"} <= metrics.keys():
        metrics["perplexity"] = jnp.exp(metrics["cost"])
    return new_state, metrics, grads


def make_local_eval(model, axes=DATA_AXIS):
    """Shared eval step: params per their specs, batch per its partition."""

    def local_eval(params, state, batch):
        _, (_, metrics) = model.loss_fn(params, state, batch, None, train=False)
        return pmean_floats(metrics, axes)

    return local_eval


def require_data_parallel_mesh(mesh, rule_name: str) -> None:
    """Refuse tp/sp/pp meshes for the async rules (data-parallel only).

    EASGD/GOSGD stack per-worker params over ``data`` and ignore the
    model's ``param_specs`` — on a mesh with a sharded ``model``/``seq``/
    ``pipe`` axis, a tensor-parallel layer's collectives would run against
    replicated full weights and silently double-count (the same bug class
    the pipeline model guards against).  The reference's async rules were
    data-parallel only too (SURVEY.md §2.1).
    """
    for axis in (MODEL_AXIS, SEQ_AXIS, PIPE_AXIS):
        if mesh.shape.get(axis, 1) > 1:
            raise ValueError(
                f"{rule_name} is data-parallel only: mesh axis {axis!r} has "
                f"size {mesh.shape[axis]} (use BSP for tp/sp/pp shardings)"
            )


def _parse_profile_window(value) -> tuple:
    """ISSUE 16: normalize the ``profile_window`` rule key (tuple or the
    launcher's ``START:STOP`` string) — lazy import keeps the telemetry
    layer off the import path of telemetry-less runs."""
    from theanompi_tpu.telemetry.profile import parse_profile_window

    return parse_profile_window(value)


def stack_for_workers(mesh, tree, n: int):
    """Tile a pytree with a leading worker axis sharded over ``data``.

    The device layout of "every worker has its own copy" — each leaf becomes
    ``(n, *shape)`` with shard ``i`` resident on worker ``i``'s devices.
    """
    from theanompi_tpu.utils.helper_funcs import put_global

    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def tile(x):
        x = np.asarray(x)
        return put_global(np.broadcast_to(x, (n, *x.shape)).copy(), sharding)

    return jax.tree.map(tile, tree)


class BaseTrainer:
    """Compile-and-iterate skeleton; subclasses define the step + layout.

    Subclass obligations: ``compile_iter_fns`` (set ``_step_fn``/``_eval_fn``),
    ``init_state``, ``eval_args()`` -> (params, state) for validation, and
    optionally ``post_step()`` (periodic exchange hook, called after every
    train iteration with ``self.iteration`` already advanced).
    """

    def __init__(self, model, mesh=None, recorder: Recorder | None = None,
                 seed: int = 0, prefetch_depth: int = 2,
                 checkpoint_dir: str | None = None, checkpoint_keep: int = 3,
                 checkpoint_async: bool = True,
                 checkpoint_verify: str = "auto",
                 checkpoint_every_n_iters: int = 0,
                 resume_force: bool = False,
                 resume_reshard: bool = False,
                 profile_dir: str | None = None,
                 profile_window: tuple[int, int] = (10, 20),
                 telemetry=None,
                 resilience: ResilienceConfig | None = None):
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh(n_data=1)
        self.n_workers = self.mesh.shape[DATA_AXIS]
        self.recorder = recorder or Recorder()
        self.seed = seed
        self.prefetch_depth = prefetch_depth
        self.batch_spec = model.batch_partition()
        # ISSUE 4 resilience: a default config is all-off (env-gated by the
        # supervisor), so a bare trainer behaves exactly as before — every
        # hot-path hook below guards on `is None`
        self.resilience = (resilience if resilience is not None
                           else ResilienceConfig())
        self.fault_plan = self.resilience.build_fault_plan()
        self.sentinel = self.resilience.build_sentinel(telemetry)
        self._watchdog = None
        self._heartbeat = None  # liveness-only writer when detector is off
        self._preempt_guard = None
        self._epoch_start_iter = 0
        self.checkpointer = None
        if checkpoint_verify not in ("auto", "fast", "full", "none"):
            raise ValueError(
                f"checkpoint_verify must be auto/fast/full/none, "
                f"got {checkpoint_verify!r}")
        self.checkpoint_verify = checkpoint_verify
        # ISSUE 10: mid-epoch save cadence in iterations (0 = boundary-only,
        # the old behavior).  Cadence saves stamp the data-plane cursor into
        # the manifest, so a SIGKILL between boundaries resumes at the
        # newest iteration — replaying no batch and skipping none
        self.checkpoint_every_n_iters = int(checkpoint_every_n_iters or 0)
        if self.checkpoint_every_n_iters < 0:
            raise ValueError(
                f"checkpoint_every_n_iters must be >= 0, "
                f"got {checkpoint_every_n_iters}")
        self._resume_data_state: dict | None = None
        # batch-trace witness (ISSUE 10 tests/debug): when set, one line
        # per consumed global batch — "epoch batch_index" — is appended,
        # so no-replay/no-skip across a crash is a file diff
        self._data_trace_path = os.environ.get("THEANOMPI_DATA_TRACE")
        if checkpoint_dir:
            from theanompi_tpu.utils.checkpoint import Checkpointer

            # async by default (ISSUE 3): the boundary only pays the
            # snapshot; serialization/publish/prune run on the writer.
            # The fingerprint is the bound method, resolved lazily —
            # subclasses set self.exchanger after this constructor runs
            # (rules with a bucketed exchanger also backfill bucket_bytes
            # so the ISSUE 8 reshard planner recomputes the same layout)
            self.checkpointer = Checkpointer(
                checkpoint_dir, keep=checkpoint_keep,
                async_save=checkpoint_async, telemetry=telemetry,
                fault_plan=self.fault_plan,
                fingerprint=self._run_fingerprint,
                resume_force=resume_force,
                reshard=resume_reshard)
        self.optimizer = model.build_optimizer()
        self.global_batch = model.batch_size * self.n_workers
        # ISSUE 8: an elastic resume onto a different device count scales
        # the LR by new_n/old_n (linear-scaling rule — LR tracks the
        # global batch at fixed per-worker batch); 1.0 = no reshard
        self.lr_scale = 1.0
        self._step_fn = None
        self._eval_fn = None
        self.params = None
        self.state = None
        self.opt_state = None
        self.epoch = 0
        self.iteration = 0
        # SURVEY.md §5 tracing row: a bounded jax.profiler window
        # (TensorBoard-viewable device trace), off unless profile_dir is set
        self.profile_dir = profile_dir
        self.profile_window = profile_window
        self._profiling = False
        # ISSUE 1 telemetry: None means no sink — the step's spans go to
        # the process's ring either way (ISSUE 25); everything that writes
        # (gauges, counters, flushes) guards on this, so a disabled run
        # constructs no Telemetry and writes nothing
        self.telemetry = telemetry
        self.recorder.telemetry = telemetry
        # ISSUE 10: the data layer's read-retry telemetry and fault hooks
        # are module-level (datasets outlive trainers and run on loader
        # threads/processes); wire only when there is something to wire,
        # so a bare trainer never clobbers hooks a test installed
        if telemetry is not None or self.fault_plan is not None:
            from theanompi_tpu.models.data.base import set_data_hooks

            set_data_hooks(telemetry=telemetry, fault_plan=self.fault_plan)
        self._compiled_step_cache: tuple | None = None  # (shape key, exe)
        self._exchange_wire_bytes_cached: int | None = None
        # per-step host->device scalar hoisting (ISSUE 2 satellite): the
        # placed lr is cached until the schedule changes it, and the step
        # counter round-trips as a device scalar (the step returns
        # `_next_step`, fed back as the next call's argument)
        self._lr_dev = None
        self._lr_host: float | None = None
        self._step_dev = None
        self._step_dev_iter: int = -1
        self._flops_per_step: float | None = None  # None = not yet probed
        self._peak_flops: float | None = None
        self._last_metrics_flush: float | None = None
        self._first_step_emitted = False  # compile.first_step_s gauge latch

    # -- subclass surface ----------------------------------------------------
    def compile_iter_fns(self) -> None:
        raise NotImplementedError

    def init_state(self) -> None:
        raise NotImplementedError

    def eval_args(self):
        """-> (params, state) to evaluate with (replicated)."""
        return self.params, self.state

    def compiled_step(self, batch):
        """The compiled train-step executable (serves ``.cost_analysis()``
        to the ``train.mfu`` gauge and ``.as_text()`` to ``tmlint
        --hlo-audit`` without each caller re-deriving the argument tuple).

        Memoized on the batch's shapes/dtypes (lowering is shape-based):
        ``lower().compile()`` is a full second XLA compile, which the
        telemetry MFU probe must not pay inside the train loop."""
        import jax.numpy as jnp

        key = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), batch)
        if self._compiled_step_cache is not None \
                and self._compiled_step_cache[0] == key:
            return self._compiled_step_cache[1]
        args = (self.params, self.state, self.opt_state, batch,
                jnp.float32(0.01), jnp.int32(0))
        exe = self._step_fn.lower(*args).compile()
        self._compiled_step_cache = (key, exe)
        return exe

    def compiled_step_text(self, batch) -> str:
        """HLO text of the compiled train step (``tmlint --hlo-audit``)."""
        return self.compiled_step(batch).as_text()

    def post_step(self) -> None:
        """Periodic host-driven exchange hook (EASGD/GOSGD)."""

    def warmup_exchange(self) -> None:
        """Execute the rule's periodic-exchange compiled path once (jit is
        lazy; ``post_step`` may not fire it on the first iterations)."""

    def warmup(self) -> None:
        """Run every compiled path once, then reset to a fresh init.

        Timing harnesses (rulecomp) call this so their measured
        window excludes XLA compilation: jit compiles at first call, not at
        ``compile_iter_fns`` (which only builds the jit wrappers).
        """
        gen = self.model.data.train_batches(self.global_batch, 0,
                                            seed=self.seed)
        try:
            batch = next(iter(gen))
        finally:
            # run()-loop parity: a prefetch-backed generator left unclosed
            # here would keep its worker thread/queue alive
            close = getattr(gen, "close", None)
            if close:
                close()
        self.train_iter(batch, lr=self.model.adjust_hyperp(0))
        self.warmup_exchange()
        # one val batch compiles the eval + consensus paths; a full
        # validate() would walk the whole val set untimed but for real
        vb = min(self.global_batch, self.model.data.n_val)
        vb -= vb % self.n_workers  # same divisibility rule as validate()
        if vb:
            vgen = self.model.data.val_batches(vb)
            try:
                vbatch = next(iter(vgen), None)
            finally:
                vclose = getattr(vgen, "close", None)
                if vclose:
                    vclose()
            if vbatch is not None:
                self.val_iter(vbatch)
        self.init_state()
        self.reset_iter()

    def reset_iter(self) -> None:
        """Zero the iteration/epoch counters and start a fresh recorder
        (reference contract name — its ``reset_iter(mode)`` re-armed the
        per-mode iteration state between phases; here counters live on the
        trainer and the compiled fns are mode-less pure functions, so a
        reset is just counters + recorder)."""
        self.iteration = 0
        self.epoch = 0
        self._resume_data_state = None
        self.recorder = Recorder(
            print_freq=self.recorder.print_freq,
            save_dir=self.recorder.save_dir,
            verbose=self.recorder.verbose,
            telemetry=self.telemetry,
        )

    def check_divergence(self, atol: float = 0.0) -> float:
        """Assert replicated param/state copies are in sync across devices.

        Debug hook (SURVEY.md §5 race-detection row): call at epoch
        boundaries when chasing non-determinism or exchange bugs; costs a
        device→host pull of the trees.
        """
        from theanompi_tpu.utils.divergence import assert_replicas_in_sync

        d1 = assert_replicas_in_sync(self.params, atol=atol, what="params")
        d2 = assert_replicas_in_sync(self.state, atol=atol, what="state")
        return max(d1, d2)

    def checkpoint_trees(self) -> dict:
        """Named pytrees a checkpoint must capture (rules add extras)."""
        return {
            "params": self.params,
            "state": self.state,
            "opt_state": self.opt_state,
        }

    def _run_fingerprint(self) -> dict:
        """The run-topology fingerprint stamped into checkpoint manifests
        (ISSUE 5): resuming under a different mesh, exchange strategy,
        accumulation depth, or model config is a hard refusal unless
        ``resume_force`` — a silent topology change corrupts the lineage
        (zero1 opt-state shards, stacked EASGD/GOSGD worker axes, and RNG
        streams all depend on it).  The model-identity half is
        :func:`~theanompi_tpu.utils.checkpoint.model_fingerprint` — ONE
        sha definition shared with the serving consumer, so a ``tmserve``
        process built from the same ``--set`` flags matches a training
        manifest (see ``MODEL_FP_EXCLUDED`` there for why
        ``n_epochs``/``verbose``/``bn_axis`` don't hash).
        """
        from theanompi_tpu.utils.checkpoint import model_fingerprint

        exch = getattr(self, "exchanger", None)
        return {
            "mesh": {str(a): int(s) for a, s in self.mesh.shape.items()},
            "exchange": getattr(exch, "strategy", type(self).__name__),
            "n_subb": int(self.model.config.get("n_subb", 1) or 1),
            **model_fingerprint(self.model),
            **self._fingerprint_extra(),
        }

    def _fingerprint_extra(self) -> dict:
        """Subclass hook for extra (or overriding) fingerprint entries —
        BSP uses it to stamp the ramp-invariant base exchange strategy
        plus the ``exch_ramp``/``exch_overlap`` knobs, so a checkpoint
        written mid-ramp still matches a resume that starts at the base."""
        return {}

    def _maybe_ramp(self, epoch: int) -> None:
        """Subclass hook, called at the top of every epoch: activate the
        ``exch_ramp`` phase ``epoch`` dictates (no-op without a ramp).
        See :class:`theanompi_tpu.parallel.overlap.RampSchedule`."""

    def _data_state(self, epoch: int, completed: bool) -> dict:
        """The data-plane position a checkpoint captures (ISSUE 10).

        The cursor is stored in SAMPLES, not this run's batches: an
        elastic resume divides by ITS OWN global batch, so a mesh8->4
        restart consumes the exact same global sample sequence the mesh8
        run would have.  ``dataset`` is :meth:`Dataset.state` — cursors
        that persist ACROSS epochs (stream mixture cursors), restored on
        boundary resumes too, not just mid-epoch ones.
        """
        cursor = max(0, self.iteration - self._epoch_start_iter)
        return {
            "version": 1,
            "epoch": int(epoch),
            "completed": bool(completed),
            "batch_cursor": int(cursor),
            "sample_cursor": int(cursor) * int(self.global_batch),
            "global_batch": int(self.global_batch),
            "seed": int(self.seed),
            "dataset": self.model.data.state(),
        }

    def save_checkpoint(self, epoch: int, completed: bool = True):
        """Kick off a checkpoint save; -> SaveHandle (or None, no dir).

        ``completed=False`` (ISSUE 10) marks a MID-epoch save (iteration
        cadence, preemption): the manifest's ``data_state`` carries the
        consumed-batch cursor and ``try_resume`` re-enters the epoch there
        instead of treating it as finished.

        The training thread pays only the blocking snapshot (multi-host
        gathers + overlapped device→host copies + a cheap recorder-history
        list copy), emitted as the ``checkpoint.snapshot`` span inside the
        checkpointer; serialization, atomic publish, the recorder-history
        write and pruning run on the background writer (``checkpoint.write``
        span) unless ``checkpoint_async=False``.
        """
        if self.checkpointer is None:
            return None
        return self.checkpointer.save(
            epoch, self.iteration, self.checkpoint_trees(),
            recorder_snapshot=self.recorder.history_snapshot(),
            lr_scale=self.lr_scale,
            data_state=self._data_state(epoch, completed))

    def _resume_verify_level(self) -> str:
        """ISSUE 5 verify policy: the cheap structural check always; the
        full per-leaf hash read exactly when it pays — the first resume
        after a non-clean exit (the previous writer never reached its
        clean-shutdown handshake, or this is a supervised restart), which
        is when torn writes and half-copied files actually appear."""
        if self.checkpoint_verify != "auto":
            return self.checkpoint_verify
        from theanompi_tpu.resilience.faults import current_attempt

        if self.checkpointer.was_unclean() or current_attempt() > 1:
            return "full"
        return "fast"

    def try_resume(self) -> bool:
        """Restore the newest *verifiable* checkpoint; -> resumed or not.

        Call after ``init_state`` (the fresh state is the restore template,
        carrying pytree structure and shardings).  Goes through the
        checkpoint recovery chain (ISSUE 5): corrupt checkpoints are
        quarantined and stepped over; an exhausted chain raises
        :class:`~theanompi_tpu.utils.checkpoint.CheckpointChainExhausted`
        (tmlauncher exit 77) and a run-topology mismatch raises
        :class:`~theanompi_tpu.utils.checkpoint.CheckpointFingerprintError`
        unless ``resume_force`` is set."""
        if self.checkpointer is None:
            return False
        res = self.checkpointer.load_latest_verified(
            self.checkpoint_trees(), verify=self._resume_verify_level())
        if res is None:
            return False
        epoch, iteration, restored = res
        for name, tree in restored.items():
            setattr(self, name, tree)  # params/state/opt_state + rule extras
        ds = (self.checkpointer.last_loaded_manifest or {}).get("data_state")
        if ds and not ds.get("completed", True):
            # mid-epoch checkpoint (ISSUE 10): re-enter the saved epoch at
            # the saved cursor — _run_epochs fast-forwards the data plane
            # by cursor arithmetic, replaying nothing and skipping nothing
            self.epoch = int(ds.get("epoch", epoch))
            self._resume_data_state = dict(ds)
        else:
            self.epoch = epoch + 1  # that epoch completed
        self.iteration = iteration
        if ds and isinstance(ds.get("dataset"), dict) and ds["dataset"]:
            # dataset-internal cursors (stream mixture positions) persist
            # ACROSS epochs: restore them on boundary resumes too
            self.model.data.set_state(ds["dataset"])
        plan = self.checkpointer.last_reshard_plan
        if plan is not None:
            # ISSUE 8: the load replanned a topology change — apply the
            # (cumulative) linear-scaling LR factor for the rest of the
            # run and say so loudly (a silently rescaled LR would read as
            # a lineage bug)
            self.lr_scale = plan.lr_scale
            lr_note = (
                "LR carried unrescaled (async rule: per-worker batch and "
                "update are n-independent)"
                if getattr(plan, "stacked", None) is not None
                else f"LR scaled x{plan.lr_scale:g} (linear-scaling rule)")
            print(f"trainer: RESHARD resumed a {plan.old_n}-worker "
                  f"checkpoint onto {self.n_workers} workers: global batch "
                  f"{self.model.batch_size * plan.old_n} -> "
                  f"{self.global_batch} (per-worker batch fixed), "
                  f"{lr_note}", file=sys.stderr, flush=True)
        else:
            # a plain resume of a previously-resharded lineage keeps its
            # cumulative LR factor (stamped in the manifest)
            man = self.checkpointer.last_loaded_manifest
            if man is not None:
                self.lr_scale = float(man.get("lr_scale", 1.0) or 1.0)
        self.recorder.load(self.checkpointer.directory)
        if self.recorder.verbose:
            where = (f"mid-epoch {self.epoch} at batch "
                     f"{self._resume_data_state.get('batch_cursor', 0)}"
                     if self._resume_data_state is not None
                     else f"epoch {epoch}")
            print(f"resumed from {where} "
                  f"(iteration {self.iteration})", flush=True)
        return True

    # -- profiling (SURVEY.md §5: jax.profiler traces) -----------------------
    def _profile_tick(self) -> None:
        """Start/stop the device trace at the configured iteration window.

        The window is [start, stop) in global iterations; steps inside it are
        captured to ``profile_dir`` (open with TensorBoard's profile plugin
        or Perfetto).  A bounded window, not whole-run tracing: traces are
        huge and perturb timing.  Stop fences on the params so the trace
        includes the full device execution of the last windowed step.
        """
        if self.profile_dir is None:
            return
        start, stop = self.profile_window
        # range membership, not equality: a resumed run (try_resume sets
        # iteration past `start`) must still trace if it's inside the window
        if not self._profiling and start <= self.iteration < stop:
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
            self._profile_mark("start")
        elif self._profiling and self.iteration >= stop:
            self._profile_stop()

    def _profile_stop(self) -> None:
        jax.block_until_ready(jax.tree.leaves(self.params))
        jax.profiler.stop_trace()
        self._profiling = False
        self._profile_mark("stop")

    def _profile_mark(self, phase: str) -> None:
        """ISSUE 16: stamp the trace window into the event stream so the
        device trace aligns with the host spans in one timeline."""
        if self.telemetry is None:
            return
        from theanompi_tpu.telemetry.metrics import PROF_INSTANTS

        self.telemetry.instant(PROF_INSTANTS[0], phase=phase,
                               iteration=self.iteration)

    # -- telemetry (ISSUE 1) -------------------------------------------------
    def exchange_wire_bytes(self) -> int | None:
        """Per-device ICI bytes for this rule's per-step exchange.

        Static accounting (the collective is fused into the XLA step, so
        nothing host-side can observe it): rules with a per-step exchanger
        (BSP) report ``Exchanger.wire_bytes`` of the gradient tree.  The
        per-device gradient buffer is the PARAM SHARD, not the global
        param (under tensor/sequence parallelism each device reduces only
        its slice), so leaves are sized via ``sharding.shard_shape`` —
        and the ring spans every exchange axis, so the traffic factor uses
        the product of the exchanger's axis sizes, not just ``data``.
        Rules without a per-step exchanger return None; their periodic
        exchanges account for themselves (see EASGD.post_step).
        """
        exch = getattr(self, "exchanger", None)
        if exch is None or self.params is None:
            return None
        return exch.wire_bytes(self._shard_param_structs(),
                               self._exchange_axis_size())

    def _exchange_axis_size(self) -> int:
        exch = getattr(self, "exchanger", None)
        if exch is None:
            return 1
        axes = (exch.axis_name if isinstance(exch.axis_name, tuple)
                else (exch.axis_name,))
        n = 1
        for a in axes:
            n *= self.mesh.shape.get(a, 1)
        return n

    def _shard_param_structs(self):
        """The per-device param-shard shapes the exchange actually moves."""

        def shard_struct(x):
            if isinstance(x, jax.Array) and x.sharding is not None:
                return jax.ShapeDtypeStruct(
                    x.sharding.shard_shape(x.shape), x.dtype)
            return x

        return jax.tree.map(shard_struct, self.params)

    def _exchange_accounting(self) -> int:
        """Cached per-step wire bytes; emits the one-time accounting event
        (strategy, bytes, worker count) the first time it resolves."""
        if self._exchange_wire_bytes_cached is None:
            wire = self.exchange_wire_bytes()
            self._exchange_wire_bytes_cached = 0 if wire is None else wire
            exch = getattr(self, "exchanger", None)
            if wire is not None and self.telemetry is not None:
                extra = exch.bucket_summary(
                    self._shard_param_structs(),
                    self._exchange_axis_size()) or {}
                self.telemetry.instant(
                    "exchange.accounting",
                    strategy=exch.strategy,
                    bytes_per_exchange=wire,
                    n_workers=self.n_workers,
                    **extra,
                )
        return self._exchange_wire_bytes_cached

    def _telemetry_flush(self, r: Recorder) -> None:
        """Publish live training metrics at the print boundary: rates,
        step-time percentiles, MFU, device memory high-water.

        The rate window is wall time since the previous flush; callers
        reset ``_last_metrics_flush`` to None across non-training work
        (validation, checkpointing — see run()) so a window never absorbs
        it and under-reports throughput.  A None window (first flush of a
        window) publishes no rate gauges rather than a wrong number.
        """
        from theanompi_tpu.telemetry import metrics as tmetrics

        tel = self.telemetry
        now = time.perf_counter()
        window_s = (now - self._last_metrics_flush
                    if self._last_metrics_flush is not None else None)
        self._last_metrics_flush = now
        if window_s:
            eps = r.print_freq * self.global_batch / window_s
            tel.gauge("train.examples_per_sec", eps)
            seq = self.model.config.get("seq_len")
            if seq:
                tel.gauge("train.tokens_per_sec", eps * seq)
        p50 = tel.metrics.percentiles("train.step_s", (50,)).get("p50")
        if self._flops_per_step and p50:
            m = tmetrics.mfu(self._flops_per_step, p50, self._peak_flops)
            if m is not None:
                tel.gauge("train.mfu", m)
        mem = tmetrics.device_memory_stats()
        if mem:
            for k, v in mem.items():
                tel.gauge(f"device.{k}", v)
        # ISSUE 16: attr.* segment gauges + per-device HBM watermarks +
        # ATTRIB.json refresh, all at this fenced boundary (no-op unless
        # the attributor was configured)
        tel.profile_flush(step=self.iteration)
        tel.flush_metrics(step=self.iteration, window_steps=r.print_freq)

    # -- iteration (reference train_iter/val_iter) ---------------------------
    def _apply_step_fault(self, batch):
        """Deterministic fault injection (ISSUE 4) — the `step` site."""
        from theanompi_tpu.resilience import faults

        action = self.fault_plan.fire("step", self.iteration)
        if action is None:
            return batch
        if action == "raise":
            raise faults.FaultInjected(
                f"injected failure at train step {self.iteration}")
        if action == "kill":
            faults.kill_self()
        # "nan": poison the batch's float leaves so the loss/grads become
        # genuinely non-finite — the sentinel sees the real article, not a
        # spoofed metric
        def poison(x):
            dt = getattr(x, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.inexact):
                return x * np.dtype(dt).type(float("nan"))
            return x

        return jax.tree.map(poison, batch)

    def train_iter(self, batch: dict, lr: float, recorder: Recorder | None = None):
        if self.fault_plan is not None:
            batch = self._apply_step_fault(batch)
        self._profile_tick()
        r = recorder or self.recorder
        tel = self.telemetry
        step_idx, epoch_idx = self.iteration, self.epoch
        with spans.span(_SPAN_STEP, step=step_idx, epoch=epoch_idx) as step:
            r.start("wait")
            # already-placed batches (prefetch path) pass through
            # device_put free
            batch = shard_batch(self.mesh, batch, spec=self.batch_spec)
            r.end("wait")
            r.start("calc")
            # scalar-hoisting (ISSUE 2 satellite): jnp.float32(lr)/jnp.int32(i)
            # here were one host->device transfer EACH per step; the lr is
            # placed once per schedule change and the step counter is carried
            # as a device scalar threaded through the step's `_next_step`
            # lint: host-sync-ok — lr is the schedule's host number
            lr_f = float(lr)
            if self._lr_dev is None or self._lr_host != lr_f:
                self._lr_dev = jnp.float32(lr_f)
                self._lr_host = lr_f
            if self._step_dev is None or self._step_dev_iter != self.iteration:
                # placed ON THE MESH like the `_next_step` the step hands back:
                # jax types carry the mesh, so a bare jnp.int32 here made the
                # second call (fed the returned counter) a different signature
                # — a full second trace and compile of the train step
                self._step_dev = jax.device_put(np.int32(self.iteration),
                                                replicated(self.mesh))
            self.params, self.state, self.opt_state, metrics = self._step_fn(
                self.params,
                self.state,
                self.opt_state,
                batch,
                self._lr_dev,
                self._step_dev,
            )
            self.iteration += 1
            nxt = (metrics.pop("_next_step", None)
                   if isinstance(metrics, dict) else None)
            if nxt is not None and getattr(nxt, "ndim", None) == 0:
                self._step_dev, self._step_dev_iter = nxt, self.iteration
            else:  # stacked/custom metrics carry no counter: re-place next call
                self._step_dev = None
            # the device guard's skip flag is sentinel bookkeeping, not a
            # training metric — pop it before the recorder sees the dict
            skipf = (metrics.pop("_sentinel_skip", None)
                     if isinstance(metrics, dict) else None)
            # fence only at print boundaries: per-iter blocking would serialize
            # the dispatch pipeline (SURVEY.md §7 hard part 5)
            fence = metrics["cost"] if self.iteration % r.print_freq == 0 else None
            r.end("calc", fence=fence)
            if fence is not None:
                # loss tag ONLY at fenced boundary steps (ISSUE 13): the
                # cost is already materialized by the calc fence above, so
                # float() is free here; tagging every step would add a
                # per-step sync.  The health monitor's NaN/spike detector
                # keys on this tag.
                # lint: host-sync-ok — the calc fence just materialized it
                step.tag(loss=float(fence))
            # no wrapping span here: the async rules' post_step brackets the
            # rounds that actually exchange with recorder 'comm' segments, which
            # the recorder already emits as spans — a per-step wrapper would
            # write a no-op span line on every non-exchange step (tau-1 of tau)
            self.post_step()
            r.end_iteration()
            r.train_metrics(**metrics)
            r.print_train_info(self.iteration)
        if tel is not None:
            # same async-dispatch honesty caveat as the calc split: between
            # print boundaries a span measures dispatch, and only the fenced
            # boundary step reflects full device time — percentile/rate
            # metrics below aggregate across a window, which is honest at
            # steady state because dispatched work must drain through the
            # donated-buffer chain
            dur = step.dur
            tel.observe("train.step_s", dur)
            if not self._first_step_emitted:
                # first-compile visibility (ISSUE 3): the first dispatch
                # pays tracing + XLA compile synchronously — or a
                # persistent-cache hit.  This gauge is the witness that
                # --compile-cache-dir works: a warm cache makes it drop.
                self._first_step_emitted = True
                tel.gauge("compile.first_step_s", dur, step=step_idx)
            wire = self._exchange_accounting()
            if wire:
                tel.count("exchange.wire_bytes", wire, emit=True,
                          step=step_idx)
            if self._flops_per_step is None:
                # MFU probe on the FIRST step, after its span closed: the
                # aot lower+compile lands next to the jit compile this
                # step already paid, instead of stalling the loop minutes
                # later at the first print boundary; its own span keeps
                # the cost visible rather than untracked
                from theanompi_tpu.telemetry import metrics as tmetrics

                with tel.span("telemetry.mfu_probe"):
                    self._flops_per_step = tmetrics.step_flops_estimate(
                        self, batch) or 0.0
                    self._peak_flops = tmetrics.peak_flops()
            if self.iteration % r.print_freq == 0:
                self._telemetry_flush(r)
        if self._watchdog is not None:
            self._watchdog.beat(self.iteration)
        elif self._heartbeat is not None:
            # detector disabled but a supervisor watches the file: keep
            # proving liveness or its --hang-timeout kills a healthy run
            self._heartbeat.beat(self.iteration)
        if self.sentinel is not None:
            # lazy refs now, materialization at the fenced print boundary:
            # the sentinel must not add a per-step device sync (same
            # discipline as the recorder's calc fence)
            self.sentinel.watch(
                step_idx,
                metrics.get("cost") if isinstance(metrics, dict) else None,
                skipf)
            if self.iteration % r.print_freq == 0:
                self.sentinel.check()
        return metrics

    def val_iter(self, batch: dict, recorder: Recorder | None = None,
                 eval_args=None):
        batch = shard_batch(self.mesh, batch, spec=self.batch_spec)
        # eval_args may be expensive (GOSGD consensus psums the whole param
        # tree) — validate() hoists it out of the per-batch loop
        params, state = eval_args if eval_args is not None else self.eval_args()
        return self._eval_fn(params, state, batch)

    def validate(self, epoch: int):
        # the val set may be smaller than the global batch; shrink to the
        # largest worker-divisible batch rather than silently skipping
        vb = min(self.global_batch, self.model.data.n_val)
        vb -= vb % self.n_workers
        if vb == 0:
            if self.recorder.verbose:
                print(
                    f"validate: n_val={self.model.data.n_val} < "
                    f"{self.n_workers} workers, skipping",
                    flush=True,
                )
            return {}
        accums: dict[str, list] = {}
        eval_args = self.eval_args()
        with (self.telemetry.span("validate", epoch=epoch)
              if self.telemetry is not None else nullcontext()):
            for batch in self.model.data.val_batches(vb):
                m = self.val_iter(batch, eval_args=eval_args)
                for k, v in m.items():
                    # device arrays accumulate WITHOUT float(): a per-batch
                    # float() forced a device sync per metric per batch,
                    # serializing the eval dispatch pipeline (ISSUE 2
                    # satellite) — the single pull happens after the loop
                    accums.setdefault(k, []).append(v)
        means = {
            k: float(np.asarray(jnp.stack(v)).mean(dtype=np.float64))
            for k, v in accums.items()
        }
        # perplexity is exp(loss): the arithmetic mean of per-batch
        # perplexities is Jensen-biased high — re-derive from the averaged
        # cost (same fix the micro-batch accumulation path applies)
        if {"perplexity", "cost"} <= means.keys():
            means["perplexity"] = float(np.exp(means["cost"]))
        self.recorder.val_metrics(epoch, **means)
        return means

    # -- full run (reference *_worker.run) -----------------------------------
    def _make_prefetcher(self, epoch: int, start_batch: int = 0):
        """The para_load equivalent: read/augment/transfer overlaps compute.

        ``start_batch`` (ISSUE 10): the resume cursor — the dataset
        fast-forwards to it by seed/cursor arithmetic (no batch is
        materialized to be thrown away) and the prefetcher's fault and
        consumption ordinals stay GLOBAL batch indices across the restart.
        """
        from theanompi_tpu.models.data.prefetch import prefetch

        return prefetch(
            self.model.data.train_batches(self.global_batch, epoch,
                                          seed=self.seed,
                                          start_batch=start_batch),
            mesh=self.mesh,
            depth=self.prefetch_depth,
            spec=self.batch_spec,
            telemetry=self.telemetry,
            # ISSUE 4: a hung source raises PrefetchStallError instead of
            # deadlocking the training thread forever (None keeps the old
            # block-forever behavior); the fault plan's `prefetch` site
            # lives inside the worker
            stall_timeout=self.resilience.prefetch_stall_timeout,
            fault_plan=self.fault_plan,
            start_batch=start_batch,
        )

    def _check_preempt(self) -> None:
        """Between-steps preemption poll (a host flag read, nothing more)."""
        if self._preempt_guard is not None and self._preempt_guard.triggered:
            raise PreemptionRequested()

    def _preemption_checkpoint(self) -> bool:
        """The final synchronous checkpoint of a preempted run.

        ISSUE 10: the state is labeled with the CURRENT epoch and carries
        the data-plane cursor (``completed=False``), so the resumed run
        re-enters the interrupted epoch at the first unconsumed batch —
        exactly-once data consumption, replacing the old at-least-once
        epoch replay (which re-trained every step since the boundary).
        When no step has run since the last boundary save there is
        nothing new to capture; the in-flight async writer (if any) is
        joined so the boundary checkpoint is durably published before
        exiting.
        """
        if self.checkpointer is None:
            return False
        if self.iteration <= self._epoch_start_iter:
            self.checkpointer.join_pending()
            return False
        handle = self.checkpointer.save(
            self.epoch, self.iteration, self.checkpoint_trees(),
            recorder_snapshot=self.recorder.history_snapshot(),
            lr_scale=self.lr_scale,
            data_state=self._data_state(self.epoch, completed=False))
        handle.join()  # synchronous: the process is about to exit
        self.checkpointer.join_pending()
        return True

    def _handle_rollback(self, e: SentinelRollback) -> None:
        """Reload the newest *verifiable* checkpoint in-process (sentinel
        'rollback').  Goes through the recovery chain (ISSUE 5): a
        NaN-triggered rollback whose latest checkpoint is corrupt
        quarantines it and lands on the verified ancestor instead of
        re-raising into a crash loop; an exhausted chain propagates as the
        typed checkpoint error (exit 77 under the launcher).  Still
        bounded by the existing ``sentinel_max_rollbacks`` budget."""
        self.sentinel.rollbacks += 1
        if (self.checkpointer is None
                or self.sentinel.rollbacks > self.sentinel.max_rollbacks):
            why = ("no checkpoint dir to roll back from"
                   if self.checkpointer is None else
                   f"rollback budget exhausted "
                   f"({self.sentinel.max_rollbacks})")
            raise NonFiniteLossError(
                f"non-finite loss at step {e.step}; {why}", step=e.step
            ) from e
        print(f"sentinel: non-finite loss at step {e.step}; rolling back "
              f"to the newest verifiable checkpoint "
              f"({self.sentinel.rollbacks}/{self.sentinel.max_rollbacks})",
              file=sys.stderr, flush=True)
        self.sentinel.reset_pending()  # pending losses describe a dead timeline
        if self._watchdog is not None:
            self._watchdog.pause()  # restore I/O + re-placement is beat-free
        try:
            resumed = self.try_resume()
        finally:
            if self._watchdog is not None:
                self._watchdog.resume()
        if not resumed:
            raise NonFiniteLossError(
                f"non-finite loss at step {e.step}; no checkpoint to roll "
                f"back to", step=e.step) from e
        if self.telemetry is not None:
            self.telemetry.instant("sentinel.rollback", step=e.step,
                                   restore_epoch=self.epoch - 1,
                                   rollback=self.sentinel.rollbacks)
        self._step_dev = None  # restored iteration needs a fresh device scalar

    def _run_epochs(self, stop=None) -> None:
        """The epoch loop proper (run() owns retry/teardown around it)."""
        model = self.model
        batches = None
        try:
            for epoch in range(self.epoch, model.n_epochs):
                self.epoch = epoch
                # quantization ramp (exch_ramp): the ONE place a phase can
                # switch — an epoch boundary, so at most one recompile per
                # phase and a resume lands in the phase its epoch dictates
                self._maybe_ramp(epoch)
                start_batch = 0
                rds, self._resume_data_state = self._resume_data_state, None
                if rds is not None and int(rds.get("epoch", -1)) == epoch:
                    # ISSUE 10: resume INSIDE this epoch.  The cursor is
                    # in samples (device-count-independent): an elastic
                    # resume divides by its OWN global batch, preserving
                    # the exact global sample order across a mesh change
                    sc = int(rds.get("sample_cursor", 0))
                    start_batch = sc // self.global_batch
                    if sc % self.global_batch:
                        print(f"trainer: resume sample cursor {sc} is not "
                              f"divisible by the global batch "
                              f"{self.global_batch}; flooring to batch "
                              f"{start_batch} (the partial batch replays)",
                              file=sys.stderr, flush=True)
                    self._epoch_start_iter = self.iteration - start_batch
                else:
                    self._epoch_start_iter = self.iteration
                self._check_preempt()
                self.recorder.start_epoch()
                # lr_scale is 1.0 except after an elastic reshard (x1.0 is
                # float-exact, so unresharded lineages are bit-unchanged)
                lr = model.adjust_hyperp(epoch) * self.lr_scale
                if batches is None:  # not pre-built at the last boundary
                    batches = self._make_prefetcher(epoch, start_batch)
                it = iter(batches)
                try:
                    while True:
                        # the dequeue is the real input stall (para_load's
                        # 'wait' — SURVEY.md §3.5); time it into the same
                        # per-iteration wait bucket train_iter's residual
                        # shard_batch adds to, so a starved pipeline reports
                        # wait > 0 instead of hiding the stall in untracked
                        # loop time
                        self.recorder.start("wait")
                        try:
                            batch = next(it)
                        except StopIteration:
                            self.recorder.cancel("wait")
                            break
                        self.recorder.end("wait")
                        self.train_iter(batch, lr)
                        if (self._data_trace_path
                                and jax.process_index() == 0):
                            # consumed-batch witness: (epoch, global batch
                            # index) of the step that just COMPLETED — a
                            # step killed inside train_iter leaves no line,
                            # so a resumed lineage's trace concatenates to
                            # exactly the uninterrupted sequence (the
                            # no-replay/no-skip assert in the e2e tests)
                            # lint: atomic-publish-ok — append-only
                            # witness lines; a torn tail IS the signal
                            # (the killed step leaves no complete line)
                            with open(self._data_trace_path, "a") as tf:
                                tf.write(
                                    f"{epoch} "
                                    f"{self.iteration - 1 - self._epoch_start_iter}"
                                    f"\n")
                        cad = self.checkpoint_every_n_iters
                        if (cad and self.checkpointer is not None
                                and (self.iteration
                                     - self._epoch_start_iter) % cad == 0):
                            # iteration-cadence mid-epoch save (ISSUE 10):
                            # stamps the data cursor; superseded by later
                            # cadence saves and the boundary save (same
                            # epoch label, atomic overwrite)
                            self.save_checkpoint(epoch, completed=False)
                        self._check_preempt()
                finally:
                    # a step failure must not leave the loader thread pinning
                    # device batches
                    close = getattr(batches, "close", None)
                    if close is not None:
                        close()
                    batches = None
                # boundary work is beat-free by nature (validation's first
                # eval compile, the val sweep, checkpoint joins): suspend
                # stall detection or a long boundary reads as a hang
                if self._watchdog is not None:
                    self._watchdog.pause()
                elif self._heartbeat is not None:
                    self._heartbeat.beat(self.iteration, force=True)
                if self.telemetry is not None:
                    # boundary bracket (ISSUE 13): the health monitor
                    # suspends hang detection between begin and end, for
                    # the same reason the watchdog pauses here
                    self.telemetry.instant("train.boundary", epoch=epoch,
                                           phase="begin")
                try:
                    if self.sentinel is not None:
                        # enforce pending observations BEFORE the boundary
                        # checkpoint: a state the policy rejects must never
                        # be the published resume point
                        self.sentinel.check()
                    # epoch-boundary overlap (ISSUE 3): build the NEXT
                    # epoch's prefetcher BEFORE validate + checkpoint, so
                    # its loader thread refills the input queue while the
                    # host validates and the checkpoint writer runs — the
                    # first post-boundary step no longer starts on a cold
                    # queue (its 'wait' segment is the witness)
                    if epoch + 1 < model.n_epochs:
                        batches = self._make_prefetcher(epoch + 1)
                    val = self.validate(epoch)
                    self.save_checkpoint(epoch)
                finally:
                    if self.telemetry is not None:
                        self.telemetry.instant("train.boundary",
                                               epoch=epoch, phase="end")
                    if self._watchdog is not None:
                        self._watchdog.resume()
                    elif self._heartbeat is not None:
                        self._heartbeat.beat(self.iteration, force=True)
                # progress up to here is durably labeled: a preemption
                # arriving before the next step must not re-save (and must
                # not regress the published iteration)
                self._epoch_start_iter = self.iteration
                if self.telemetry is not None:
                    # restart the rate window: validation + checkpoint time
                    # must not deflate the next examples/s gauge
                    self._last_metrics_flush = None
                self.epoch = epoch + 1  # resume point: next, not this one
                self._check_preempt()
                if stop is not None and stop(epoch, val):
                    break
        finally:
            # an early stop() or an exception leaves the pre-built next-epoch
            # prefetcher alive — close it so its thread stops pinning batches
            if batches is not None:
                close = getattr(batches, "close", None)
                if close is not None:
                    close()

    def run(self, stop=None):
        """Train to completion.

        ``stop``: optional ``(epoch, val_metrics) -> bool`` checked after each
        epoch's validation — a True ends training early (used by the
        rule-comparison harness for train-to-target runs).

        Resilience (ISSUE 4, all opt-in — see the resilience package):
        a sentinel 'rollback' reloads the latest checkpoint in-process and
        retries; a preemption signal lands as a final synchronous
        checkpoint plus a :class:`PreemptionExit` carrying the distinct
        resumable exit code; a watchdog thread (under supervision) turns a
        stalled loop into a restartable hang exit.
        """
        if self._step_fn is None:
            self.compile_iter_fns()
        if self.params is None:
            self.init_state()
        if (self.telemetry is not None
                and self.telemetry.flight is not None):
            # the blackbox dump of a crashed run carries the topology it
            # died under (mesh axes, exchange strategy, model identity)
            self.telemetry.flight.set_fingerprint(self._run_fingerprint())
        model = self.model
        guard = None
        if self.resilience.preemption_enabled():
            guard = PreemptGuard(telemetry=self.telemetry)
            if not guard.install():  # not the main thread: stay inactive
                guard = None
        self._preempt_guard = guard
        self._watchdog = self.resilience.build_watchdog(self.telemetry)
        if self._watchdog is not None:
            self._watchdog.start()
        else:
            self._heartbeat = self.resilience.build_heartbeat()
        try:
            while True:
                try:
                    self._run_epochs(stop)
                    break
                except SentinelRollback as e:
                    self._handle_rollback(e)  # may escalate NonFiniteLossError
        except PreemptionRequested:
            if self._watchdog is not None:
                # the final synchronous checkpoint is beat-free and must
                # not be killed as a hang (76 would burn restart budget;
                # this exit is the budget-free 75)
                self._watchdog.stop()
                self._watchdog = None
            saved = self._preemption_checkpoint()
            if self.checkpointer is not None:
                # the preemption checkpoint is synchronous and complete:
                # drop the dirty marker so the resumed attempt takes the
                # cheap fast verify, not the full hash read
                self.checkpointer.mark_clean()
            if self.telemetry is not None:
                self.telemetry.instant("preempt.exit", epoch=self.epoch,
                                       iteration=self.iteration,
                                       checkpointed=saved)
            self.recorder.save()
            model.cleanup()
            raise PreemptionExit(
                f"preempted at epoch {self.epoch}, iteration "
                f"{self.iteration}"
                + ("; resumable checkpoint saved" if saved else ""))
        finally:
            self._preempt_guard = None
            if guard is not None:
                guard.uninstall()
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
            self._heartbeat = None
            # window ran past the end of training, OR an exception landed
            # inside it — either way the device trace must be stopped and
            # flushed, not leaked (the bounded-window contract)
            if self._profiling:
                self._profile_stop()
            # at most one in-flight checkpoint writer: exit joins it (like
            # the next save or a resume would), so a writer exception
            # surfaces here instead of dying with the daemon thread.  But
            # when a PRIMARY exception is already unwinding (often the same
            # root cause — full disk, dead mount), the writer's error must
            # not supersede it: report and let the original propagate (the
            # same correlated-failure discipline Rule.wait applies to
            # telemetry finalize)
            if self.checkpointer is not None:
                if sys.exc_info()[0] is None:
                    self.checkpointer.join_pending()
                else:
                    try:
                        self.checkpointer.join_pending()
                    except Exception as e:
                        print(f"checkpoint writer failed during teardown: "
                              f"{e}", file=sys.stderr)
        if self.checkpointer is not None:
            # clean-shutdown handshake (ISSUE 5): only a run that reaches
            # this line drops the dirty marker — the next resume of a
            # marker-holding directory pays the full-hash verify
            self.checkpointer.mark_clean()
        self.recorder.save()
        model.cleanup()
        return self.recorder


class Rule:
    """Reference-compatible rule facade shared by BSP/EASGD/GOSGD.

    Usage (mirrors the reference README pattern, SURVEY.md §3.1)::

        rule = BSP(config={"exch_strategy": "psum"})
        rule.init(devices=8, modelfile="theanompi_tpu.models.wide_resnet",
                  modelclass="WideResNet")
        rule.wait()

    ``devices`` is a worker count, a list of jax devices, or None (all
    devices).  ``init`` builds the mesh and compiles; ``wait`` runs training
    to completion and returns the recorder (there is no process tree to join
    — the "cluster" is the mesh).
    """

    def __init__(self, config: dict[str, Any] | None = None):
        self.config = config or {}
        self.trainer: BaseTrainer | None = None

    def make_trainer(self, model, mesh, recorder) -> BaseTrainer:
        raise NotImplementedError

    def common_trainer_kwargs(self, recorder) -> dict:
        """Base-trainer kwargs every rule forwards."""
        return dict(
            recorder=recorder,
            seed=self.config.get("seed", 0),
            prefetch_depth=self.config.get("prefetch", 2),
            checkpoint_dir=self.config.get("checkpoint_dir"),
            checkpoint_keep=self.config.get("checkpoint_keep", 3),
            checkpoint_async=self.config.get("checkpoint_async", True),
            # ISSUE 5: verify mode (auto = fast, full after unclean exit)
            # and the fingerprint-mismatch override (--resume-force)
            checkpoint_verify=self.config.get("checkpoint_verify", "auto"),
            # ISSUE 10: mid-epoch save cadence in iterations (0 = off);
            # each cadence save stamps the data-plane cursor so a crash
            # resumes at the newest iteration, not the epoch start
            checkpoint_every_n_iters=int(
                self.config.get("checkpoint_every_n_iters", 0) or 0),
            resume_force=bool(self.config.get("resume_force", False)),
            # ISSUE 8: open the elastic reshard gate (--resume-reshard)
            resume_reshard=bool(self.config.get("resume_reshard", False)),
            profile_dir=self.config.get("profile_dir"),
            # ISSUE 16: parse, don't tuple() — a launcher-provided
            # ``--rule-set profile_window=10:20`` string would otherwise
            # silently become a 5-char tuple and never open the window
            profile_window=_parse_profile_window(
                self.config.get("profile_window", (10, 20))),
            telemetry=self.make_telemetry(),
            # ISSUE 4: fault_plan / sentinel_* / watchdog* / heartbeat_path /
            # handle_preemption / prefetch_stall_timeout rule keys (see
            # ResilienceConfig.KEYS); defaults are all-off
            resilience=ResilienceConfig.from_rule_config(self.config),
        )

    def make_telemetry(self):
        """Telemetry sink from config (``telemetry_dir`` et al.), or None.

        Per-rank sink files: each process of a multi-host pod writes its
        own ``events-rank*.jsonl`` under the same directory; rank 0
        aggregates whatever is visible at the end of :meth:`wait`.
        """
        directory = self.config.get("telemetry_dir")
        if not directory:
            return None
        from theanompi_tpu.telemetry import Telemetry

        return Telemetry(
            directory,
            max_bytes=self.config.get("telemetry_max_bytes", 32 * 2**20),
            keep=self.config.get("telemetry_keep", 3),
            # ISSUE 13: health detectors + crash flight recorder default ON
            # whenever telemetry itself is on.  ``telemetry_health`` takes
            # False, True, or a dict of HealthConfig overrides;
            # ``telemetry_blackbox`` is the event-ring capacity (0 = off)
            health=self.config.get("telemetry_health", True),
            flight_recorder=int(
                self.config.get("telemetry_blackbox", 256) or 0),
            # ISSUE 16: step-time attribution defaults ON with telemetry
            # (``telemetry_profile=False`` opts out); publishes ``attr.*``
            # gauges + ATTRIB.json from the existing event stream
            profile=self.config.get("telemetry_profile", True),
        )

    def adjust_model_config(self, model_config: dict, n_workers: int) -> None:
        """Rule-specific model-config defaults (e.g. sync-BN for BSP)."""

    def init(
        self,
        devices=None,
        modelfile: str = "theanompi_tpu.models.wide_resnet",
        modelclass: str = "WideResNet",
        model_config: dict | None = None,
    ):
        n_model = self.config.get("n_model", 1)
        n_seq = self.config.get("n_seq", 1)
        n_pipe = self.config.get("n_pipe", 1)
        if isinstance(devices, int):
            # `devices` is the WORKER (data-parallel) count, as in the
            # reference API; pipe/model/seq axes multiply the device need
            need = devices * n_model * n_seq * n_pipe
            mesh = make_mesh(n_data=devices, n_model=n_model, n_seq=n_seq,
                             n_pipe=n_pipe, devices=jax.devices()[:need])
        elif devices is None:
            mesh = make_mesh(n_model=n_model, n_seq=n_seq, n_pipe=n_pipe)
        else:
            mesh = make_mesh(
                n_data=len(devices) // (n_model * n_seq * n_pipe),
                n_model=n_model, n_seq=n_seq, n_pipe=n_pipe, devices=devices,
            )
        n = mesh.shape[DATA_AXIS]
        model_config = dict(model_config or {})
        self.adjust_model_config(model_config, n)
        model_cls = import_model(modelfile, modelclass)
        model = model_cls(model_config)
        recorder = Recorder(
            print_freq=self.config.get("print_freq", 40),
            save_dir=self.config.get("record_dir"),
            verbose=self.config.get("verbose", model.verbose),
        )
        self.trainer = self.make_trainer(model, mesh, recorder)
        self.trainer.compile_iter_fns()
        self.trainer.init_state()
        if self.config.get("resume") or self.config.get("resume_reshard"):
            self.trainer.try_resume()
        return self

    def wait(self):
        """Run training to completion (reference: join the mpirun tree)."""
        if self.trainer is None:
            raise RuntimeError("call init() before wait()")
        tel = self.trainer.telemetry
        try:
            return self.trainer.run()
        finally:
            exc = sys.exc_info()[1]
            if tel is not None and tel.flight is not None and exc is not None:
                # last words BEFORE close(): the flight recorder dumps the
                # event ring + verdicts + fingerprint for any exception
                # escaping training, including the cooperative
                # PreemptionExit (a preempted run's blackbox is its proof
                # of orderly death)
                try:
                    tel.flight.dump(
                        ("preemption" if isinstance(exc, PreemptionExit)
                         else "crash"),
                        health=(tel.health.verdicts()
                                if tel.health is not None else None),
                        error=f"{type(exc).__name__}: {exc}")
                except OSError as e:
                    print(f"blackbox dump failed: {e}", file=sys.stderr)
            if tel is not None:
                # best-effort: a full disk / dead shared mount here (often
                # correlated with whatever killed training) must not mask
                # the primary exception propagating out of run()
                try:
                    tel.close()
                    if jax.process_index() == 0:
                        # rank-0 aggregation: Chrome trace + cross-rank
                        # step-skew / straggler summary over every rank
                        # file visible under the telemetry dir
                        from theanompi_tpu.telemetry import aggregate

                        aggregate.finalize(tel.directory)
                except Exception as e:
                    print(f"telemetry finalize failed: {e}", file=sys.stderr)
