"""The model contract: the duck-typed interface rules drive models through.

Reference (unverified — SURVEY.md §2.3): upstream documents "how to add a
customized model" — a class with ``__init__(config)``, attributes
``batch_size``/``n_epochs``/``data``/``params``, methods
``compile_iter_fns``/``train_iter``/``val_iter``/``adjust_hyperp``/
``scale_lr``/``cleanup``.  The split here is the idiomatic-jax factoring of
exactly that contract:

- the **model** owns hyperparameters, the data object, pure ``init_params``
  and ``loss_fn``, the LR schedule (``adjust_hyperp``) and the optimizer
  choice — everything that defines *what* is trained;
- the **rule's trainer** owns compilation and iteration
  (``compile_iter_fns``/``train_iter``/``val_iter`` live there) — everything
  about *how* steps execute and exchange.

``loss_fn`` is pure and traced once; there is no ``theano.function``
compile-per-model machinery to port — ``jax.jit`` over the rule's step *is*
the ``mode=XLA`` linker the north star asks for.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from theanompi_tpu.ops import SGD, softmax_cross_entropy, top_k_error
from theanompi_tpu.ops.layers import Layer
from theanompi_tpu.parallel.mesh import BF16, FP32, DATA_AXIS, Precision


class Model:
    """Base model: config merging + the contract surface.

    Subclasses must provide ``build_data()`` and either override
    ``init_params``/``loss_fn`` or use :class:`SupervisedModel`.
    """

    default_config: dict[str, Any] = {}

    def __init__(self, config: dict[str, Any] | None = None):
        self.config = {**self.default_config, **(config or {})}
        self.verbose = self.config.get("verbose", True)
        self.batch_size = self.config.get("batch_size", 128)
        self.n_epochs = self.config.get("n_epochs", 10)
        self.precision: Precision = (
            BF16 if self.config.get("precision", "bf16") == "bf16" else FP32
        )
        self.data = self.build_data()

    # -- construction hooks -------------------------------------------------
    def build_data(self):
        raise NotImplementedError

    def build_optimizer(self):
        return SGD(
            momentum=self.config.get("momentum", 0.9),
            weight_decay=self.config.get("weight_decay", 0.0),
            nesterov=self.config.get("nesterov", False),
            grad_clip=self.config.get("grad_clip"),
        )

    def init_opt_state(self, optimizer, params):
        """Optimizer-state layout; GANs override to split per network."""
        return optimizer.init(params)

    # -- sharding hooks (defaults = pure data parallelism) -------------------
    def param_specs(self, params):
        """PartitionSpec per param leaf (tensor-parallel models override
        with :func:`theanompi_tpu.parallel.tensor.specs_from_rules`)."""
        return jax.tree.map(lambda _: P(), params)

    def state_specs(self, state):
        return jax.tree.map(lambda _: P(), state)

    def opt_state_specs(self, optimizer, param_specs):
        """Mirrors ``init_opt_state``; GANs override to split per network."""
        return optimizer.init_specs(param_specs)

    def batch_partition(self) -> P:
        """Leading-dims spec for batches (truncated per leaf rank).
        Sequence-parallel models return ``P("data", "seq")``."""
        return P(DATA_AXIS)

    def grad_reduce_axes(self) -> tuple[str, ...]:
        """Mesh axes gradients are mean-reduced over (the BSP exchange).
        Sequence-parallel models add ``"seq"`` — each seq shard computes a
        partial gradient of the token-mean loss."""
        return (DATA_AXIS,)

    # -- pure functions the trainer compiles --------------------------------
    def init_params(self, rng):
        """-> (params, state) pytrees (fp32 params; state = BN buffers etc.)."""
        raise NotImplementedError

    def loss_fn(self, params, state, batch, rng, train: bool):
        """-> (loss, (new_state, metrics)).  Pure; traced under jit."""
        raise NotImplementedError

    # -- schedule -----------------------------------------------------------
    def adjust_hyperp(self, epoch: int) -> float:
        """Learning rate for ``epoch`` (reference method name preserved).

        Default: base LR with step decay at configured epochs.
        """
        lr = self.config.get("lr", 0.1)
        for e in self.config.get("lr_decay_epochs", ()):
            if epoch >= e:
                lr *= self.config.get("lr_decay_factor", 0.1)
        return lr

    def scale_lr(self, size: int) -> None:
        """Linear LR scaling with worker count (reference EASGD hook)."""
        self.config["lr"] = self.config.get("lr", 0.1) * size

    def resolved_paths(self) -> dict:
        """The implementations this run resolved where the code chooses
        from platform or shape (kernel vs XLA, C vs numpy): the launcher
        prints them, so a fallback is visible in the run's own output."""
        data_paths = getattr(self.data, "resolved_paths", None)
        return data_paths() if data_paths else {}

    def cleanup(self) -> None:
        if hasattr(self.data, "cleanup"):
            self.data.cleanup()


class SupervisedModel(Model):
    """Classification models: a net (ops layers) + softmax CE + top-k error.

    Subclasses implement ``build_net() -> (Layer, in_shape)``; batches are
    ``{"x": [B, ...], "y": [B] int}``.
    """

    #: weight on auxiliary-head losses (train-time only; GoogLeNet paper §5)
    aux_loss_weight = 0.3

    def __init__(self, config=None):
        super().__init__(config)
        self.net, self.in_shape = self.build_net()

    def build_net(self) -> tuple[Layer, tuple]:
        raise NotImplementedError

    def init_params(self, rng):
        params, state, out_shape = self.net.init(rng, self.in_shape)
        self._out_shape = out_shape
        return params, state

    def apply_net(self, params, state, x, *, train, rng):
        """-> (logits, aux_logits, new_state).  Models with auxiliary
        classifier heads override to return per-head logits during training;
        the shared ``loss_fn`` folds them in at ``aux_loss_weight`` so l2 and
        metrics handling stay in one place."""
        logits, new_state = self.net.apply(params, state, x, train=train, rng=rng)
        return logits, (), new_state

    def l2_sq_norm(self, params):
        """Squared L2 norm of the params, sharding-aware: leaves whose spec
        shards mesh axes (pipe-stacked blocks, expert weights) are psummed
        over those axes so the l2 term — and hence the loss — is replicated
        on every shard."""
        from theanompi_tpu.ops.opt import global_sq_norm

        return global_sq_norm(params, self.param_specs(params))

    def prepare_x(self, x):
        if x.dtype == jnp.uint8:
            # images travel host->device as uint8 (4x fewer bytes than
            # fp32 — the transfer is the input pipeline's scarce resource);
            # the cast+normalize runs on device, where XLA fuses it into
            # the first conv
            x = x.astype(self.precision.compute_dtype)
            stats = getattr(self.data, "norm_stats", None)
            if stats is not None:
                mean, inv_std = stats
                x = (x - jnp.asarray(mean, x.dtype)) * jnp.asarray(
                    inv_std, x.dtype
                )
        elif jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(self.precision.compute_dtype)  # int tokens stay int
        return x

    def loss_fn(self, params, state, batch, rng, train: bool):
        x = self.prepare_x(batch["x"])
        compute_params = self.precision.cast_to_compute(params)
        logits, aux_logits, new_state = self.apply_net(
            compute_params, state, x, train=train, rng=rng
        )
        loss = softmax_cross_entropy(logits, batch["y"])
        for a in aux_logits:
            loss = loss + self.aux_loss_weight * softmax_cross_entropy(
                a, batch["y"]
            )
        if self.config.get("l2", 0.0):
            # reference models folded L2 into the graph cost; weight_decay on
            # the optimizer is the decoupled alternative
            loss = loss + self.config["l2"] * self.l2_sq_norm(params)
        metrics = {
            "cost": loss,
            "error": top_k_error(logits, batch["y"], k=1),
            "error_top5": top_k_error(logits, batch["y"], k=5)
            if logits.shape[-1] >= 5
            else jnp.zeros((), jnp.float32),
        }
        return loss, (new_state, metrics)
