"""Training driver: whole steps of ``BaseTrainer.train_iter`` between fences.

The trainer is a ``BSPTrainer`` built as ``launcher.py`` builds it
(``BSP().init(...)``); rows reach it through the model's own data plane
and the trainer's own prefetcher.  The weights, the rows, the loop, the
clock and the comparison are the benchmark's.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import reference, weights, work
from benchmarks.common import (TraceWindow, annotate, import_generator,
                               memory_peak_bytes, model_config)

#: steps the host may run ahead of the device: it waits for step i - LAG
#: before it dispatches step i + 1, so the device never idles and the
#: window overshoots ``--seconds`` by at most LAG steps
LAG = 2
CHECK_STEPS = 3


def build(cfg: dict, traffic: dict, seed: int, devices):
    from theanompi_tpu import BSP

    run = cfg["run"]
    n = len(devices)
    global_batch = int(traffic["rows_per_step"])
    if global_batch != run["per_chip_batch"] * n:
        raise SystemExit(
            f"benchmarks: traffic {traffic['name']} feeds {global_batch} rows a "
            f"step; {n} chip(s) at the configuration's per_chip_batch "
            f"{run['per_chip_batch']} take {run['per_chip_batch'] * n}")
    mc = model_config(cfg)
    mc.update(seq_len=traffic["seq_len"], batch_size=run["per_chip_batch"],
              lr=run["lr"], momentum=run["momentum"], grad_clip=run["grad_clip"],
              n_epochs=10**9, n_train=global_batch, n_val=global_batch)
    rule = BSP(config={"seed": int(seed) & 0x7FFFFFFF, "print_freq": 10**9,
                       "verbose": False, "exch_strategy": run["exch_strategy"]})
    rule.init(devices=list(devices),
              modelfile="theanompi_tpu.models.transformer_lm",
              modelclass="TransformerLM", model_config=mc)
    trainer = rule.trainer
    model = trainer.model
    # the seed's weights take the place of the trainer's own init, as a
    # restored checkpoint would; the momentum stays at its zeros
    trainer.params = weights.seeded_params(model, cfg, seed,
                                           NamedSharding(trainer.mesh, P()))
    rows = import_generator(traffic).generate(
        traffic, seed, vocab=cfg["vocab_size"], global_batch=global_batch)
    model.data._train_seqs, model.data.n_train = rows, len(rows)
    return trainer


def feed(trainer):
    """Batches as ``_run_epochs`` draws them: the trainer's prefetcher over
    the model's ``train_batches``, one epoch after another."""
    epoch = 0
    while True:
        batches = trainer._make_prefetcher(epoch)
        try:
            yield from batches
        finally:
            close = getattr(batches, "close", None)
            if close is not None:
                close()
        epoch += 1


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def _canonical_norms(tree) -> dict:
    return {weights.canonical(path): float(v)
            for path, v in weights.tree_paths(jax.device_get(tree))}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = statistics.median(ref.values())
    keys = [k for k in ref if keep is None or keep(k)]
    return max(abs(prog[k] - ref[k]) / max(ref[k], median) for k in keys)


def compare(prog: dict, ref: dict, limits: dict) -> list[dict]:
    """``prog``/``ref``: ``{"losses", "first_grad", "change"}``.  Leaves
    whose first gradient in the reference is under a thousandth of the
    median leaf's are left out of the change (they move by round-off)."""
    g_med = statistics.median(ref["first_grad"].values())
    moved = lambda k: ref["first_grad"][k] >= 1e-3 * g_med  # noqa: E731
    out = [{"name": f"loss_gap_step{i + 1}", "value": abs(p - r),
            "limit": limits["loss_gap"]}
           for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))]
    out.append({"name": "first_grad_gap",
                "value": worst_leaf_gap(prog["first_grad"], ref["first_grad"]),
                "limit": limits["first_grad_gap"]})
    out.append({"name": "change_gap",
                "value": worst_leaf_gap(prog["change"], ref["change"], moved),
                "limit": limits["change_gap"]})
    return out


def check_steps(trainer, batches, cfg, seed, lr) -> tuple[dict, list]:
    """Drive the trainer through its first steps by the window's own call
    and feed; -> (the program's readings, the rows it was fed)."""
    shapes = jax.eval_shape(lambda: trainer.params)
    change = jax.jit(lambda p, key: _norms(jax.tree.map(
        jnp.subtract, p, weights.program_tree(cfg, key, shapes))))
    losses, fed, first = [], [], None
    for i in range(CHECK_STEPS):
        batch = next(batches)
        fed.append((np.asarray(batch["x"]), np.asarray(batch["y"])))
        with annotate("train_iter"):
            m = trainer.train_iter(batch, lr)
        losses.append(m["cost"])
        if i == 0:
            # v1 = -lr * g1: the first gradient as the optimizer got it
            first = {k: v / lr for k, v in _canonical_norms(
                _norms(trainer.opt_state["velocity"])).items()}
    moved = _canonical_norms(change(trainer.params, weights.seed_key(seed)))
    return {"losses": [float(c) for c in losses], "first_grad": first,
            "change": moved}, fed


def run(ctx: dict) -> dict:
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    seconds, marks, devices = ctx["seconds"], ctx["marks"], ctx["devices"]
    run_cfg = cfg["run"]
    lr = float(run_cfg["lr"])
    trainer = build(cfg, traffic, seed, devices)
    jax.block_until_ready(trainer.params)
    marks["weights_s"] = time.perf_counter()
    batches = feed(trainer)
    prog, fed = check_steps(trainer, batches, cfg, seed, lr)
    for _ in range(int(traffic["warm_up_steps"])):
        m = trainer.train_iter(next(batches), lr)
    jax.block_until_ready((m["cost"], trainer.params))
    marks["warm_up_s"] = time.perf_counter()

    tokens_per_step = int(traffic["rows_per_step"]) * traffic["seq_len"]
    trace = TraceWindow(ctx, float(traffic.get("trace_seconds", 10.0)))
    costs, done_t, dispatch_t, wait_s = [], [], [], 0.0
    t_open = time.perf_counter()
    marks["window_open"], marks["compiles_open"] = t_open, ctx["compiles"].n
    trace.arm(t_open, seconds)
    while True:
        t0 = time.perf_counter()
        with annotate("data.fetch"):
            batch = next(batches)
        wait_s += time.perf_counter() - t0
        dispatch_t.append(t0)
        with annotate("train_iter"):
            costs.append(trainer.train_iter(batch, lr)["cost"])
        if len(costs) > LAG:
            with annotate("fence"):
                jax.block_until_ready(costs[-1 - LAG])
            done_t.append(time.perf_counter())
        trace.poll()
        if time.perf_counter() - t_open >= seconds:
            break
    jax.block_until_ready((costs[-1], trainer.params))
    t_close = time.perf_counter()
    marks["compiles_close"] = ctx["compiles"].n
    trace.close(t_close)
    window_s = t_close - t_open
    peak = memory_peak_bytes(devices)
    losses = [float(c) for c in costs]
    batches.close()

    n_steps = len(costs)
    e2e = {"train_tokens_per_s_per_chip":
           n_steps * tokens_per_step / window_s / len(devices)}
    series = {"trainer.step_ms": [(b - a) * 1e3 for a, b in zip(done_t, done_t[1:])]}
    traced_steps = sum(trace.covers(a, b) for a, b in zip(done_t, done_t[1:]))
    clean = [t for t in done_t if t <= trace.untraced_until]
    flops_per_step = tokens_per_step * work.train_flops_per_token(cfg, traffic["seq_len"])
    counters = {
        "window_s": window_s, "steps": n_steps, "tokens": n_steps * tokens_per_step,
        "data_wait_s": wait_s, "memory_peak_bytes": peak,
        "rows_per_chip_step": run_cfg["per_chip_batch"],
        "seq_len": traffic["seq_len"], "traced.steps": traced_steps,
        # whole steps completed while no profiler was attached, and their time
        "mfu_flops": (len(clean) - 1) * flops_per_step,
        "mfu_s": clean[-1] - clean[0] if len(clean) > 1 else 0.0,
        "kernel_flops_per_run": run_cfg["per_chip_batch"]
        * work.flash_train_flops_per_row(cfg, traffic["seq_len"]),
        "kernel_bytes_per_run": run_cfg["per_chip_batch"]
        * work.flash_train_bytes_per_row(cfg, traffic["seq_len"]),
    }
    failed = sum(not math.isfinite(x) for x in losses)

    # -- correct: the reference follows the first three steps ----------------
    for tree in (trainer.params, trainer.opt_state, trainer.state):
        for leaf in jax.tree.leaves(tree):
            leaf.delete()
    del trainer, batches, batch, costs
    gc.collect()
    hp = (lr, float(run_cfg["momentum"]), float(run_cfg["grad_clip"] or 0.0))
    t_check = time.perf_counter()
    ref = dict(zip(("losses", "first_grad", "change"),
                   reference.train_steps(cfg, seed, fed, hp)))
    compared = compare(prog, ref, ctx["cell"]["limits"])
    compared.append({"name": "nonfinite_losses", "value": failed, "limit": 0})
    return dict(e2e=e2e, series=series, counters=counters, attempted=n_steps,
                failed=failed, compared=compared, trace=trace,
                extra={"loss_first": losses[0], "loss_last": losses[-1],
                       "check_s": time.perf_counter() - t_check})
