"""Rule-value comparison: BSP vs EASGD vs GOSGD trained to a target.

The reference's selling point (SURVEY.md §6, paper claim) is that EASGD is
wall-clock competitive with — or better than — BSP at equal accuracy.  Round
1 verified the rules' *mechanics* only; this harness measures their *value*:
train the same model from the same init under each rule on the same mesh,
stop when validation error first reaches a target (or at ``max_epochs``),
and record steps, epochs, and wall-clock to target.

Usage (also exposed as ``python -m theanompi_tpu.utils.rulecomp``)::

    from theanompi_tpu.utils.rulecomp import compare_rules
    results = compare_rules(devices=8, target_error=0.80,
                            out_path="rulecomp.json")

Each result row::

    {"rule": "easgd_tau4", "reached": true, "epochs_to_target": 3,
     "steps_to_target": 96, "epochs_run": 4, "steps_run": 128,
     "wall_s": 12.4, "effective_lr": 0.4, "best_val_error": 0.71,
     "val_error_curve": [...]}

``effective_lr`` is the model's base LR *after* the rule's hooks ran —
EASGD's reference ``scale_lr`` hook multiplies LR by the worker count by
default, so EASGD rows train hotter than BSP/GOSGD at the same config;
the field makes that confound visible in the artifact.

Compile time is excluded honestly: jit compiles at first *call*, not at
``compile_iter_fns``, so each run executes every compiled path once via
``trainer.warmup()`` (train step, the rule's exchange, eval), resets to a
fresh init, and only then starts the clock.  The virtual-CPU mesh measures
*algorithmic* value (steps/epochs to target); on real chips the same
harness measures comm cost too.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any

DEFAULT_MODEL_CONFIG = {
    "depth": 10,
    "widen": 1,
    "batch_size": 8,
    "image_size": 16,
    "n_train": 512,
    "n_val": 128,
    "precision": "fp32",
    "lr": 0.05,
}


def default_rulesets() -> list[tuple[str, str, dict]]:
    """-> [(name, rule_class_name, rule_config)] — the VERDICT #5 grid."""
    return [
        ("bsp", "BSP", {}),
        ("easgd_tau1", "EASGD", {"tau": 1}),
        ("easgd_tau4", "EASGD", {"tau": 4}),
        ("easgd_tau16", "EASGD", {"tau": 16}),
        ("gosgd", "GOSGD", {}),
    ]


def run_to_target(rule, *, devices, model_config: dict, target_error: float,
                  max_epochs: int, modelfile: str, modelclass: str,
                  metric: str = "error") -> dict:
    """Train one rule until the val ``metric`` <= target (or max_epochs);
    -> result row.  ``metric`` defaults to classification error; LM rows
    pass ``"perplexity"`` (the reference's headline LM metric)."""
    rule.init(devices=devices, modelfile=modelfile, modelclass=modelclass,
              model_config={**model_config, "n_epochs": max_epochs})
    rule.trainer.warmup()  # compile everything outside the timed window
    hit: dict[str, Any] = {}

    def stop(epoch: int, val: dict) -> bool:
        err = val.get(metric)
        if err is not None and err <= target_error and "epoch" not in hit:
            hit["epoch"] = epoch
            hit["steps"] = rule.trainer.iteration
        return "epoch" in hit

    t0 = time.perf_counter()
    rec = rule.trainer.run(stop=stop)
    wall = time.perf_counter() - t0
    curve = [float(e) for e in rec.val_history.get(metric, [])]
    row = {
        "reached": "epoch" in hit,
        "metric": metric,
        # post-hook LR: EASGD's scale_lr multiplies by n_workers by default
        "effective_lr": rule.trainer.model.config.get("lr"),
        "epochs_to_target": hit.get("epoch"),
        "steps_to_target": hit.get("steps"),
        "epochs_run": len(curve),
        "steps_run": rule.trainer.iteration,
        "wall_s": round(wall, 3),
        "best_val_error": min(curve) if curve else None,
        "val_error_curve": curve,
    }
    if metric != "error":
        # self-describing aliases (ADVICE r4): a perplexity row otherwise
        # reports its values only under error-named keys, disambiguated by
        # nothing but the ``metric`` field.  The error-named keys stay for
        # cross-metric consumers (``_better``, the sweep summaries).
        row[f"best_val_{metric}"] = row["best_val_error"]
        row[f"val_{metric}_curve"] = curve
    return row


def _better(a: dict, b: dict) -> bool:
    """Is row ``a`` a better outcome than row ``b``?  Reached beats not;
    among reached, fewer epochs then less wall time; among unreached,
    lower best val error."""
    if a["reached"] != b["reached"]:
        return a["reached"]
    if a["reached"]:
        return (a["epochs_to_target"], a["wall_s"]) < (
            b["epochs_to_target"], b["wall_s"])
    a_err = 1e9 if a["best_val_error"] is None else a["best_val_error"]
    b_err = 1e9 if b["best_val_error"] is None else b["best_val_error"]
    return a_err < b_err


def compare_rules(devices=8, model_config: dict | None = None,
                  target_error: float = 0.5, max_epochs: int = 8,
                  rules: list[tuple[str, str, dict]] | None = None,
                  modelfile: str = "theanompi_tpu.models.wide_resnet",
                  modelclass: str = "WideResNet",
                  lr_sweep: tuple[float, ...] | None = None,
                  out_path: str | None = None,
                  verbose: bool = True) -> dict:
    """Run the full comparison grid; -> artifact dict (optionally written).

    ``lr_sweep``: base LRs to try PER RULE; each rule is reported at its
    best-performing setting, with the whole sweep recorded alongside.
    This de-confounds the comparison (VERDICT r2 #6): EASGD's reference
    ``scale_lr`` hook multiplies the base LR by the worker count, so at a
    single shared base LR the rules train at different effective LRs and
    "reached target first" conflates rule value with LR luck.  With the
    sweep, each rule competes at its own tuned setting — the reference
    paper's wall-clock-to-accuracy claim is only meaningful that way.
    """
    import theanompi_tpu as tm

    model_config = {**DEFAULT_MODEL_CONFIG, **(model_config or {}),
                    "verbose": False}
    rows = []
    for entry in (rules or default_rulesets()):
        # (name, cls, cfg) or (name, cls, cfg, [rule-config overrides])
        # — the override list crosses with the LR sweep (VERDICT r3 #8:
        # EASGD's α must be swept JOINTLY with lr, not pinned)
        name, cls_name, cfg = entry[:3]
        overrides = entry[3] if len(entry) > 3 else [{}]
        sweep_rows = []
        for lr in (lr_sweep or (model_config["lr"],)):
            for ov in overrides:
                rule_cls = getattr(tm, cls_name)
                rule = rule_cls(config={**cfg, **ov, "seed": 0,
                                        "verbose": False})
                row = run_to_target(
                    rule, devices=devices,
                    model_config={**model_config, "lr": lr},
                    target_error=target_error, max_epochs=max_epochs,
                    modelfile=modelfile, modelclass=modelclass,
                )
                row["base_lr"] = lr
                if ov:
                    row["rule_overrides"] = dict(ov)
                sweep_rows.append(row)
        best = sweep_rows[0]
        for r in sweep_rows[1:]:
            if _better(r, best):
                best = r
        row = {"rule": name, "rule_class": cls_name, "rule_config": cfg,
               **best}
        if lr_sweep or len(sweep_rows) > 1:
            row["sweep"] = [
                {k: r[k] for k in ("base_lr", "effective_lr", "reached",
                                   "epochs_to_target", "steps_to_target",
                                   "best_val_error", "rule_overrides")
                 if k in r}
                for r in sweep_rows
            ]
        rows.append(row)
        if verbose:
            print(json.dumps(row), flush=True)
    artifact = {
        "model": f"{modelfile}.{modelclass}",
        "model_config": {k: v for k, v in model_config.items()},
        "devices": devices if isinstance(devices, int) else len(devices),
        "target_error": target_error,
        "max_epochs": max_epochs,
        "lr_sweep": list(lr_sweep) if lr_sweep else None,
        "results": rows,
    }
    if out_path:
        with open(out_path + ".tmp", "w") as f:
            json.dump(artifact, f, indent=1)
        os.replace(out_path + ".tmp", out_path)
    return artifact


#: α grid for the τ>1 diagnosis: 0.1125 is the old pinned default (0.9/8
#: per the EASGD paper's β=0.9); 0.05 couples looser, 0.3/0.5 tighter —
#: the paper's claim is that larger τ stays competitive with TUNED α.
#: The two ``scale_lr: False`` arms remove the remaining LR confound: with
#: the reference hook on, EASGD trains at 8x the base LR, so its effective
#: range would not overlap the LocalSGD control's at all and an LR-window
#: failure would masquerade as an elastic-coupling failure.
ALPHA_SWEEP = [{"alpha": 0.05}, {"alpha": 0.1125}, {"alpha": 0.3},
               {"alpha": 0.5},
               {"alpha": 0.1125, "scale_lr": False},
               {"alpha": 0.3, "scale_lr": False}]

#: VERDICT r4 #5: the r4 grid's smallest α (0.05) may simply still be too
#: large at τ=16 — the EASGD paper's stability condition couples α to τ
#: (smaller α at larger τ).  The deep sweep extends a full decade below,
#: all at the unscaled lr the r4 diagnosis validated; if every rung fails
#: while LocalSGD τ=16 passes, "elastic coupling fails at every α ≤ 0.05"
#: upgrades to "… at every α ≥ 0.00125 in a two-decade range" — a
#: scale-bound verdict, not a mis-parameterization.
ALPHA_SWEEP_DEEP = ALPHA_SWEEP + [
    {"alpha": a, "scale_lr": False}
    for a in (0.00125, 0.0025, 0.005, 0.0125, 0.025, 0.05)
]


def _diagnose(results: list[dict]) -> list[str]:
    """Name the failing factor per τ from the grid + control rows."""
    by = {r["rule"]: r for r in results}
    out = []
    for tau in (4, 16):
        e, c = by.get(f"easgd_tau{tau}"), by.get(f"localsgd_tau{tau}")
        if not (e and c):
            continue
        if e["reached"]:
            ov = e.get("rule_overrides", {})
            # the exclusive "hook was the confound" claim requires that NO
            # hook-on arm reached, not just that the best arm is hook-off
            hook_on_reached = any(
                s["reached"] and s.get("rule_overrides", {}).get(
                    "scale_lr", True) is not False
                for s in e.get("sweep", [e])
            )
            if ov.get("scale_lr") is False and not hook_on_reached:
                why = ("the reference scale_lr hook was the confound — "
                       "tau>1 needs the UNSCALED base lr (the r3 sweep "
                       "varied base lr with the n_workers-x hook always "
                       "on, so every setting trained too hot)")
            elif ov.get("scale_lr") is False:
                why = ("best at the unscaled lr, though a scale_lr-on arm "
                       "also reached — the hook hurts but is not the sole "
                       "factor")
            elif ov.get("alpha") is not None and ov["alpha"] != 0.1125:
                why = "the r3 failure was the pinned alpha, not tau"
            else:
                why = ("reached at the previously-pinned alpha — lr/grid "
                       "sensitivity rather than alpha")
            out.append(
                f"easgd_tau{tau}: reaches the target at base_lr="
                f"{e['base_lr']}, overrides={ov} "
                f"(epochs_to_target={e['epochs_to_target']}) — {why}"
            )
        elif c["reached"]:
            alphas = sorted({
                s["rule_overrides"]["alpha"]
                for s in e.get("sweep", [])
                if s.get("rule_overrides", {}).get("alpha") is not None
            })
            span = (f" (alpha swept {alphas[0]}–{alphas[-1]}, "
                    f"{len(alphas)} rungs)") if alphas else ""
            out.append(
                f"easgd_tau{tau}: fails at every (lr, alpha) in the "
                f"grid{span} while the plain-averaging control "
                f"localsgd_tau{tau} reaches the target (epochs_to_target="
                f"{c['epochs_to_target']}, base_lr={c['base_lr']}) — "
                f"tau-stale exchange per se is fine at this scale; the "
                f"ELASTIC COUPLING is the failing factor"
            )
        else:
            out.append(
                f"easgd_tau{tau}: neither EASGD at any (lr, alpha) nor the "
                f"plain-averaging control reaches the target (control best "
                f"val error {c['best_val_error']}) — tau-stale exchange "
                f"itself trades off convergence at this mini scale, "
                f"independent of the elastic/SPMD reformulation"
            )
    return out


def diagnose_easgd_tau(devices=8, model_config: dict | None = None,
                       target_error: float = 0.55, max_epochs: int = 8,
                       lr_sweep: tuple[float, ...] = (0.0125, 0.05, 0.2),
                       out_path: str | None = None,
                       verbose: bool = True) -> dict:
    """The VERDICT r3 #8 grid: EASGD τ∈{4,16} with α swept JOINTLY with
    lr, plus the control that separates scale from reformulation — BSP
    exchanging every τ steps (:class:`~theanompi_tpu.parallel.easgd
    .LocalSGD`, plain periodic averaging on the same budget).  The
    artifact's ``diagnosis`` section names which factor fails."""
    rules = [
        ("bsp", "BSP", {}),
        ("easgd_tau1", "EASGD", {"tau": 1}),
        ("easgd_tau4", "EASGD", {"tau": 4}, ALPHA_SWEEP),
        # τ=16 gets the two-decade α sweep (VERDICT r4 #5)
        ("easgd_tau16", "EASGD", {"tau": 16}, ALPHA_SWEEP_DEEP),
        ("localsgd_tau4", "LocalSGD", {"tau": 4}),
        ("localsgd_tau16", "LocalSGD", {"tau": 16}),
        ("gosgd", "GOSGD", {}),
    ]
    art = compare_rules(devices=devices, model_config=model_config,
                        target_error=target_error, max_epochs=max_epochs,
                        rules=rules, lr_sweep=lr_sweep, out_path=None,
                        verbose=verbose)
    art["diagnosis"] = _diagnose(art["results"])
    if out_path:
        with open(out_path + ".tmp", "w") as f:
            json.dump(art, f, indent=1)
        os.replace(out_path + ".tmp", out_path)
    return art


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--target-error", type=float, default=None,
                   help="default: 0.5 for the rule grid, 0.55 for "
                        "--diagnose-easgd (each path's function default)")
    p.add_argument("--max-epochs", type=int, default=8)
    p.add_argument("--lr-sweep", default=None,
                   help="comma-separated base LRs to tune each rule over")
    p.add_argument("--out", default="rulecomp.json")
    p.add_argument("--force-host-devices", type=int, default=None,
                   help="fake N virtual CPU devices (same as JAX_PLATFORMS="
                        "cpu XLA_FLAGS=--xla_force_host_platform_device_"
                        "count=N)")
    p.add_argument("--diagnose-easgd", action="store_true",
                   help="run the tau>1 diagnosis grid (alpha x lr sweep + "
                        "local-SGD controls) instead of the default grid")
    a = p.parse_args(argv)
    if a.force_host_devices:
        from theanompi_tpu.parallel.mesh import force_host_devices

        force_host_devices(a.force_host_devices)
    sweep = (tuple(float(x) for x in a.lr_sweep.split(","))
             if a.lr_sweep else None)
    if a.diagnose_easgd:
        art = diagnose_easgd_tau(devices=a.devices,
                                 target_error=(0.55 if a.target_error is None
                                               else a.target_error),
                                 max_epochs=a.max_epochs,
                                 lr_sweep=sweep or (0.0125, 0.05, 0.2),
                                 out_path=a.out)
        for line in art["diagnosis"]:
            print(line)
    else:
        art = compare_rules(devices=a.devices,
                            target_error=(0.5 if a.target_error is None
                                          else a.target_error),
                            max_epochs=a.max_epochs, lr_sweep=sweep,
                            out_path=a.out)
    reached = [r for r in art["results"] if r["reached"]]
    print(json.dumps({
        "reached": len(reached), "of": len(art["results"]), "out": a.out
    }))


if __name__ == "__main__":
    main()
