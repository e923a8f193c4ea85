"""Scaling-efficiency measurement harness (the north-star metric's tool).

BASELINE.json's north star is >= 90% linear BSP scaling efficiency for
ResNet-50 on a TPU pod; the reference paper's headline was near-linear
AlexNet speedup to 8 GPUs (SURVEY.md §6, unverified).  This harness makes
that checkable: for each worker count n it measures pipelined step time on an
n-device data mesh and reports

- **weak-scaling efficiency**: images/sec/chip at n relative to n=1 (the
  per-worker batch is fixed, the global batch grows with n — the reference's
  setting);
- **comm share**: the fraction of device op time spent in collectives,
  measured from the profiler trace (``measure_comm_share`` — per-op device
  events, collective kinds summed; validated by an injection test that
  plants a fat collective and asserts a nonzero share).  The old
  *differential* estimate (same step compiled with the ``none`` strategy)
  is kept as ``comm_share_differential`` for comparison, but it is
  noise-dominated on shared/virtual setups and never resolved a signal.

Run on the CPU fake mesh (collectives are memcpys — the harness validates
the *machinery* and gives an upper bound on framework overhead) or on a real
multi-chip slice (the numbers that count).  CLI::

    python -m theanompi_tpu.utils.scaling --ns 1,2,4,8 --out SCALING.json
    # no multi-chip hardware? add --virtual 8 (forces host devices)
    # exchange-strategy microbenchmark (HLO collective counts + static
    # wire bytes per strategy — exact on any backend):
    python -m theanompi_tpu.utils.scaling --exchange-bench --ns 4 \
        --strategies psum,psum_bucket,ring_int8,zero1 --out EXCHANGE.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import tempfile

import numpy as np

#: collective names across both backends: TPU HLO instruction kinds (via
#: the roofline op classifier) and CPU thunk/primitive names
_CPU_COLLECTIVES = ("psum", "pmean", "all_gather", "all_to_all", "ppermute",
                    "reduce_scatter", "all-reduce", "all-gather",
                    "all-to-all", "collective-permute", "reduce-scatter")
_CPU_OP_RE = re.compile(r"^[a-z][\w\-]*(\.\d+)?$")


def _trace_comm_split(logdir: str) -> tuple[float, float]:
    """-> (collective seconds, total op seconds) from the newest xplane.

    TPU: the device plane's per-HLO-op events (same classification as the
    roofline tool).  CPU (virtual mesh): the ``tf_XLA*`` executor lines
    carry per-thunk events named after the lowered primitives
    (``psum.7``, ``dot_general.3``); summing across worker threads
    weights ops by total worker time, which is the right denominator for
    a SHARE (the absolute seconds are thread-summed, not wall — see
    ``comm_op_s_per_step``).  Validated by an injection test that plants
    a deliberately fat collective and asserts a nonzero share (VERDICT
    r2 #5 — the old differential method never measured anything but 0).
    The xplane is parsed exactly once and both backends read the same
    ``XSpace``.
    """
    import glob
    import os

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from theanompi_tpu.utils.roofline import _op_kind

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {logdir}")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())

    comm = total = 0.0
    saw_device = False
    for plane in xs.planes:
        if "TPU" not in plane.name and "device" not in plane.name.lower():
            continue
        emeta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                saw_device = True
                kind = _op_kind(emeta.get(ev.metadata_id, ""))
                if kind == "while":
                    continue
                total += ev.duration_ps
                if kind == "collective":
                    comm += ev.duration_ps
    if saw_device:
        return comm / 1e12, total / 1e12

    # CPU fallback: executor thread lines on the host plane
    for plane in xs.planes:
        if "CPU" not in plane.name:
            continue
        emeta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for ev in line.events:
                nm = emeta.get(ev.metadata_id, "")
                if not _CPU_OP_RE.match(nm):
                    continue  # waits, rendezvous, pool bookkeeping, end: markers
                total += ev.duration_ps
                base = nm.split(".")[0]
                if base in _CPU_COLLECTIVES:
                    comm += ev.duration_ps
    return comm / 1e12, total / 1e12


def _have_xplane_protos() -> bool:
    """Whether tensorflow's xplane protos (the trace parser's only
    third-party need) are importable — probed before any profiled run."""
    import importlib.util

    try:
        return importlib.util.find_spec(
            "tensorflow.tsl.profiler.protobuf.xplane_pb2") is not None
    except Exception:  # lint: swallow-ok — degrade to null comm_share
        # the intent is "null comm_share instead of crashing" on ANY broken
        # tensorflow install — find_spec can raise more than ImportError
        # (e.g. a protobuf version mismatch during package init, ADVICE r4)
        return False


def measure_comm_share(trainer, batches, steps: int = 6, lr: float = 0.01):
    """Profiler-backed communication share of the train step.

    -> (comm_share, comm_seconds, total_op_seconds).  Runs ``steps``
    dispatched steps under ``jax.profiler.trace`` (single end sync, the
    bench dispatch pattern) and splits device-side op time into
    collective vs everything else.
    """
    import jax

    m = trainer.train_iter(batches[0], lr=lr)  # warm outside the trace
    float(m["cost"])
    with tempfile.TemporaryDirectory(prefix="commshare_") as logdir:
        with jax.profiler.trace(logdir):
            for i in range(steps):
                m = trainer.train_iter(batches[i % len(batches)], lr=lr)
            float(m["cost"])
        comm_s, total_s = _trace_comm_split(logdir)
    return (comm_s / total_s if total_s else 0.0), comm_s, total_s


def _build(model_name: str, model_config: dict, n: int, strategy: str,
           bucket_mb: float = 4.0, overlap: bool = False,
           telemetry_dir: str | None = None):
    import jax

    from theanompi_tpu.parallel.bsp import BSPTrainer
    from theanompi_tpu.parallel.mesh import make_mesh
    from theanompi_tpu.utils.helper_funcs import import_model, shard_batch
    from theanompi_tpu.utils.recorder import Recorder

    model_cls = import_model(f"theanompi_tpu.models.{model_name}",
                             {"wide_resnet": "WideResNet",
                              "resnet50": "ResNet50",
                              "alex_net": "AlexNet"}.get(model_name, model_name))
    cfg = dict(model_config)
    if n > 1:
        cfg.setdefault("bn_axis", "data")  # BSP default: sync-BN
    model = model_cls(cfg)
    mesh = make_mesh(n_data=n, devices=jax.devices()[:n])
    telemetry = None
    if telemetry_dir:
        # ISSUE 13: an opted-in bench rung is health-watchable live
        # (tmhealth <dir>) — per-step spans add host overhead, so the
        # measured numbers are only comparable to other telemetry-on runs
        from theanompi_tpu.telemetry import Telemetry

        telemetry = Telemetry(telemetry_dir, health=True,
                              flight_recorder=256)
    trainer = BSPTrainer(model, mesh=mesh, exch_strategy=strategy,
                         exch_bucket_mb=bucket_mb, exch_overlap=overlap,
                         telemetry=telemetry,
                         recorder=Recorder(verbose=False, print_freq=10**9))
    trainer.compile_iter_fns()
    trainer.init_state()
    batches = [
        shard_batch(mesh, b, spec=trainer.batch_spec)
        for b in model.data.train_batches(trainer.global_batch, 0, seed=0)
    ]
    jax.block_until_ready(batches)
    return trainer, batches


def measure_scaling(
    model_name: str = "wide_resnet",
    model_config: dict | None = None,
    ns=(1, 2, 4, 8),
    steps: int = 10,
    trials: int = 3,
    strategy: str = "psum",
    out_path: str | None = None,
    telemetry_dir: str | None = None,
) -> dict:
    """-> the artifact dict (and writes it to ``out_path`` if given)."""
    import jax

    from theanompi_tpu.utils.benchlib import best_trial

    model_config = model_config or {
        "batch_size": 32, "n_train": 256, "n_val": 64,
        "n_epochs": 1, "augment": False, "verbose": False,
    }
    per_n = {}
    # probed ONCE before the loop (ADVICE r4: calling it per n re-imported
    # tensorflow every iteration) — and only when some rung will measure
    # comm share at all (the n=1 rung has no collectives to profile)
    have_xplane = any(n > 1 for n in ns) and _have_xplane_protos()
    for n in ns:
        # per-rung telemetry subdir: each rung's sink would otherwise
        # truncate the previous rung's events
        tdir = (None if telemetry_dir is None
                else f"{telemetry_dir}/n{int(n)}")
        trainer, batches = _build(model_name, model_config, n, strategy,
                                  telemetry_dir=tdir)
        # warmup: compile both programs' first dispatch
        m = trainer.train_iter(batches[0], lr=0.01)
        float(m["cost"])
        (dt, _, _), results = best_trial(trainer, batches, steps, trials)
        times = [r[0] for r in results]
        if trainer.telemetry is not None:
            trainer.telemetry.close()

        t_noex = dt
        comm_share = comm_s = 0.0
        if n > 1:
            tr2, b2 = _build(model_name, model_config, n, "none")
            m = tr2.train_iter(b2[0], lr=0.01)
            float(m["cost"])
            (t_noex, _, _), _ = best_trial(tr2, b2, steps, trials)
            # profiler-backed split (the validated measurement; the
            # differential column is kept for comparison but is
            # noise-dominated on shared/virtual setups).  The xplane
            # parser needs tensorflow's profiler protos — on a JAX-only
            # install record comm_share as null instead of crashing
            # (ADVICE r3 #1); the differential column below remains the
            # only estimate in that case.  Availability was probed once
            # before the loop so no profiled run is wasted.
            if have_xplane:
                comm_share, comm_s, _ = measure_comm_share(
                    trainer, batches, steps=steps)
            else:
                comm_share = comm_s = None

        ips = steps * trainer.global_batch / dt
        per_n[int(n)] = {
            "global_batch": trainer.global_batch,
            "step_ms": round(dt / steps * 1e3, 3),
            "imgs_per_sec": round(ips, 2),
            "imgs_per_sec_per_chip": round(ips / n, 2),
            "comm_share": (None if comm_share is None
                           else round(comm_share, 4)),
            # thread-summed op seconds (NOT wall time — on an n-device
            # virtual mesh the executor threads' durations add up): only
            # meaningful relative to the same sum for all ops, which is
            # exactly what comm_share reports
            "comm_op_s_per_step": (None if comm_s is None
                                   else round(comm_s / steps, 6)),
            "comm_share_differential": (
                round(max(0.0, 1.0 - t_noex / dt), 4) if n > 1 else 0.0),
            "trial_s": [round(t, 4) for t in times],
        }
    for n in ns:
        per_n[int(n)]["efficiency"] = round(
            per_n[int(n)]["imgs_per_sec_per_chip"]
            / per_n[int(ns[0])]["imgs_per_sec_per_chip"],
            4,
        )
    artifact = {
        "model": model_name,
        "strategy": strategy,
        "platform": jax.devices()[0].platform,
        "steps": steps,
        "trials": trials,
        "ns": [int(n) for n in ns],
        # efficiency is relative to the SMALLEST measured n; only a run
        # whose ns include 1 measures the true vs-one-chip north star
        "efficiency_base_n": int(ns[0]),
        "per_n": per_n,
        "north_star": "efficiency >= 0.9 at pod scale (BASELINE.json)",
    }
    if jax.devices()[0].platform != "tpu":
        artifact["caveat"] = (
            "virtual host-device mesh: the n workers compete for the same "
            "host cores, so 'efficiency' here measures host-FLOP contention "
            "plus framework overhead, NOT interconnect scaling; only the "
            "machinery (mesh build, collectives, comm-share accounting) is "
            "being validated. The north-star number requires a real "
            "multi-chip slice."
        )
    if out_path:
        with open(out_path + ".tmp", "w") as f:
            json.dump(artifact, f, indent=1)
        os.replace(out_path + ".tmp", out_path)
    return artifact


#: the exchange microbenchmark's default strategy sweep
EXCHANGE_BENCH_STRATEGIES = (
    "psum", "psum_bf16", "psum_bucket", "psum_bf16_bucket",
    "ring", "ring_bucket", "ring_int8", "zero1",
)


def exchange_microbench(
    model_name: str = "wide_resnet",
    model_config: dict | None = None,
    n: int = 4,
    strategies=EXCHANGE_BENCH_STRATEGIES,
    steps: int = 4,
    trials: int = 1,
    bucket_mb: float = 4.0,
    overlap: bool = False,
    out_path: str | None = None,
) -> dict:
    """Exchange-strategy microbenchmark on an ``n``-device mesh.

    For each strategy: HLO-derived collective counts of the compiled train
    step (``telemetry.metrics.hlo_collective_counts`` — the honest
    launch-overhead proxy when the collective is fused into one XLA
    program), static per-step wire bytes (``Exchanger.wire_bytes``), bucket
    layout, and pipelined step time.  On the CPU fake mesh the *times*
    only bound framework overhead; the collective counts and byte
    accounting are exact on any backend — that is the point: bucketing
    regressions show up as op-count jumps with no TPU attached.

    ``overlap=True`` (ISSUE 12) adds the fused-vs-overlapped comparison:
    every bucketed strategy is built a second time with ``exch_overlap``
    and a shared ``none``-strategy baseline is measured once, so each row
    gains ``step_ms_overlap`` plus the differential comm shares
    ``comm_share_differential`` (fused) and
    ``comm_share_differential_overlap`` — the overlap claim is precisely
    that the second number approaches zero (comm hidden under backward)
    while wire bytes and collective counts stay identical.
    """
    import jax

    from theanompi_tpu.parallel.exchanger import BUCKETED_STRATEGIES
    from theanompi_tpu.telemetry.metrics import hlo_collective_counts
    from theanompi_tpu.utils.benchlib import best_trial

    model_config = model_config or {
        "batch_size": 8, "n_train": 64, "n_val": 16,
        "n_epochs": 1, "augment": False, "verbose": False,
    }

    def timed(strategy, ov=False):
        trainer, batches = _build(model_name, model_config, n, strategy,
                                  bucket_mb=bucket_mb, overlap=ov)
        m = trainer.train_iter(batches[0], lr=0.01)  # compile + warm
        float(m["cost"])
        counts = hlo_collective_counts(trainer.compiled_step_text(batches[0]))
        (dt, _, _), _ = best_trial(trainer, batches, steps, trials)
        return trainer, counts, dt

    t_base = None
    if overlap and n > 1:
        # ONE exchange-free baseline shared by every differential column
        _, _, t_base = timed("none")

    per_strategy = {}
    for strategy in strategies:
        trainer, counts, dt = timed(strategy)
        row = {
            "collectives": counts,
            "collective_ops_total": sum(counts.values()),
            "wire_bytes_per_step": trainer.exchange_wire_bytes(),
            "step_ms": round(dt / steps * 1e3, 3),
        }
        buckets = trainer.exchanger.bucket_summary(
            trainer._shard_param_structs(), n)
        if buckets:
            row["buckets"] = buckets
        if t_base is not None:
            row["comm_share_differential"] = round(
                max(0.0, 1.0 - t_base / dt), 4)
        if overlap and strategy in BUCKETED_STRATEGIES:
            _, counts_ov, dt_ov = timed(strategy, ov=True)
            row["step_ms_overlap"] = round(dt_ov / steps * 1e3, 3)
            # the schedule lock rides along: overlap must not change WHAT
            # is communicated, only WHEN (audited in analysis/hlo_audit)
            row["overlap_collectives_equal"] = (counts_ov == counts)
            if t_base is not None:
                row["comm_share_differential_overlap"] = round(
                    max(0.0, 1.0 - t_base / dt_ov), 4)
        per_strategy[strategy] = row
    artifact = {
        "model": model_name,
        "n": int(n),
        "platform": jax.devices()[0].platform,
        "steps": steps,
        "bucket_mb": bucket_mb,
        "overlap": bool(overlap),
        "per_strategy": per_strategy,
        "note": ("collective counts + wire bytes are static/exact on any "
                 "backend; step_ms is only meaningful on real chips"),
    }
    if out_path:
        with open(out_path + ".tmp", "w") as f:
            json.dump(artifact, f, indent=1)
        os.replace(out_path + ".tmp", out_path)
    return artifact


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="wide_resnet")
    p.add_argument("--ns", default="1,2,4,8")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--trials", type=int, default=3)
    p.add_argument("--strategy", default="psum")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--set", dest="model_set", action="append", default=[],
                   metavar="K=V",
                   help="extra model-config entry (repeatable; same syntax "
                   "as tmlauncher --set, e.g. --set image_size=64)")
    p.add_argument("--out", default="SCALING.json")
    p.add_argument("--telemetry-dir", default=None,
                   help="per-rung telemetry + live health under "
                   "<dir>/n<N> (ISSUE 13; watch with tmhealth) — adds "
                   "per-step span overhead, so compare only against "
                   "other telemetry-on runs")
    p.add_argument("--virtual", type=int, default=0,
                   help="force N virtual host (CPU) devices first")
    p.add_argument("--exchange-bench", action="store_true",
                   help="run the exchange-strategy microbenchmark instead "
                   "of the scaling ladder (HLO collective counts + static "
                   "wire bytes + step time per strategy)")
    p.add_argument("--strategies",
                   default=",".join(EXCHANGE_BENCH_STRATEGIES),
                   help="comma list for --exchange-bench")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   help="fused-bucket size for the bucketed strategies")
    p.add_argument("--overlap", action="store_true",
                   help="with --exchange-bench: add the fused-vs-overlapped "
                   "(exch_overlap) comparison column per bucketed strategy, "
                   "plus differential comm shares against a shared "
                   "no-exchange baseline")
    args = p.parse_args(argv)
    if args.virtual:
        from theanompi_tpu.parallel.mesh import force_host_devices

        force_host_devices(args.virtual)
    from theanompi_tpu.parallel.mesh import setup_compile_cache

    # a sweep compiles one program per rung; re-runs load them
    setup_compile_cache()
    ns = tuple(int(x) for x in args.ns.split(","))
    cfg = {"batch_size": args.batch_size, "n_train": max(256, args.batch_size * 8),
           "n_val": 64, "n_epochs": 1, "augment": False, "verbose": False}
    from theanompi_tpu.launcher import _parse_kv

    cfg.update(_parse_kv(args.model_set))
    if args.exchange_bench:
        out = ("EXCHANGE.json" if args.out == "SCALING.json" else args.out)
        art = exchange_microbench(
            args.model, cfg, n=ns[-1],
            strategies=tuple(args.strategies.split(",")),
            steps=args.steps, trials=args.trials,
            bucket_mb=args.bucket_mb, overlap=args.overlap, out_path=out)
        for s, r in art["per_strategy"].items():
            c = r["collectives"]
            ov = (f"  ov {r['step_ms_overlap']:8.3f} ms"
                  if "step_ms_overlap" in r else "")
            print(f"{s:18s} step {r['step_ms']:8.3f} ms{ov}  "
                  f"wire {r['wire_bytes_per_step']:>12}  "
                  f"ar {c.get('all-reduce', 0):3d}  "
                  f"rs {c.get('reduce-scatter', 0):3d}  "
                  f"ag {c.get('all-gather', 0):3d}  "
                  f"perm {c.get('collective-permute', 0):3d}")
        print(f"wrote {out}")
        return
    art = measure_scaling(args.model, cfg, ns=ns, steps=args.steps,
                          trials=args.trials, strategy=args.strategy,
                          out_path=args.out,
                          telemetry_dir=args.telemetry_dir)
    for n in art["ns"]:
        r = art["per_n"][n]
        comm = ("  n/a" if r["comm_share"] is None
                else f"{r['comm_share']:5.3f}")
        print(f"n={n}: {r['imgs_per_sec']:9.1f} img/s "
              f"({r['imgs_per_sec_per_chip']:8.1f}/chip)  "
              f"eff {r['efficiency']:5.3f}  comm {comm}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
