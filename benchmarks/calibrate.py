"""Readings the limits in ``cells/*.json`` are set from (not part of a run).

    python -m benchmarks.calibrate --workload <cell> --seeds 1,2,3 [--seconds 8]

Training: the reference put in the program's place at the cell's own size,
(a) one precision down (the fp8 control), (b) with half of the batch left
out and the mean taken over the rest, (c) with the exchange left out (one
chip's rows only), each read by the run's own comparison against the
float32 reference.  No measured window is needed.  Serving: a short run of
the cell at its own load per seed, all in this one process, and over each
run's sample the gap of the token the fp8 control puts first.
One JSON line per seed and reading.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def _train(loaded: dict, seeds: list[int], chips: int) -> None:
    from benchmarks import reference, train
    from benchmarks.common import import_generator

    cfg, traffic = loaded["cfg"], loaded["traffic"]
    run = cfg["run"]
    per_chip, gb = run["per_chip_batch"], int(traffic["rows_per_step"])
    hp = (float(run["lr"]), float(run["momentum"]), float(run["grad_clip"] or 0.0))
    keys = ("losses", "first_grad", "change")
    limits = loaded["cell"]["limits"]
    for seed in seeds:
        rows = import_generator(traffic).generate(
            traffic, seed, vocab=cfg["vocab_size"], global_batch=gb)
        fed = [(rows[i * gb:(i + 1) * gb, :-1], rows[i * gb:(i + 1) * gb, 1:])
               for i in range(train.CHECK_STEPS)]

        def repeat_first(n):  # the mean over n rows repeated is the mean over them
            return [tuple(np.tile(a[:n], (gb // n, 1)) for a in xy) for xy in fed]
        ref = dict(zip(keys, reference.train_steps(cfg, seed, fed, hp)))
        variants = {"control_fp8": (fed, "fp8"),
                    "fault_half_batch": (repeat_first(gb // 2), "fp32")}
        if chips > 1:
            variants["fault_no_exchange"] = (repeat_first(per_chip), "fp32")
        for name, (batches, precision) in variants.items():
            got = dict(zip(keys, reference.train_steps(cfg, seed, batches, hp, precision)))
            print(json.dumps({"seed": seed, "reading": name, **{
                c["name"]: c["value"] for c in train.compare(got, ref, limits)}}),
                flush=True)


def _serve(loaded: dict, workload: str, seeds: list[int], seconds: float, devices):
    from benchmarks import run as bench_run

    for seed in seeds:
        loaded["traffic"]["calibrate_control"] = True
        line = bench_run.execute(loaded, workload, seed, seconds, 0, devices)
        print(json.dumps({"seed": seed, "reading": "program_and_control_fp8",
                          "program": line["compared"], **line["extra"]}), flush=True)


def main(argv=None) -> int:
    from benchmarks import run as bench_run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    loaded = bench_run.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench_run.set_compile_cache()
    devices = bench_run.find_chips(1)  # the reference runs on one chip
    if loaded["traffic"]["kind"] == "train":
        _train(loaded, seeds, loaded["entry"]["chips"])
    else:
        _serve(loaded, args.workload, seeds, args.seconds, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
