"""The benchmark's one command.

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run.  ``BENCHMARK.json`` names the cell's configuration,
traffic and chips; everything else is found by name under ``benchmarks/``:
``configs/<configuration>.json`` (the file the entry names),
``traffic/<traffic>.json`` (which names its generator and its kind of
driver), ``cells/<cell>.json`` (the limits ``correct`` is decided by) and,
for each per-layer metric, ``metrics/<metric>.json`` (which names its
reader under ``readers/``).  No accelerator, or fewer chips than the cell
asks for, is an exit with no result line: never a CPU number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from benchmarks.common import ROOT, load_json  # noqa: E402
from benchmarks.peaks import peaks_for  # noqa: E402

EXIT_NO_CHIP = 3


def load_cell(workload: str) -> dict:
    """Everything the files say about one cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"benchmarks: no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    check_config(cfg, config)
    return dict(bench=bench, entry=entry, cfg=cfg,
                traffic=load_json("traffic", entry["traffic"] + ".json"),
                cell=load_json("cells", workload + ".json"))


def check_config(cfg: dict, entry: dict) -> None:
    """Refuse a size that differs from the published one and is not listed."""
    listed = set(cfg["reduced"])
    if listed != set(entry["reduced"]) or cfg["source"] != entry["source"]:
        raise SystemExit(f"benchmarks: {entry['file']} and BENCHMARK.json "
                         f"disagree on source or reduced")
    for key, published in cfg["published"].items():
        if cfg.get(key) != published and key not in listed:
            raise SystemExit(
                f"benchmarks: {entry['file']}: {key} = {cfg.get(key)!r} differs "
                f"from the published {published!r} and is not under 'reduced'")


def set_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at the checkout's ``.jax_cache/``: a fixed path (the path is
    part of the cache key), the same one the program's entry points use."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = os.path.join(ROOT, ".jax_cache")
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


def find_chips(n: int):
    """The first ``n`` accelerator devices, or an exit with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < n:
        print(f"benchmarks: the cell needs {n} accelerator chip(s); jax reports "
              f"{len(devices)} x {devices[0].platform}: no result",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    return devices[:n]


class CompileCount:
    """Counts programs built as they happen: compiled, or loaded from the
    persistent cache (JAX reports both under the one event)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def per_layer(loaded: dict, workload: str, run: dict) -> dict:
    """Each per-layer metric of this cell, by its own declaration and
    reader; a reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in loaded["bench"]["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        decl = load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module(f"benchmarks.readers.{decl['reader']}")
        value = reader.read(run, **decl.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(loaded: dict, workload: str, seed: int, seconds: float, trace: int,
            devices) -> dict:
    """A whole run on ``devices``; -> the result line as a dict."""
    import jax

    cfg, traffic, entry = loaded["cfg"], loaded["traffic"], loaded["entry"]
    compiles = CompileCount()
    marks: dict = {}
    ctx = dict(workload=workload, cfg=cfg, traffic=traffic, cell=loaded["cell"],
               seed=seed, seconds=seconds, trace=trace, devices=list(devices),
               marks=marks, compiles=compiles)
    driver = importlib.import_module(f"benchmarks.{traffic['kind']}")
    res = driver.run(ctx)
    setup_s = marks["window_open"] - T_START
    split = {"weights_s": marks["weights_s"] - T_START,
             "warm_up_s": marks["warm_up_s"] - marks["weights_s"],
             "lead_in_s": marks["window_open"] - marks["warm_up_s"],
             "setup_s": setup_s, "programs_compiled_in_set_up": marks["compiles_open"],
             "programs_compiled_in_window": marks["compiles_close"] - marks["compiles_open"]}
    print("setup " + json.dumps(split), flush=True)

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": jax.device_count(),
              "memory_peak_bytes": res["counters"]["memory_peak_bytes"]}
    line = {"correct": all(c["value"] <= c["limit"] for c in res["compared"]),
            "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        reduced = res["trace"].reduce(len(devices), traffic["host_spans"],
                                      traffic["main_module"])
        run = dict(series=res["series"], counters=res["counters"], trace=reduced,
                   peaks=peaks_for(kind), chips=len(devices), cfg=cfg,
                   traffic=traffic)
        line["metrics"] = per_layer(loaded, workload, run)
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        units = {m["name"]: m["unit"] for m in loaded["bench"]["end_to_end"]}
        e2e = dict(res["e2e"], setup_s=setup_s)
        line["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()}
    line["device"] = device
    line["extra"] = dict(res.get("extra", {}), window_s=res["counters"]["window_s"])
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in res["compared"]}
    for c in res["compared"]:
        print(f"compared {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload)
    import theanompi_tpu  # noqa: F401  the system under test; absent = no result
    set_compile_cache()
    devices = find_chips(loaded["entry"]["chips"])
    line = execute(loaded, args.workload, args.seed, args.seconds, args.trace,
                   devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
