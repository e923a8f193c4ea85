"""Tiny stand-ins for the cells, so a test run can hold a whole rehearsal:
the real files with every size shrunk (never used for a number)."""

import copy

from benchmarks import run as bench_run

#: limits for the tiny sizes, set as the cells' own are (``PERF.md``): above
#: what sound tiny runs read on the CPU (first gradient 0.0032, change
#: 0.0041, logit gap 0.0015) and below what the fp8 control reads there
#: (0.0097, 0.017, 0.0063); the real sizes' limits are the cells' files'
TINY_LIMITS = {"train": {"loss_gap": 0.002, "first_grad_gap": 0.008,
                         "change_gap": 0.012},
               "serve": {"widest_logit_gap": 0.004}}

WORKLOADS = {"train": "cgpt13.train.1chip", "bsp4": "cgpt13.train.bsp4",
             "serve": "cgpt13.serve.backlog"}


def tiny_cell(kind: str) -> tuple[str, dict]:
    workload = WORKLOADS[kind]
    loaded = copy.deepcopy(bench_run.load_cell(workload))
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    loaded["cell"]["limits"] = dict(TINY_LIMITS["serve" if kind == "serve" else "train"])
    cfg.update(n_layer=2, n_embd=64, n_head=2, n_inner=256, n_positions=64,
               vocab_size=211)
    if kind == "serve":
        cfg["run"].update(max_batch=8, num_blocks=128, block_size=8)
        # every answer has the 16 gaps tpot_ms_p90 asks for, so a loaded
        # test machine still finishes enough of them in its short window
        traffic["pairs"] = [[8 + (i * 7) % 20, 18 + (i * 5) % 12]
                            for i in range(4096)]
        traffic.update(lead_in_s=0.3, check_requests=3)
    else:
        cfg["run"].update(per_chip_batch=2)  # half the cell's rows a chip
        traffic.update(seq_len=64, steps_per_epoch=8,
                       rows_per_step=traffic["rows_per_step"] // 2)
    return workload, loaded


def rehearse(kind: str, n_devices: int = 1, seed: int = 2**31 + 77) -> dict:
    """The harness without its look for a chip: a whole run on CPU devices."""
    import jax

    workload, loaded = tiny_cell(kind)
    return bench_run.execute(loaded, workload, seed=seed,
                             seconds=2.0 if kind == "serve" else 1.0,
                             trace=0, devices=jax.devices()[:n_devices])


def well_formed(line, metric):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "compared"
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


def failed_names(line):
    return sorted(k for k, c in line["compared"].items() if c["value"] > c["limit"])
