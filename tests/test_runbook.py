"""CPU-mesh dry-run of the BASELINE.md RUNBOOK commands (VERDICT r4 #8).

The v5e-16 north-star procedure can't execute here (the hardware in reach
is one chip or one four-chip host), so this locks the *procedure*: the exact CLI entry points and flags
the RUNBOOK documents must parse, run end-to-end on the virtual mesh at
tiny scale, and emit artifacts with the fields the RUNBOOK's efficiency
arithmetic reads.  If a flag or artifact key changes, this breaks before
the doc rots.
"""

import json
import os

from theanompi_tpu import launcher


def test_runbook_launcher_command(tmp_path):
    """RUNBOOK step 4's tmlauncher invocation, shrunk to one tiny epoch
    (with the ISSUE 3 knobs: --checkpoint-dir and the checkpoint_async
    rule key the RUNBOOK documents; the compile cache is placed by
    JAX_COMPILATION_CACHE_DIR and locked by test_compile_cache_smoke)."""
    record = str(tmp_path / "record")
    telemetry = str(tmp_path / "telemetry")
    ckpt = str(tmp_path / "ckpt")
    rc = launcher.main([
        "--rule", "BSP", "--devices", "8",
        "--modelfile", "theanompi_tpu.models.resnet50",
        "--modelclass", "ResNet50",
        "--set", "batch_size=2", "--set", "n_epochs=1",
        "--set", "image_size=32", "--set", "store_size=40",
        "--set", "stage_blocks=(1,1,1,1)",
        "--set", "n_classes=4", "--set", "n_train=32", "--set", "n_val=16",
        "--set", "shard_size=16", "--set", "precision=fp32",
        "--rule-set", "exch_strategy=psum_bf16_bucket",
        "--rule-set", "exch_bucket_mb=4",
        "--rule-set", "exch_overlap=True",
        "--rule-set", "checkpoint_async=True",
        "--checkpoint-dir", ckpt,
        "--record-dir", record, "--telemetry-dir", telemetry, "--quiet",
    ])
    assert rc == 0
    # the recorder histories the RUNBOOK points at
    assert any(f.endswith(".npy") for f in os.listdir(record))
    # the telemetry artifacts the RUNBOOK's observability step points at
    files = os.listdir(telemetry)
    assert any(f.startswith("events-rank") for f in files)
    assert "trace.json" in files and "summary.json" in files
    trace = json.load(open(os.path.join(telemetry, "trace.json")))
    assert trace["traceEvents"]
    # the ISSUE 3 knob did its job: an async checkpoint published with
    # its latest pointer
    assert "latest.json" in os.listdir(ckpt)
    assert any(f.startswith("ckpt_e") and f.endswith(".npz")
               for f in os.listdir(ckpt))


def test_runbook_supervised_command(tmp_path, monkeypatch,
                                    subproc_compile_cache):
    """RUNBOOK step 5's supervised launch (`--supervise --max-restarts 3`)
    at toy scale: the supervisor parent runs in-process, the session runs
    in a child process, and the resilience.json audit trail lands next to
    the checkpoints (the exact flags BASELINE.md documents — ISSUE 4)."""
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_THREEFRY_PARTITIONABLE", "true")
    monkeypatch.setenv("PYTHONPATH",
                       repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    assert sys.executable
    ckpt = str(tmp_path / "ckpt")
    rc = launcher.main([
        "--rule", "BSP", "--devices", "4",
        "--modelfile", "theanompi_tpu.models.wide_resnet",
        "--modelclass", "WideResNet",
        "--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
        "--set", "image_size=8", "--set", "n_train=32", "--set", "n_val=16",
        "--set", "n_epochs=1", "--set", "precision='fp32'",
        "--checkpoint-dir", ckpt,
        "--supervise", "--max-restarts", "3", "--backoff-base", "0.5",
        "--quiet",
    ])
    assert rc == 0
    art = json.load(open(os.path.join(ckpt, "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["clean"]
    assert art["restarts"] == 0 and art["final_exit"] == 0
    assert "latest.json" in os.listdir(ckpt)


def test_runbook_data_resume_command(tmp_path, monkeypatch,
                                     subproc_compile_cache):
    """RUNBOOK step 5d's mid-epoch kill/resume rehearsal (ISSUE 10): the
    exact flag set BASELINE.md documents — `--rule-set
    checkpoint_every_n_iters=N` under `--supervise` with
    THEANOMPI_DATA_TRACE — killed one step INTO epoch 1, restarted, and
    the trace audit the runbook describes holds: one line per completed
    step, no batch replayed, none skipped."""
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_THREEFRY_PARTITIONABLE", "true")
    monkeypatch.setenv("PYTHONPATH",
                       repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    trace = str(tmp_path / "trace")
    monkeypatch.setenv("THEANOMPI_DATA_TRACE", trace)
    monkeypatch.setenv("THEANOMPI_FAULT_PLAN", "step:kill@3@1")
    assert sys.executable
    ckpt = str(tmp_path / "ckpt")
    rc = launcher.main([
        "--rule", "BSP", "--devices", "4",
        "--modelfile", "theanompi_tpu.models.wide_resnet",
        "--modelclass", "WideResNet",
        "--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
        "--set", "image_size=8", "--set", "n_train=32", "--set", "n_val=16",
        "--set", "n_epochs=2", "--set", "precision='fp32'",
        "--rule-set", "checkpoint_every_n_iters=1",
        # the runbook's determinism note: synchronous cadence saves
        "--rule-set", "checkpoint_async=False",
        "--checkpoint-dir", ckpt,
        "--supervise", "--max-restarts", "3", "--backoff-base", "0.1",
        "--quiet",
    ])
    assert rc == 0
    art = json.load(open(os.path.join(ckpt, "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]
    # the runbook's trace audit: gap-free, duplicate-free consumed-step
    # sequence across both attempts (2 epochs x 2 steps)
    lines = [tuple(int(v) for v in l.split())
             for l in open(trace) if l.strip()]
    assert lines == [(0, 0), (0, 1), (1, 0), (1, 1)]


def test_runbook_serve_command(tmp_path, capsys):
    """RUNBOOK step 6 (ISSUE 6): the exact `tmserve` invocation — verified
    read-only checkpoint load (matching --set config), continuous-batching
    engine, --quantize-int8, --telemetry-dir, SERVE.json artifact with the
    fields the runbook's headroom procedure reads."""
    import jax
    import numpy as np

    from theanompi_tpu.launcher import _parse_kv
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving import cli as serve_cli
    from theanompi_tpu.utils.checkpoint import Checkpointer, model_fingerprint

    tiny = ["dim=32", "heads=2", "n_layers=1", "seq_len=32", "vocab=61",
            "dropout=0.0", "precision=fp32", "n_train=64", "n_val=32"]
    # a training-writer checkpoint with the FULL run fingerprint — the
    # serving load must match on the model-identity subset only
    model = TransformerLM(_parse_kv(tiny))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "ckpt")
    writer = Checkpointer(ckpt, fingerprint={
        "mesh": {"data": 8}, "exchange": "psum_bf16_bucket", "n_subb": 1,
        **model_fingerprint(model)})
    writer.save(0, 5, {"params": jax.tree.map(np.asarray, params)})
    writer.mark_clean()

    out = str(tmp_path / "SERVE.json")
    tel = str(tmp_path / "telemetry-serve")
    rc = serve_cli.main([
        "--modelclass", "TransformerLM",
        *[a for s in tiny for a in ("--set", s)],
        "--checkpoint-dir", ckpt, "--requests", "4", "--arrival-rate", "50",
        "--prompt-len", "4", "--max-new-tokens", "4",
        "--max-batch", "2", "--block-size", "4", "--quantize-int8",
        "--telemetry-dir", tel, "--out", out, "--quiet",
    ])
    assert rc == 0
    art = json.load(open(out))
    # the fields step 6's headroom procedure reads
    assert art["metric"] == "serve_tokens_per_sec" and art["value"] > 0
    assert art["requests"] == 4 and art["checkpoint_epoch"] == 0
    assert "preemptions" in art and art["quantized_int8"]
    for h in ("ttft_ms", "token_ms"):
        assert "p50" in art[h] and "p99" in art[h]
    # one-JSON-line stdout (bench contract) + the Perfetto trace
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")][-1]
    assert json.loads(line)["metric"] == "serve_tokens_per_sec"
    trace = json.load(open(os.path.join(tel, "trace.json")))
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert "serve.prefill" in names and "serve.decode" in names


def test_runbook_serve_prefix_cache_command(tmp_path):
    """BASELINE step 6c (ISSUE 17): the exact multi-turn prefix-cache
    rehearsal invocation — --prefix-cache with --turns/--shared-prefix-len
    traffic — and the SERVE.json accounting fields the step reads
    (prefix_cache, prefix_hit_rate > 0, prefill_tokens_saved > 0)."""
    import jax
    import numpy as np

    from theanompi_tpu.launcher import _parse_kv
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving import cli as serve_cli
    from theanompi_tpu.utils.checkpoint import Checkpointer, model_fingerprint

    tiny = ["dim=32", "heads=2", "n_layers=1", "seq_len=32", "vocab=61",
            "dropout=0.0", "precision=fp32", "n_train=64", "n_val=32"]
    model = TransformerLM(_parse_kv(tiny))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "ckpt")
    writer = Checkpointer(ckpt, fingerprint={
        "mesh": {"data": 8}, "exchange": "psum_bf16_bucket", "n_subb": 1,
        **model_fingerprint(model)})
    writer.save(0, 5, {"params": jax.tree.map(np.asarray, params)})
    writer.mark_clean()

    out = str(tmp_path / "SERVE.json")
    rc = serve_cli.main([
        "--modelclass", "TransformerLM",
        *[a for s in tiny for a in ("--set", s)],
        "--checkpoint-dir", ckpt, "--requests", "6", "--arrival-rate", "50",
        "--prompt-len", "4", "--max-new-tokens", "4",
        "--max-batch", "2", "--block-size", "4",
        "--prefix-cache", "--turns", "3", "--shared-prefix-len", "8",
        "--out", out, "--quiet",
    ])
    assert rc == 0
    art = json.load(open(out))
    # the fields step 6c's procedure reads
    assert art["prefix_cache"] is True
    assert art["prefix_hit_rate"] > 0
    assert art["prefill_tokens_saved"] > 0
    assert art["requests"] == 6 and art["value"] > 0


def test_runbook_serve_decode_kernel_ab(tmp_path):
    """BASELINE step 6d (ISSUE 18): the decode-kernel A/B pair — the
    exact step-6 invocation re-run with --decode-kernel on and off — and
    the SERVE.json fields the comparison reads (decode_kernel naming the
    served impl, decode_step_ms percentiles per variant).  On the CPU
    dry-run "on" resolves to the Mosaic interpreter (bit-identical to the
    fallback by the tier-1 parity lock)."""
    import json

    from theanompi_tpu.serving import cli as serve_cli

    tiny = ["dim=32", "heads=2", "n_layers=1", "seq_len=32", "vocab=61",
            "dropout=0.0", "precision=fp32", "n_train=64", "n_val=32"]
    impls = {}
    for variant in ("on", "off"):
        out = str(tmp_path / f"SERVE_{variant}.json")
        rc = serve_cli.main([
            "--modelclass", "TransformerLM",
            *[a for s in tiny for a in ("--set", s)],
            "--requests", "3", "--prompt-len", "4", "--max-new-tokens", "4",
            "--max-batch", "2", "--block-size", "4",
            "--decode-kernel", variant, "--out", out, "--quiet",
        ])
        assert rc == 0
        art = json.load(open(out))
        impls[variant] = art["decode_kernel"]
        assert art["value"] > 0
        assert "p50" in art["decode_step_ms"]
        assert "p99" in art["decode_step_ms"]
    assert impls["off"] == "fallback"
    assert impls["on"] == "kernel_interpret"  # CPU host: interpreter


def test_runbook_router_command(tmp_path, monkeypatch, capsys):
    """BASELINE step 6e (ISSUE 19): the exact `tmrouter` invocation at
    toy scale — two REAL tmserve replicas leased as ``kind="serving"``
    fleet jobs on the mesh8 pool, the seeded open-loop trace balanced
    over their durable queues, and the ROUTER.json fields the step's
    procedure reads (exactly_once, router-visible ttft_ms percentiles,
    replica_trajectory, fleet_exit).  The contention/autoscale half is
    locked at full depth in test_router_e2e.py; replicas here inherit
    the session compile cache through the fleet child env."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("PYTHONPATH",
                       repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    monkeypatch.delenv("THEANOMPI_DATA_TRACE", raising=False)
    from theanompi_tpu.router import cli as router_cli

    d = str(tmp_path / "fleet")
    out = str(tmp_path / "ROUTER.json")
    tel = str(tmp_path / "telemetry-router")
    # same tiny shapes as the other step-6 dry-runs: the replica
    # subprocesses hit the session compile cache those tests warmed
    rc = router_cli.main([
        "--fleet-dir", d, "--pool-size", "8",
        "--replicas", "2", "--max-replicas", "2", "--replica-devices", "2",
        "--modelclass", "TransformerLM",
        "--set", "dim=32", "--set", "heads=2", "--set", "n_layers=1",
        "--set", "seq_len=32", "--set", "vocab=61", "--set", "dropout=0.0",
        "--set", "precision='fp32'", "--set", "n_train=64",
        "--set", "n_val=32",
        "--replica-arg=--max-batch", "--replica-arg=2",
        "--replica-arg=--block-size", "--replica-arg=4",
        "--requests", "4", "--vocab", "61", "--prompt-len", "4",
        "--max-new-tokens", "4", "--timeout-s", "120",
        "--telemetry-dir", tel, "--out", out, "--quiet",
    ])
    assert rc == 0
    art = json.load(open(out))
    # the fields step 6e's procedure reads
    assert art["exactly_once"] is True
    assert art["requests"] == 4 and art["answered"] == 4
    assert art["terminal_states"] == {"done": 4}
    assert art["metric"] == "router_tokens_per_sec" and art["value"] > 0
    assert "p50" in art["ttft_ms"] and "p99" in art["ttft_ms"]
    assert art["replicas_spawned"] == 2 and art["replicas_dead"] == 0
    assert art["fleet_exit"] == 0
    assert art["replica_trajectory"][-1][1] == 2
    # one-JSON-line stdout (bench contract)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")][-1]
    assert json.loads(line)["metric"] == "router_tokens_per_sec"
    # router.* telemetry flowed through the registered names
    ev_files = [f for f in os.listdir(tel) if f.startswith("events-rank")]
    assert ev_files
    body = open(os.path.join(tel, ev_files[0])).read()
    assert "router.dispatch" in body
    # every lease returned: both replica jobs drained to done
    from theanompi_tpu.fleet import read_record

    for jid in ("replica-0", "replica-1"):
        assert read_record(d, jid).status == "done"


def test_runbook_serve_resilience_command(tmp_path):
    """RUNBOOK step 6b (ISSUE 14): the resilient-serving flags of the
    exact invocation — deadlines + --shed, --drain-s, --rollout-watch —
    and the SERVE.json fields the runbook reads (terminal_states summing
    to requests, the rollout block, attempt, REQUESTS.jsonl).  The
    --supervise half (drain-under-SIGTERM, crash restart) is locked by
    the subprocess e2es in tests/test_serving_resilience.py."""
    import jax
    import numpy as np

    from theanompi_tpu.launcher import _parse_kv
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving import TERMINAL_STATES, terminal_rids
    from theanompi_tpu.serving import cli as serve_cli
    from theanompi_tpu.utils.checkpoint import Checkpointer, model_fingerprint

    tiny = ["dim=32", "heads=2", "n_layers=1", "seq_len=32", "vocab=61",
            "dropout=0.0", "precision=fp32", "n_train=64", "n_val=32"]
    model = TransformerLM(_parse_kv(tiny))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "ckpt")
    writer = Checkpointer(ckpt, fingerprint={
        "mesh": {"data": 8}, "exchange": "psum_bf16_bucket", "n_subb": 1,
        **model_fingerprint(model)})
    writer.save(0, 5, {"params": jax.tree.map(np.asarray, params)})
    writer.mark_clean()

    out = str(tmp_path / "SERVE.json")
    tel = str(tmp_path / "telemetry-serve")
    rc = serve_cli.main([
        "--modelclass", "TransformerLM",
        *[a for s in tiny for a in ("--set", s)],
        "--checkpoint-dir", ckpt, "--requests", "4", "--arrival-rate", "50",
        "--prompt-len", "4", "--max-new-tokens", "4",
        "--max-batch", "2", "--block-size", "4",
        "--total-deadline-ms", "30000", "--shed", "--drain-s", "20",
        "--rollout-watch", "--rollout-probation-s", "60",
        "--telemetry-dir", tel, "--out", out, "--quiet",
    ])
    assert rc == 0
    art = json.load(open(out))
    # the fields step 6b's procedure reads
    states = art["terminal_states"]
    assert set(states) <= set(TERMINAL_STATES)
    assert sum(states.values()) == art["requests"] == 4
    assert states.get("done") == 4  # nothing shed/expired at this load
    roll = art["rollout"]
    assert roll["rollouts"] == roll["rollbacks"] == roll["refused"] == 0
    assert roll["serving_epoch"] == 0
    assert art["attempt"] == 1 and art["drained"] is False
    # the durable per-request log a supervised restart dedups against
    assert terminal_rids(os.path.join(tel, "REQUESTS.jsonl")) == {0, 1, 2, 3}


def test_runbook_checkpoint_scrubber_command(tmp_path, capsys):
    """The RUNBOOK's checkpoint-hygiene step (ISSUE 5): the exact
    `python -m theanompi_tpu.utils.checkpoint --verify DIR` scrubber CLI
    must run, report per-checkpoint verdicts, and exit 0 on a healthy
    directory / 77 when anything fails verification."""
    import numpy as np

    from theanompi_tpu.utils import checkpoint as ck_mod

    d = str(tmp_path / "ckpt")
    ck = ck_mod.Checkpointer(d, keep=5)
    tree = {"a": np.arange(6, dtype=np.float32)}
    for e in range(2):
        ck.save(e, e, {"params": tree})
    assert ck_mod.main(["--verify", d]) == 0
    out = capsys.readouterr().out
    assert "2/2 checkpoints verifiable" in out
    # rot one file: the scrubber reports it and flips to the exit-code-
    # contract's checkpoint code (77)
    path = ck._path(1)
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(os.path.getsize(path) // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    assert ck_mod.main(["--verify", d]) == 77
    assert "CORRUPT" in capsys.readouterr().out


def test_runbook_reshard_plan_command(tmp_path, capsys):
    """The RUNBOOK's elastic-resume dry run (ISSUE 8): the exact
    `python -m theanompi_tpu.utils.checkpoint --reshard-plan DIR
    --to-devices N` invocation must plan a topology transition from the
    manifest alone and exit 0 plannable / 79 refused."""
    import numpy as np

    from theanompi_tpu.resilience import EXIT_RESHARD
    from theanompi_tpu.utils import checkpoint as ck_mod

    d = str(tmp_path / "ckpt")
    ck = ck_mod.Checkpointer(d, fingerprint={
        "mesh": {"data": 16, "pipe": 1, "model": 1, "seq": 1},
        "exchange": "zero1", "n_subb": 1,
        "model": "ResNet50", "model_config_sha": "deadbeef"})
    ck.save(0, 40, {
        "params": {"w": np.zeros((30,), np.float32)},
        "opt_state": {"velocity": [np.zeros((32,), np.float32)]}})
    ck.mark_clean()
    assert ck_mod.main(["--reshard-plan", d, "--to-devices", "8"]) == 0
    out = capsys.readouterr().out
    assert "reshard plan: 16 -> 8 workers" in out
    assert "LR x0.5" in out and "plannable" in out
    # an unplannable transition flips to the contract's reshard code
    assert ck_mod.main(["--reshard-plan", d, "--to-devices", "8",
                        "--strategy", "psum"]) == EXIT_RESHARD
    assert "REFUSED" in capsys.readouterr().out
    # the launcher accepts the runbook's --elastic spelling
    args = launcher.build_parser().parse_args(
        ["--elastic", "--devices", "all"])
    assert args.elastic


def test_runbook_tmlint_command(tmp_path, capsys):
    """The RUNBOOK's static-analysis gate (ISSUE 7): the exact
    `python -m theanompi_tpu.analysis --report LINT.json` invocation must
    run clean over the tree (exit 0), write the artifact with an empty
    findings list, and keep the justified suppressions auditable."""
    from theanompi_tpu.analysis import cli as lint_cli

    report = str(tmp_path / "LINT.json")
    rc = lint_cli.main(["--report", report])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 finding(s)" in out
    rep = json.loads(open(report).read())
    assert rep["tool"] == "tmlint" and rep["findings"] == []
    assert rep["summary"]["suppressed"] > 0  # markers stay visible


def test_runbook_tmlint_concurrency_tier(capsys):
    """BASELINE step 7's concurrency dry-run (ISSUE 15): the exact
    `tmlint --rules atomic-publish,guarded-state,thread-lifecycle,lock-order`
    subset must sweep the package clean — every durable writer publishes
    via os.replace (or carries a justified suppression), mixed-guard
    state and unnamed/unjoined threads stay out, and every nested lock
    acquisition matches the declared LOCK_ORDER_DAG."""
    from theanompi_tpu.analysis import cli as lint_cli

    rc = lint_cli.main(["--rules",
                        "atomic-publish,guarded-state,thread-lifecycle,"
                        "lock-order", "--quiet"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 finding(s)" in out
    # the append-mode audit logs ride on justified suppressions — they
    # must stay visible in the summary, not vanish
    import re

    m = re.search(r"(\d+) suppressed", out)
    assert m and int(m.group(1)) > 0


def test_runbook_fleet_command(tmp_path, monkeypatch, subproc_compile_cache):
    """RUNBOOK step 8's fleet rehearsal (ISSUE 11) at toy scale: the exact
    `tmfleet submit` / `run` / `status` flags BASELINE.md documents must
    drive two jobs through one mesh8 pool to completion (they fit side by
    side here — the contention/preemption half of the rehearsal is locked
    at full depth in test_fleet.py) and leave the artifacts the runbook
    reads: per-job job.json + resilience.json, fleet_events.jsonl, and
    the status JSON with every lease returned."""
    import sys

    from theanompi_tpu.fleet import cli as fleet_cli
    from theanompi_tpu.fleet import read_fleet_events

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_THREEFRY_PARTITIONABLE", "true")
    monkeypatch.setenv("PYTHONPATH",
                       repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    monkeypatch.delenv("THEANOMPI_DATA_TRACE", raising=False)
    assert sys.executable
    d = str(tmp_path / "fleet")
    tiny = ["--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
            "--set", "image_size=8", "--set", "n_train=32",
            "--set", "n_val=16", "--set", "n_epochs=1",
            "--set", "precision='fp32'"]
    for jid, pri in (("nightly", 0), ("ablation", 5)):
        assert fleet_cli.main([
            "submit", "--fleet-dir", d, "--job-id", jid,
            "--priority", str(pri), "--min-devices", "4",
            "--max-devices", "4", "--max-restarts", "3",
            "--backoff-base", "0.1", *tiny]) == 0
    assert fleet_cli.main(["run", "--fleet-dir", d, "--pool-size", "8",
                           "--quiet"]) == 0
    # the status JSON the runbook's verdict step reads
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert fleet_cli.main(["status", "--fleet-dir", d]) == 0
    status = json.loads(buf.getvalue())
    assert {j["status"] for j in status["jobs"]} == {"done"}
    assert status["pool"]["pool_size"] == 8 and status["pool"]["leases"] == {}
    # per-job artifacts: supervisor audit trail + published checkpoint
    for jid in ("nightly", "ablation"):
        jdir = os.path.join(d, "jobs", jid)
        art = json.load(open(os.path.join(jdir, "resilience.json")))
        assert art["final_exit"] == 0
        assert "latest.json" in os.listdir(os.path.join(jdir, "ckpt"))
    names = [e["event"] for e in read_fleet_events(d)]
    assert names.count("fleet.schedule") == 2
    assert names.count("fleet.complete") == 2


def test_runbook_fleet_async_command(tmp_path, monkeypatch,
                                     subproc_compile_cache):
    """RUNBOOK step 8b's contended-async rehearsal (ISSUE 20) at toy
    scale: the exact `tmfleet submit --rule EASGD` flags BASELINE.md
    documents, with a straggler injected through the documented
    `THEANOMPI_FAULT_PLAN`/`THEANOMPI_EASGD_SLOW_S` env pair, must drive
    the EASGD job to completion and leave the artifacts the step's
    verdict reads: per-job telemetry with `easgd.exchange` instants and
    a HEALTH.json whose async_staleness verdict is ok/warn, never
    critical.  (The preemption/elastic-resume half runs at full depth in
    test_fleet.py's chaos acceptance — this locks the CLI surface.)"""
    import sys

    from theanompi_tpu.fleet import cli as fleet_cli
    from theanompi_tpu.fleet import read_fleet_events
    from theanompi_tpu.fleet.jobs import read_record
    from theanompi_tpu.telemetry.health import read_health

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_THREEFRY_PARTITIONABLE", "true")
    monkeypatch.setenv("PYTHONPATH",
                       repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    monkeypatch.delenv("THEANOMPI_DATA_TRACE", raising=False)
    # the documented injection pair: one straggler at the second
    # elastic exchange, shrunk from the runbook's 0.6 s to keep the
    # dry-run fast (the flag surface is what this test locks)
    monkeypatch.setenv("THEANOMPI_FAULT_PLAN", "easgd:worker_slow@1")
    monkeypatch.setenv("THEANOMPI_EASGD_SLOW_S", "0.05")
    assert sys.executable
    d = str(tmp_path / "pool")
    tel = os.path.join(d, "jobs", "nightly-easgd", "telemetry")
    assert fleet_cli.main([
        "submit", "--fleet-dir", d, "--job-id", "nightly-easgd",
        "--priority", "0", "--min-devices", "4", "--max-devices", "4",
        "--rule", "EASGD", "--rule-set", "tau=1",
        "--rule-set", "scale_lr=False",
        "--rule-set", "checkpoint_every_n_iters=1",
        "--rule-set", "telemetry_health={'tick_s': 0.05}",
        "--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
        "--set", "image_size=8", "--set", "n_train=32",
        "--set", "n_val=16", "--set", "n_epochs=1",
        "--set", "precision='fp32'",
        "--max-restarts", "3", "--backoff-base", "0.1",
        f"--extra-arg=--telemetry-dir={tel}"]) == 0
    assert fleet_cli.main(["run", "--fleet-dir", d, "--pool-size", "8",
                           "--quiet"]) == 0
    rec = read_record(d, "nightly-easgd")
    assert rec.status == "done" and rec.spec.rule == "EASGD"
    names = [e["event"] for e in read_fleet_events(d)]
    assert names.count("fleet.schedule") == 1
    assert names.count("fleet.complete") == 1
    # the per-job telemetry the step-8b verdict reads
    ev_files = [f for f in sorted(os.listdir(tel))
                if f.startswith("events-rank")]
    assert ev_files
    events = [json.loads(ln)
              for ln in open(os.path.join(tel, ev_files[0]))]
    rounds = [e for e in events if e.get("name") == "easgd.exchange"]
    assert rounds  # tau=1, 2 steps/epoch -> 2 exchange instants
    assert all("staleness" in e and "stretch" in e for e in rounds)
    health = read_health(tel)
    assert health is not None
    sevs = {v["detector"]: v["severity"] for v in health["verdicts"]}
    assert sevs.get("async_staleness", "ok") in ("ok", "warn")


def test_runbook_tmprof_command(tmp_path, capsys):
    """BASELINE step 9 (ISSUE 16): the exact `tmprof ./telemetry` and
    `tmprof --ledger update/check` invocations.  The attribution table
    must come from a real telemetry dir (segments partitioning the
    window), the update must ingest a RUNBOOK artifact, and the check
    over a ledger backfilled from the repo's committed artifacts must
    exit 0 — the acceptance's no-false-regression half.  Both ledgers
    live under tmp_path: the repo-root PERF_LEDGER.jsonl is the driver's."""
    from theanompi_tpu.telemetry import Telemetry
    from theanompi_tpu.telemetry import prof

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tel_dir = str(tmp_path / "telemetry")
    tel = Telemetry(tel_dir, rank=0, profile=True)
    t = 100.0
    for step in range(3):
        tel.emit_span("recorder.wait", t, 0.004)
        t += 0.004
        tel.emit_span("train.step", t, 0.02, step=step)
        t += 0.02
        tel.emit_span("exchange.overlap", t, 0.002)
        t += 0.002
    tel.close()

    rc = prof.main([tel_dir])
    out = capsys.readouterr().out
    assert rc == 0, out  # compute-bound synthetic window: no host verdict
    assert "rank 0" in out and "[train]" in out and "verdict:" in out

    ledger = str(tmp_path / "TMPROF_LEDGER.jsonl")
    attrib = os.path.join(tel_dir, "ATTRIB.json")
    assert os.path.exists(attrib)  # close() published it
    rc = prof.main(["--ledger", "update", attrib, "--ledger-path", ledger])
    assert rc == 0
    assert "ingested" in capsys.readouterr().out

    backfilled = str(tmp_path / "repo_artifacts.jsonl")
    assert prof.main(["--ledger", "backfill", repo,
                      "--ledger-path", backfilled]) == 0
    rc = prof.main(["--ledger", "check", "--ledger-path", backfilled])
    capsys.readouterr()
    assert rc == 0, "repo's committed perf artifacts read as regressed"
