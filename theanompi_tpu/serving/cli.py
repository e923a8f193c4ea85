"""tmserve: the serving CLI (ISSUE 6).

Serves synthetic open-loop traffic against a ``TransformerLM``-family
checkpoint through the continuous-batching engine and reports tokens/sec +
p50/p99 time-to-first-token and per-token latency — the serving twin of
``tmlauncher``, sharing its config surface (``--set`` k=v pairs must
reproduce the training config: the verified load checks the model
class + config sha recorded in the checkpoint manifest) and its exit-code
contract (0 clean, 70 crash, 77 no verifiable checkpoint, 78 config error,
one ``tmserve: error:`` stderr line each).

Checkpoints load STRICTLY via the PR 5 verified chain
(:func:`theanompi_tpu.utils.checkpoint.load_for_inference` — read-only:
safe against a directory a live trainer owns); ``--serve-force`` mirrors
``--resume-force`` for deliberate config drift.  Without
``--checkpoint-dir`` the model serves its random init (a throughput bench
needs weights, not learning).

Example::

    tmserve --modelclass TransformerLM \
        --set dim=256 --set n_layers=4 --set seq_len=256 \
        --checkpoint-dir ./ckpt --requests 64 --arrival-rate 32 \
        --max-batch 8 --num-blocks 96 --quantize-int8 --out SERVE.json

A ``HybridLM`` (serving-only; no ``--prefix-cache``)::

    tmserve --modelfile theanompi_tpu.models.hybrid_lm --modelclass HybridLM \
        --set "pattern='MEM*E'" --set dim=256 --set seq_len=256 \
        --requests 16 --max-batch 8 --out SERVE.json

A looped stack — the pattern applied ``loops`` times with shared weights,
``-`` a gated FFN, rotary positions, a norm after each mixer; the cache
holds ``loops`` entries a ``*`` layer, so size ``--num-blocks`` by ``2 x
loops x n_attn x kv_heads x head_dim x itemsize`` bytes a token::

    tmserve --modelfile theanompi_tpu.models.hybrid_lm --modelclass HybridLM \
        --set "pattern='*-*-'" --set loops=4 --set post_norm=True \
        --set rope_theta=1e6 --set ffn_dim=704 --set kv_heads=8 \
        --requests 16 --max-batch 8 --num-blocks 129 --out SERVE.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from theanompi_tpu.launcher import _parse_kv
from theanompi_tpu.resilience.codes import EXIT_CKPT, EXIT_CONFIG, EXIT_CRASH


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmserve",
        description="Serve synthetic open-loop traffic from a trained "
        "checkpoint through the continuous-batching inference engine.",
        allow_abbrev=False,
    )
    p.add_argument("--modelfile",
                   default="theanompi_tpu.models.transformer_lm",
                   help="module of the model class (default the "
                   "transformer LM; theanompi_tpu.models.hybrid_lm for "
                   "HybridLM)")
    p.add_argument("--modelclass", default="TransformerLM",
                   help="a model with the serving interface (apply_prefill, "
                   "apply_decode, cache_spec): TransformerLM and its MoE "
                   "variant, or HybridLM — a per-layer pattern of Mamba-2 "
                   "(M), expert (E), attention (*) and gated-FFN (-) mixers, "
                   "serving-only, configured by --set pattern=... and its "
                   "widths; --set loops=T applies the pattern T times with "
                   "shared weights and an exit gate (exit_threshold), "
                   "rope_theta=<base> gives rotary positions, post_norm=True "
                   "a norm after each mixer")
    p.add_argument("--set", dest="model_set", action="append", default=[],
                   metavar="K=V", help="model config entry (repeatable; "
                   "must reproduce the training config for the checkpoint "
                   "fingerprint to match)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="load weights via the verified chain (read-only; "
                   "absent = serve the random init)")
    p.add_argument("--serve-verify", default="fast",
                   choices=["fast", "full", "none"],
                   help="checkpoint verification level (default fast)")
    p.add_argument("--serve-force", action="store_true",
                   help="override the model-fingerprint check on load "
                   "(mirrors tmlauncher --resume-force)")
    # -- engine ------------------------------------------------------------
    p.add_argument("--max-batch", type=int, default=8,
                   help="fixed decode batch width (slots)")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV-cache tokens per block")
    p.add_argument("--num-blocks", type=int, default=None,
                   help="KV block pool size (default: worst case; smaller "
                   "values oversubscribe and rely on preemption); a block "
                   "holds --block-size tokens over every entry the model's "
                   "cache_spec() asks for — loops x attention layers for a "
                   "looped HybridLM")
    p.add_argument("--quantize-int8", action="store_true",
                   help="int8 weight-only quantization of matmul weights "
                   "(ring_int8 per-chunk-scale format)")
    p.add_argument("--top-k", type=int, default=0,
                   help="restrict sampling to the top-k logits (0 = off)")
    p.add_argument("--decode-kernel", default="auto",
                   choices=("on", "off", "auto"),
                   help="fused paged-attention decode kernel (ISSUE 18): "
                   "on forces the pallas path (Mosaic interpreter off-TPU "
                   "— bit-identical, A/B and parity runs), off pins the "
                   "pure-JAX fallback, auto compiles it on TPU when the "
                   "head geometry tiles and falls back otherwise")
    p.add_argument("--prefix-cache", action="store_true",
                   help="radix prefix cache over the KV block pool (ISSUE "
                   "17): admissions reuse cached full-block prompt-prefix "
                   "K/V via partial prefill; token streams are unchanged "
                   "and the cache invalidates on live weight rollout.  "
                   "Refused (config error) for a model that keeps per-slot "
                   "recurrent state, e.g. HybridLM with M layers: shared "
                   "K/V blocks hold no state to resume from; and for any "
                   "HybridLM, which has no partial prefill")
    # -- synthetic traffic -------------------------------------------------
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--prompt-len", type=int, default=16,
                   help="synthetic prompt length (tokens; with --turns>1, "
                   "the per-turn extension length)")
    p.add_argument("--turns", type=int, default=1,
                   help="multi-turn sessions: each consecutive group of "
                   "this many requests is one conversation whose turn t "
                   "prompt extends turn t-1's by --prompt-len new tokens "
                   "(prefix-cache traffic; 1 = independent requests)")
    p.add_argument("--shared-prefix-len", type=int, default=0,
                   help="identical 'system prompt' tokens prepended to "
                   "EVERY request (cross-session prefix-cache traffic)")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="open-loop Poisson arrival rate in requests/sec "
                   "(0 = all requests arrive at t=0)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 samples under explicit PRNG keys")
    p.add_argument("--seed", type=int, default=0)
    # -- resilience (ISSUE 14) ---------------------------------------------
    p.add_argument("--ttft-deadline-ms", type=float, default=None,
                   help="per-request time-to-first-token deadline; a "
                   "request past it EXPIRES (typed terminal state) "
                   "instead of occupying a slot")
    p.add_argument("--total-deadline-ms", type=float, default=None,
                   help="per-request end-to-end deadline (expire beyond)")
    p.add_argument("--shed", action="store_true",
                   help="admission-time load shedding: refuse (terminal "
                   "state 'shed') a deadline-carrying request the queue "
                   "backlog provably cannot meet at the recent token rate")
    p.add_argument("--drain-s", type=float, default=5.0,
                   help="graceful-drain budget: on SIGTERM stop admitting, "
                   "finish or expire in-flight requests within this many "
                   "seconds, then exit clean (0)")
    p.add_argument("--requests-log", default=None,
                   help="append one JSONL line per terminal request here "
                   "(default <telemetry-dir>/REQUESTS.jsonl when telemetry "
                   "is on); a restarted --supervise attempt reads it back "
                   "and skips already-answered ids")
    # -- router replica mode (ISSUE 19) ------------------------------------
    p.add_argument("--queue-file", default=None,
                   help="serve a durable admission queue instead of "
                   "synthetic traffic: tail this JSONL file for request "
                   "entries appended by tmrouter, exit clean on its "
                   "{\"op\": \"drain\"} sentinel (REQUESTS.jsonl and "
                   "SERVE_SNAPSHOT.json default into its directory)")
    p.add_argument("--snapshot", default=None,
                   help="publish the scheduler's live load here atomically "
                   "(default: next to --queue-file, else "
                   "<telemetry-dir>/SERVE_SNAPSHOT.json; the router "
                   "balances on this)")
    p.add_argument("--snapshot-every", type=int, default=8,
                   help="scheduler steps between live-snapshot publishes")
    p.add_argument("--supervise", action="store_true",
                   help="run the replica as a supervised child through the "
                   "shared run_job seam: crash classification, bounded "
                   "backoff restarts, per-attempt resilience.json "
                   "(written to --telemetry-dir, never the read-only "
                   "--checkpoint-dir)")
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument("--backoff-base", type=float, default=1.0)
    # -- live weight rollout (ISSUE 14) ------------------------------------
    p.add_argument("--rollout-watch", action="store_true",
                   help="watch --checkpoint-dir and hot-swap newly "
                   "VERIFIED checkpoints between scheduler steps (active "
                   "requests recompute under the new weights — none are "
                   "dropped); corrupt/half-published candidates are "
                   "refused and re-polled, never quarantined")
    p.add_argument("--rollout-poll-s", type=float, default=0.5,
                   help="checkpoint-dir poll interval (listdir only)")
    p.add_argument("--rollout-probation-s", type=float, default=10.0,
                   help="after a swap, auto-roll back to the previous "
                   "weights if the health monitor's SLO/throughput "
                   "verdict turns critical within this window")
    # -- output ------------------------------------------------------------
    p.add_argument("--telemetry-dir", default=None,
                   help="serve.prefill/serve.decode spans + serve.* "
                   "gauges as per-rank JSONL (trace.json exported at exit; "
                   "also enables live HEALTH.json — see tmhealth)")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="serving SLO (ISSUE 13): flag a health verdict "
                   "when the live p99 time-to-first-token exceeds this "
                   "many ms (requires --telemetry-dir)")
    p.add_argument("--out", default=None,
                   help="write the report dict as JSON here (SERVE.json)")
    p.add_argument("--quiet", action="store_true")
    return p


def _error_line(phase: str, e: BaseException) -> None:
    print(f"tmserve: error: {phase}: {type(e).__name__}: {e}",
          file=sys.stderr, flush=True)
    if os.environ.get("THEANOMPI_DEBUG"):
        import traceback

        traceback.print_exc()


def synthetic_requests(n: int, vocab: int, prompt_len: int,
                       max_new_tokens: int, rate: float, seed: int,
                       temperature: float = 0.0,
                       ttft_deadline_ms: float | None = None,
                       total_deadline_ms: float | None = None,
                       turns: int = 1, shared_prefix: int = 0):
    """Seeded open-loop request stream: uniform-random prompts, Poisson
    arrivals at ``rate`` req/s (``rate=0`` = one burst at t=0).  The
    stream is a pure function of its arguments — a restarted supervised
    replica regenerates the identical stream and filters out the ids its
    REQUESTS.jsonl already answered.

    Prefix-cache traffic shapes (ISSUE 17, both default off):
    ``shared_prefix`` tokens are drawn once and prepended to EVERY prompt
    (a shared system prompt); ``turns > 1`` groups consecutive rids into
    sessions where turn t's prompt is turn t-1's plus ``prompt_len`` new
    tokens — turn t re-sends the conversation so far, the traffic the
    prefix cache exists for.  The shapes only change which tokens the
    prompts contain; every downstream contract (rid dedup, determinism,
    arrivals) is untouched."""
    import numpy as np

    from theanompi_tpu.serving.scheduler import Request

    rng = np.random.RandomState(seed)
    shared = ([int(x) for x in rng.randint(0, vocab, shared_prefix)]
              if shared_prefix > 0 else [])
    t = 0.0
    out = []
    convo: list[int] = []
    for rid in range(n):
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        if turns <= 1 or rid % turns == 0:
            convo = []
        convo = convo + [int(x) for x in rng.randint(0, vocab, prompt_len)]
        out.append(Request(
            rid=rid,
            prompt=shared + convo,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            arrival_s=t if rate > 0 else 0.0,
            ttft_deadline_ms=ttft_deadline_ms,
            total_deadline_ms=total_deadline_ms,
        ))
    return out


def serve(args) -> dict:
    """Build model + engine + scheduler, run the synthetic load; -> report.

    The resilience tier (ISSUE 14) hangs off this one loop: SIGTERM flips
    a drain event the open-loop driver polls (stop admitting, finish or
    expire in-flight within ``--drain-s``, exit clean); every terminal
    request appends to REQUESTS.jsonl so a supervised restart can skip
    already-answered ids; ``--rollout-watch`` polls the checkpoint dir
    between steps and hot-swaps verified checkpoints.
    """
    import time

    from theanompi_tpu.parallel.mesh import CompileStats, setup_compile_cache

    t_start = time.perf_counter()
    # before the first compile; router/fleet replicas share one cache the
    # same way, so the first replica compiles and every later one loads
    cache_dir = setup_compile_cache()
    compiles = CompileStats()
    try:
        return _serve(args, t_start, cache_dir, compiles)
    finally:
        compiles.close()


def _serve(args, t_start: float, cache_dir: str, compiles) -> dict:
    import importlib
    import signal
    import threading
    import time

    from theanompi_tpu.parallel.mesh import device_summary
    from theanompi_tpu.resilience.faults import FaultPlan
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.lifecycle import (
        REQUESTS_LOG,
        SNAPSHOT,
        RequestLog,
        SnapshotPublisher,
        terminal_rids,
    )
    from theanompi_tpu.serving.scheduler import (
        Scheduler,
        run_open_loop,
        run_queue_loop,
        serve_report,
    )
    from theanompi_tpu.utils.checkpoint import load_for_inference

    cls = getattr(importlib.import_module(args.modelfile), args.modelclass)
    model = cls(_parse_kv(args.model_set))
    import jax

    params, _state = model.init_params(jax.random.PRNGKey(args.seed))
    epoch = None
    if args.checkpoint_dir:
        restored = load_for_inference(
            args.checkpoint_dir, {"params": params},
            verify=args.serve_verify, model=model, force=args.serve_force)
        if restored is None:
            raise FileNotFoundError(
                f"no checkpoint in {args.checkpoint_dir} (tmserve does not "
                f"serve random inits when a directory was given)")
        epoch, _it, trees = restored
        params = trees["params"]
    if args.rollout_watch and not args.checkpoint_dir:
        raise ValueError("--rollout-watch needs --checkpoint-dir (there is "
                         "nothing to watch)")

    telemetry = None
    if args.telemetry_dir:
        from theanompi_tpu.telemetry import Telemetry

        # ISSUE 13: live health rides the telemetry opt-in, same default
        # as training; --slo-ttft-ms arms the serving SLO detector
        health: bool | dict = True
        if args.slo_ttft_ms is not None:
            health = {"slo_ttft_p99_ms": float(args.slo_ttft_ms)}
        # ISSUE 16: serve-step attribution (queue-wait/prefill/decode/
        # rollout-swap) rides the same opt-in; ATTRIB.json lands in the
        # telemetry dir at close
        telemetry = Telemetry(args.telemetry_dir, health=health,
                              flight_recorder=256, profile=True)

    fault_plan = FaultPlan.from_spec(None)  # THEANOMPI_FAULT_PLAN env
    try:
        attempt = int(os.environ.get("THEANOMPI_ATTEMPT", "1"))
    except ValueError:
        attempt = 1
    engine = InferenceEngine(
        model, params, block_size=args.block_size,
        num_blocks=args.num_blocks, max_batch=args.max_batch,
        quantize_int8=args.quantize_int8, top_k=args.top_k, seed=args.seed,
        decode_kernel=getattr(args, "decode_kernel", "auto"))
    sched = Scheduler(engine, telemetry=telemetry, shed=args.shed,
                      fault_plan=fault_plan,
                      prefix_cache=getattr(args, "prefix_cache", False))
    if telemetry is not None:
        from theanompi_tpu.telemetry.metrics import (
            SERVE_DECODE_KERNEL_INSTANTS,
        )

        # ISSUE 18: record the resolved decode path once per run — the
        # A/B trace needs to know WHICH impl produced its decode spans
        telemetry.instant(SERVE_DECODE_KERNEL_INSTANTS[0],
                          impl=engine.decode_impl,
                          requested=getattr(args, "decode_kernel", "auto"))
    queue_file = getattr(args, "queue_file", None)
    reqs = [] if queue_file else synthetic_requests(
        args.requests, model.data.vocab, args.prompt_len,
        args.max_new_tokens, args.arrival_rate, args.seed,
        args.temperature, ttft_deadline_ms=args.ttft_deadline_ms,
        total_deadline_ms=args.total_deadline_ms,
        turns=getattr(args, "turns", 1),
        shared_prefix=getattr(args, "shared_prefix_len", 0))

    # -- durable terminal-state log + restart dedup (ISSUE 14) -------------
    # queue mode (ISSUE 19): the log defaults NEXT TO the queue file so
    # the router finds it without extra plumbing
    log_path = args.requests_log or (
        os.path.join(args.telemetry_dir, REQUESTS_LOG)
        if args.telemetry_dir else
        os.path.join(os.path.dirname(os.path.abspath(queue_file)),
                     REQUESTS_LOG) if queue_file else None)
    req_log = None
    answered: set[int] = set()
    n_skipped = 0
    if log_path:
        answered = terminal_rids(log_path)
        if answered and not queue_file:
            before = len(reqs)
            reqs = [r for r in reqs if r.rid not in answered]
            n_skipped = before - len(reqs)
        req_log = RequestLog(log_path, attempt=attempt)
    if queue_file and answered:
        n_skipped = len(answered)

    # -- live load snapshot (ISSUE 19 satellite) ---------------------------
    snap_path = getattr(args, "snapshot", None) or (
        os.path.join(os.path.dirname(os.path.abspath(queue_file)), SNAPSHOT)
        if queue_file else
        os.path.join(args.telemetry_dir, SNAPSHOT)
        if args.telemetry_dir else None)
    snapshot = (SnapshotPublisher(
        snap_path, every_steps=getattr(args, "snapshot_every", 8))
        if snap_path else None)

    # -- graceful drain: SIGTERM -> drain within --drain-s, exit clean -----
    drain_ev = threading.Event()
    prev_term = None
    if threading.current_thread() is threading.main_thread():
        prev_term = signal.signal(signal.SIGTERM,
                                  lambda _sig, _frm: drain_ev.set())

    # -- verified live rollout watcher -------------------------------------
    rollout = None
    if args.rollout_watch:
        from theanompi_tpu.serving.rollout import RolloutManager

        rollout = RolloutManager(
            engine, args.checkpoint_dir, {"params": params}, model=model,
            verify=args.serve_verify, current_epoch=epoch,
            poll_s=args.rollout_poll_s,
            probation_s=args.rollout_probation_s,
            telemetry=telemetry, fault_plan=fault_plan)

    setup_s = time.perf_counter() - t_start
    try:
        if queue_file:
            results, wall_s = run_queue_loop(
                sched, queue_file, drain=drain_ev.is_set,
                drain_s=args.drain_s,
                on_terminal=req_log.record if req_log else None,
                between_steps=rollout.poll if rollout else None,
                snapshot=snapshot, answered=answered)
        else:
            results, wall_s = run_open_loop(
                sched, reqs, drain=drain_ev.is_set, drain_s=args.drain_s,
                on_terminal=req_log.record if req_log else None,
                between_steps=rollout.poll if rollout else None,
                snapshot=snapshot)
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        if req_log is not None:
            req_log.close()
    report = serve_report(results, wall_s, sched)
    # what ran where: the device as jax reports it, the kernel tiers the
    # engine resolved, and set-up (model build + every compile, which the
    # first prefill/decode of each shape pays inside wall_s) kept apart
    # from the serving numbers
    report["device"] = device_summary()
    report["paths"] = engine.resolved_paths()
    report["setup_s"] = round(setup_s, 3)
    report["compile"] = {"cache_dir": cache_dir, **compiles.as_dict()}
    report["checkpoint_epoch"] = (rollout.current_epoch if rollout
                                  else epoch)
    report["attempt"] = attempt
    if n_skipped:
        report["skipped_already_answered"] = n_skipped
    if log_path:
        report["requests_log"] = log_path
    if queue_file:
        report["queue_file"] = queue_file
    if rollout is not None:
        report["rollout"] = {"rollouts": rollout.n_rollouts,
                             "rollbacks": rollout.n_rollbacks,
                             "refused": rollout.n_refused,
                             "serving_epoch": rollout.current_epoch}
    if engine.quant_stats:
        report["quantization"] = engine.quant_stats
    if telemetry is not None:
        from theanompi_tpu.telemetry.metrics import (
            SERVE_DECODE_KERNEL_GAUGES,
            SERVE_GAUGES,
        )

        g_tps, g_active, g_free = SERVE_GAUGES
        telemetry.gauge(g_tps, report["value"])
        telemetry.gauge(g_active, 0)
        telemetry.gauge(g_free, sched.pool.free_blocks)
        step_p50 = (report.get("decode_step_ms") or {}).get("p50")
        if step_p50 is not None:
            telemetry.gauge(SERVE_DECODE_KERNEL_GAUGES[0], step_p50,
                            impl=engine.decode_impl)
        telemetry.close()
        telemetry.export_chrome_trace(
            os.path.join(args.telemetry_dir, "trace.json"))
    return report


def main(argv: list[str] | None = None) -> int:
    """Exit-code contract (shared with tmlauncher; see the README table):
    0 clean, 70 serving crash, 77 checkpoint chain exhausted, 78 config
    error — one ``tmserve:`` stderr line each."""
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    try:
        args = build_parser().parse_args(argv)
    except SystemExit as e:
        # argparse exits 2 on bad flags — keep its contract
        return int(e.code or 0)

    if args.supervise:
        # the supervision half lives across the wall in the resilience
        # layer (serving may never import resilience.supervisor); one lazy
        # import reaches it, mirroring the launcher's _supervise seam
        if os.environ.get("THEANOMPI_SUPERVISED"):
            _error_line("config", RuntimeError(
                "--supervise inside a supervised child (recursion guard)"))
            return EXIT_CONFIG
        from theanompi_tpu.resilience.replica import serve_supervised

        return serve_supervised(
            argv, max_restarts=args.max_restarts,
            backoff_base=args.backoff_base,
            telemetry_dir=args.telemetry_dir, seed=args.seed)

    from theanompi_tpu.utils.checkpoint import (
        CheckpointCorruptError,
        CheckpointFingerprintError,
    )

    try:
        report = serve(args)
    except CheckpointFingerprintError as e:
        _error_line("load", e)
        return EXIT_CONFIG
    except CheckpointCorruptError as e:
        _error_line("checkpoint", e)
        return EXIT_CKPT
    except (ImportError, AttributeError, TypeError, ValueError, KeyError,
            FileNotFoundError, NotImplementedError) as e:
        _error_line("config", e)
        return EXIT_CONFIG
    except Exception as e:
        _error_line("serving", e)
        return EXIT_CRASH
    if args.out:
        with open(args.out + ".tmp", "w") as f:
            json.dump(report, f, indent=1)
        os.replace(args.out + ".tmp", args.out)
    print(json.dumps(report))
    if not args.quiet and args.telemetry_dir:
        print(f"tmserve: telemetry in {args.telemetry_dir} (trace.json "
              f"for Perfetto)", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
