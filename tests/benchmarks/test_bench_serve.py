"""Rehearsals of the serving driver at a tiny size through the real
entries (``InferenceEngine``, ``Scheduler.submit`` / ``.step``), on the CPU."""

import numpy as np

from bench_tiny import failed_names, well_formed, rehearse, tiny_cell

from benchmarks import run as bench_run
from benchmarks import serve
from benchmarks.common import load_json


def test_sound_run_is_correct():
    line = rehearse("serve")
    well_formed(line, "serve_tokens_per_s")
    # the traffic file names the rate alone: the tail is a per-layer metric
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["extra"]["tokens_compared"] > 20


def test_a_traffic_file_without_the_key_reports_both_end_to_end_metrics():
    import jax

    workload, loaded = tiny_cell("serve")
    assert loaded["traffic"].pop("end_to_end") == ["serve_tokens_per_s"]
    loaded["bench"]["end_to_end"].append(
        {"name": "tpot_ms_p90", "unit": "ms", "better": "lower", "bound": 0.04,
         "source": "host_clock"})
    line = bench_run.execute(loaded, workload, seed=2**31 + 5, seconds=2.0,
                             trace=0, devices=jax.devices()[:1])
    assert line["metrics"]["tpot_ms_p90"]["value"] > 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_tail_is_read_per_layer_from_the_runs_series():
    from benchmarks.readers import percentile

    decl = load_json("metrics", "sched.tpot_ms_p90_backlog.json")
    assert decl["workloads"] == ["cgpt13.serve.backlog"]
    tpots = [30.0 + i for i in range(11)]
    run = {"series": {"tpot_ms": tpots}}
    assert percentile.read(run, **decl["args"]) == 39.0
    assert percentile.read({"series": {}}, **decl["args"]) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from theanompi_tpu.serving.engine import InferenceEngine

    real = InferenceEngine.decode

    def altered(self, tables, lengths, tokens, temps, rids):
        nxt, logits = real(self, tables, lengths, tokens, temps, rids)
        # every fourth step, every slot: any sampled request holds some
        self._n_altered = getattr(self, "_n_altered", 0) + 1
        if self._n_altered % 4 == 0:
            nxt = (np.array(nxt) + 1) % self.model.data.vocab
        return nxt, logits
    monkeypatch.setattr(InferenceEngine, "decode", altered)
    line = rehearse("serve")
    assert line["correct"] is False
    assert failed_names(line) == ["widest_logit_gap"]


def test_the_fp8_control_is_not_correct():
    """At each position of a prompt and its greedy tokens, the token the
    fp8 control puts first lies further below the reference's best than
    the limit allows."""
    import jax.numpy as jnp

    from benchmarks import reference

    _, loaded = tiny_cell("serve")
    cfg, limit = loaded["cfg"], loaded["cell"]["limits"]["widest_logit_gap"]
    rng = np.random.Generator(np.random.PCG64(3))
    sample = []
    for _ in range(3):
        prompt = rng.integers(0, cfg["vocab_size"], size=12).tolist()
        toks = list(prompt)
        for _ in range(20):  # greedy under the reference itself
            padded = np.zeros((cfg["n_positions"],), np.int32)
            padded[:len(toks)] = toks
            logits = reference.served_logits(cfg, 11, padded)
            toks.append(int(jnp.argmax(logits[len(toks) - 1])))
        sample.append((prompt, toks[len(prompt):]))
    assert serve.served_gaps(cfg, 11, sample)["widest_logit_gap"] == 0.0
    control = serve.served_gaps(cfg, 11, sample, control=True)
    assert control["widest_logit_gap"] > limit, control
