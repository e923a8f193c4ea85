"""HLO collective-count lint (ISSUE 2), now a tmlint shim (ISSUE 7).

The one-off compile-and-count here became the general compiled-artifact
auditor (``theanompi_tpu/analysis/hlo_audit.py``): same wide_resnet
step, same lock (>=30-leaf model + psum_bucket -> <=4 all-reduce ops),
plus donation and host-callback checks this file never had.  The audit
artifacts are ``lru_cache``'d, so this shim and ``test_hlo_audit.py``
share one XLA compile per strategy.
"""

from theanompi_tpu.analysis import hlo_audit
from theanompi_tpu.telemetry.metrics import hlo_collective_counts


def test_bucketed_step_compiles_to_few_allreduces():
    """Acceptance: >=30-leaf model + psum_bucket -> <=4 all-reduce HLO ops
    (grad bucket + fused metrics pmean + fused state pmean).

    The other half of this lock — the leaf-wise psum baseline compiling
    to one all-reduce per gradient leaf and counting higher — stopped
    holding on jax 0.9.0: XLA's combiner merges the 43 leaf-wise
    all-reduces into one op, as the old docstring said it one day might.
    The compiled count no longer tells the two strategies apart; whether
    bucketing still earns its keep is a question for a chip trace
    (ROADMAP S6/D3), not for this lint."""
    bucketed = hlo_audit.audit_train_step("psum_bucket")
    n_leaves = bucketed["n_param_leaves"]
    assert n_leaves >= 30, f"model too small to prove bucketing: {n_leaves}"
    assert bucketed["ok"], bucketed["violations"]
    n_bucketed = bucketed["collectives"].get("all-reduce", 0)
    assert n_bucketed <= 4, bucketed["collectives"]

    leafwise = hlo_audit.audit_train_step("psum")
    assert leafwise["ok"], leafwise["violations"]
    assert leafwise["collectives"]["all-reduce"] >= 1


def test_hlo_collective_counts_parser():
    """Parser unit: defs count, -start/-done pairs count once, operand
    references (no parens) and metadata mentions don't."""
    text = """
  %all-reduce.1 = f32[16]{0} all-reduce(f32[16]{0} %p), to_apply=%add
  %ars = (f32[4]{0}, f32[4]{0}) all-reduce-start(f32[4]{0} %q)
  %ard = f32[4]{0} all-reduce-done((f32[4]{0}, f32[4]{0}) %ars)
  %rs = f32[4]{0} reduce-scatter(f32[16]{0} %all-reduce.1), dimensions={0}
  %ag = f32[16]{0} all-gather(f32[4]{0} %rs), dimensions={0}
  %cp = f32[4]{0} collective-permute(f32[4]{0} %x)
  %y = f32[4]{0} add(f32[4]{0} %cp, f32[4]{0} %cp)
"""
    counts = hlo_collective_counts(text)
    assert counts == {"all-reduce": 2, "reduce-scatter": 1,
                      "all-gather": 1, "collective-permute": 1}
