"""Compiled-artifact auditor tests (ISSUE 7 tentpole, HLO half).

The acceptance criterion: donation + the PR 2 collective-count lock
asserted for at least ``psum_bucket`` and ``zero1``, plus the serve
decode step.  Artifacts are ``lru_cache``'d in the auditor, so the
strategy compiles here are shared with ``test_lint_collectives.py``.
Negative proofs run on throwaway jitted toys (ms-scale compiles): a
pure_callback IS detected, an undonated step IS detected — the auditor
must be falsifiable, not a rubber stamp.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.analysis import hlo_audit


# ---------------------------------------------------------------------------
# parsers (pure text)
# ---------------------------------------------------------------------------

HEADER = ("HloModule jit_step, is_scheduled=true, input_output_alias={ "
          "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias), "
          "{2,0}: (3, {}, may-alias) }, entry_computation_layout=...")


def test_donation_alias_parser():
    assert hlo_audit.donation_alias_count(HEADER) == 3
    assert hlo_audit.donation_alias_count("HloModule jit_f, "
                                          "entry_computation_layout=x") == 0


def test_host_callback_parser():
    text = ('%cc = (f32[8]) custom-call(s64[] %c), '
            'custom_call_target="xla_python_cpu_callback"\n'
            '%ok = f32[8] custom-call(f32[8] %x), '
            'custom_call_target="SomeBlasGemm"\n')
    assert hlo_audit.host_callbacks(text) == ["xla_python_cpu_callback"]


# a synthetic optimized entry: ar.1 -> fusion.1 -> ar.2 is a chained,
# interleaved pair; ar.3 hangs off the same input with no collective
# ancestor (trailing)
CHAINED_ENTRY = """\
HloModule jit_step, is_scheduled=true

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ar.1 = f32[8] all-reduce(f32[8] %p0), to_apply=%add
  %fusion.1 = f32[8] fusion(f32[8] %ar.1), kind=kLoop
  %ar.2 = f32[8] all-reduce(f32[8] %fusion.1), to_apply=%add
  %ar.3 = f32[8] all-reduce(f32[8] %p0), to_apply=%add
  %ag.1 = f32[8] all-gather(f32[8] %ar.2), dimensions={0}
  ROOT %out = f32[8] fusion(f32[8] %ag.1, f32[8] %ar.3), kind=kLoop
}
"""


def test_entry_dependency_graph_parser():
    graph, order = hlo_audit.entry_dependency_graph(CHAINED_ENTRY)
    assert order == ["p0", "ar.1", "fusion.1", "ar.2", "ar.3", "ag.1", "out"]
    assert graph["ar.2"][0] == "all-reduce"
    # %name extraction over-approximates (to_apply=%add rides along) —
    # safe for reachability, which only follows entry-defined names
    assert graph["ar.2"][1] == ["fusion.1", "add"]
    assert graph["out"][0] == "fusion"


def test_collective_chain_stats_discriminates():
    """ar.1->ar.2 is one same-kind chained pair, through a fusion; the
    all-gather's dependency on the all-reduces is CROSS-kind and must not
    count (zero1's scatter->update->gather exists in either schedule)."""
    stats = hlo_audit.collective_chain_stats(CHAINED_ENTRY)
    assert stats == {"n_collectives": 4, "chained_same_kind": 1,
                     "interleaved_pairs": 1}


def test_collective_chain_stats_on_trailing_schedule():
    trailing = CHAINED_ENTRY.replace("f32[8] %fusion.1), to_apply",
                                     "f32[8] %p0), to_apply")
    stats = hlo_audit.collective_chain_stats(trailing)
    assert stats["chained_same_kind"] == 0
    assert stats["interleaved_pairs"] == 0


# ---------------------------------------------------------------------------
# the locked artifacts
# ---------------------------------------------------------------------------


def test_psum_bucket_audit():
    r = hlo_audit.audit_train_step("psum_bucket")
    assert r["ok"], r["violations"]
    assert r["collectives"].get("all-reduce", 0) <= 4
    assert r["alias_count"] >= r["n_param_leaves"]  # donation applied
    assert r["host_callbacks"] == []


def test_zero1_audit():
    r = hlo_audit.audit_train_step("zero1")
    assert r["ok"], r["violations"]
    assert r["collectives"].get("reduce-scatter", 0) >= 1
    assert r["collectives"].get("all-gather", 0) >= 1
    assert r["collectives"].get("all-reduce", 0) <= 3
    assert r["alias_count"] >= r["n_param_leaves"]
    assert r["host_callbacks"] == []


def test_serve_decode_audit():
    r = hlo_audit.audit_serve_step()
    assert r["ok"], r["violations"]
    assert r["alias_count"] >= 2          # k and v pools donated
    assert r["collectives"] == {}         # single-device serve
    assert r["host_callbacks"] == []


def test_serve_prefill_audit():
    """ISSUE 17: the prefix-cache hit path (partial prefill) holds the
    same HLO contract as decode — donated pools, zero collectives."""
    r = hlo_audit.audit_serve_prefill()
    assert r["ok"], r["violations"]
    assert r["alias_count"] >= 2          # k and v pools donated
    assert r["collectives"] == {}
    assert r["host_callbacks"] == []


@pytest.mark.parametrize("strategy", hlo_audit.DEFAULT_OVERLAP_STRATEGIES)
def test_overlap_schedule_audit(strategy):
    """ISSUE 12 acceptance: the optimized HLO proves the overlapped
    schedule — a same-kind collective chain of >= n_buckets-1 edges
    running through backward fusions, identical collective counts, and
    the fused baseline still trailing (0 chain edges)."""
    r = hlo_audit.audit_overlap_schedule(strategy)
    assert r["ok"], r["violations"]
    assert r["n_buckets"] >= 2
    assert r["chain"]["chained_same_kind"] >= r["n_buckets"] - 1
    assert r["chain"]["interleaved_pairs"] >= r["n_buckets"] - 1
    # negative proof: fused still audits as trailing
    assert r["fused_chain"]["chained_same_kind"] == 0


def test_serve_decode_kernel_audit():
    """ISSUE 18: the decode fast path dispatches as TPU custom calls
    (with the kernel-off lowering as the negative proof), keeps the
    donation / zero-collective contract, and the kernel is bit-identical
    to the fallback on CPU."""
    r = hlo_audit.audit_serve_decode_kernel()
    assert r["ok"], r["violations"]
    assert r["custom_calls_on"] >= r["n_layers"]   # paged attn per layer
    assert r["custom_calls_off"] == 0              # negative proof
    # ISSUE 26: every layer's call reads the whole 5-D pools, no slice
    assert len(r["paged_calls"]) == r["n_layers"] >= 2
    assert all(c == {"shapes": [r["pool_shape"]] * 2, "sliced": False}
               for c in r["paged_calls"])
    assert r["custom_calls_int8"] >= 1             # fused int8 matmul
    assert r["alias_count"] >= 2                   # pools stay donated
    assert r["collectives"] == {}
    assert r["decode_parity_bitwise"]
    assert r["int8_rel_err"] <= hlo_audit.INT8_REL_TOL


def test_expert_products_audit():
    """ISSUE 28: with the kernel on, every grouped product of a small
    ``HybridLM``'s decode program is a ``grouped_matmul`` custom call fed
    by the ``[E, K, N]`` parameter leaf itself and no ``ragged_dot`` is
    left; with it off, the ``ragged_dot``s are all there."""
    r = hlo_audit.audit_expert_products()
    assert r["ok"], r["violations"]
    n = r["expected"]
    assert n == 4 and len(r["products_on"]) == len(r["products_off"]) == n
    assert all(c["via"] == "kernel" and c["weight_from"] == "parameter"
               and c["weight"] in r["stacks"] for c in r["products_on"])
    assert r["stacks"] == [[4, 128, 256], [4, 256, 128]]
    assert all(c["via"] == "ragged_dot" for c in r["products_off"])


def test_state_update_audit():
    """ISSUE 30: with the kernel resolved, both state layers of a small
    ``HybridLM``'s decode program are ``mamba_state_update`` calls on the
    whole float32 pool — the donated argument, then the previous call's
    pool — aliased to their output; the plain lines leave no such call."""
    r = hlo_audit.audit_state_update()
    assert r["ok"], r["violations"]
    assert r["expected"] == 2 and r["pool_shape"] == [2, 2, 4, 32, 128]
    assert r["resolved_kernel"] == "kernel" and r["resolved_plain"] == "plain"
    assert r["calls_kernel"] == [
        {"pool": r["pool_shape"], "pool_from": "parameter", "aliased": True},
        {"pool": r["pool_shape"], "pool_from": "state_update",
         "aliased": True}]
    assert r["calls_plain"] == []


def test_run_default_audits_is_green():
    reports = hlo_audit.run_default_audits()
    assert [(r["kind"], r.get("strategy")) for r in reports] == [
        ("train", "psum_bucket"), ("train", "zero1"),
        ("train-overlap", "psum_bucket"), ("train-overlap", "zero1"),
        ("serve", None), ("serve-prefill", None), ("serve-kernel", None),
        ("serve-experts", None), ("serve-state", None)]
    assert all(r["ok"] for r in reports)


# ---------------------------------------------------------------------------
# negative proofs: the auditor detects what it claims to detect
# ---------------------------------------------------------------------------


def test_auditor_detects_a_host_callback():
    def cb(v):
        return v

    def step(x):
        return jax.pure_callback(
            cb, jax.ShapeDtypeStruct((4,), jnp.float32), x) * 2.0

    text = jax.jit(step).lower(jnp.ones((4,), jnp.float32)) \
        .compile().as_text()
    facts = hlo_audit.audit_text(text)
    assert facts["host_callbacks"], "pure_callback not detected in HLO"


def test_auditor_detects_missing_donation():
    def step(x, y):
        return x + y, x * y

    args = (jnp.ones((8,)), jnp.ones((8,)))
    undonated = jax.jit(step).lower(*args).compile().as_text()
    donated = jax.jit(step, donate_argnums=(0,)).lower(*args) \
        .compile().as_text()
    assert hlo_audit.donation_alias_count(undonated) == 0
    assert hlo_audit.donation_alias_count(donated) >= 1


@pytest.mark.parametrize("cut,sliced", [
    (lambda pool: pool, False),
    (lambda pool: pool[1:], True),                       # static slice
    (lambda pool: jax.lax.dynamic_slice_in_dim(pool, 1, 1), True),
])
def test_auditor_detects_a_sliced_pool_before_the_kernel(cut, sliced,
                                                         monkeypatch):
    """ISSUE 26's guard has teeth: a slice of the K/V pool in front of
    the paged-decode call (what ``self.k[layer]`` lowered to, a copy of a
    layer per call on the chip) reads as ``sliced`` with a shape that is
    not the pool's, and the kernel audit lists it as a violation."""
    from theanompi_tpu.ops.pallas_paged_attention import paged_attend_decode

    pool = jnp.zeros((3, 4, 8, 8, 128), jnp.float32)
    tables = jnp.zeros((2, 2), jnp.int32)
    q = jnp.zeros((2, 8, 128), jnp.float32)

    def step(k, v, t, q, p):
        return paged_attend_decode(cut(k), cut(v), 0, t, 8, q, p,
                                   interpret=False)

    text = jax.jit(step).trace(pool, pool, tables, q,
                               jnp.zeros((2,), jnp.int32)) \
        .lower(lowering_platforms=("tpu",)).as_text()
    (call,) = hlo_audit.paged_call_operands(text)
    assert call["sliced"] is sliced
    assert (call["shapes"] == [list(pool.shape)] * 2) is (not sliced)

    facts = dict(hlo_audit._serve_decode_kernel_artifact(),
                 pool_shape=list(pool.shape))
    facts["paged_calls"] = [call] * facts["n_layers"]
    monkeypatch.setattr(hlo_audit, "_serve_decode_kernel_artifact",
                        lambda: facts)
    r = hlo_audit.audit_serve_decode_kernel()
    assert r["ok"] is (not sliced)
    assert any("not the whole" in v for v in r["violations"]) is sliced


@pytest.mark.parametrize("cut,made_by", [
    (lambda w: w, "parameter"),
    (lambda w: w.astype(jnp.bfloat16), "convert"),       # a leaf held in fp32
    (lambda w: w[1:], "slice"),
    (lambda w: jax.lax.dynamic_slice_in_dim(w, 1, 3), "dynamic_slice"),
])
def test_auditor_detects_a_copied_expert_stack_before_the_kernel(
        cut, made_by, monkeypatch):
    """ISSUE 28's guard has teeth: a re-cast or a slice of the stacked
    expert weights in front of the grouped product reads as made by that
    op, not as the parameter, and the audit lists it as a violation."""
    from theanompi_tpu.ops.pallas_grouped_matmul import grouped_matmul

    dtype = jnp.float32 if made_by == "convert" else jnp.bfloat16
    w = jnp.zeros((4, 128, 256), dtype)
    rows = jnp.zeros((16, 128), jnp.bfloat16)

    def step(rows, w, sizes):
        w = cut(w)
        return grouped_matmul(rows, w, sizes[:w.shape[0]], tm=16,
                              interpret=False)

    text = jax.jit(step).trace(rows, w, jnp.zeros((4,), jnp.int32)) \
        .lower(lowering_platforms=("tpu",)).as_text()
    (call,) = hlo_audit.grouped_product_calls(text)
    assert call["via"] == "kernel" and call["weight_from"] == made_by

    facts = dict(hlo_audit._expert_products_artifact())
    facts["products_on"] = [dict(c, weight_from=made_by)
                            for c in facts["products_on"]]
    monkeypatch.setattr(hlo_audit, "_expert_products_artifact", lambda: facts)
    r = hlo_audit.audit_expert_products()
    assert r["ok"] is (made_by == "parameter")
    assert any("not a whole parameter leaf" in v
               for v in r["violations"]) is (made_by != "parameter")


@pytest.mark.parametrize("cut,made_by,aliased", [
    (lambda pool: pool, "parameter", True),
    (lambda pool: pool[1:], "slice", True),
    (lambda pool: pool * 1.0, "multiply", True),
    (lambda pool: pool, "parameter", False),
])
def test_auditor_detects_a_copied_or_unaliased_state_pool(
        cut, made_by, aliased, monkeypatch):
    """ISSUE 30's guard has teeth: a slice or a copy of the pool in front
    of the state update reads as made by that op, a call without the alias
    reads as not aliased, and the audit lists either as a violation."""
    from theanompi_tpu.ops import pallas_state_update as psu

    z = functools.partial(jnp.zeros, dtype=jnp.float32)
    pool, a, dtx, bc = z((3, 2, 4, 8, 128)), z((2, 4)), z((2, 4, 8)), \
        z((2, 2, 128))

    def step(pool, a, dtx, bc):
        return psu.state_update(cut(pool), 0, a, dtx, bc, bc, interpret=False)

    text = jax.jit(step).trace(pool, a, dtx, bc) \
        .lower(lowering_platforms=("tpu",)).as_text()
    if not aliased:
        text = text.replace("output_tuple_indices = [0], operand_index = 4",
                            "output_tuple_indices = [1], operand_index = 4")
    (call,) = hlo_audit.state_update_calls(text)
    assert call["pool_from"] == made_by and call["aliased"] is aliased

    facts = dict(hlo_audit._state_update_artifact())
    facts["calls_kernel"] = [dict(facts["calls_kernel"][0], pool_from=made_by,
                                  aliased=aliased)] + facts["calls_kernel"][1:]
    monkeypatch.setattr(hlo_audit, "_state_update_artifact", lambda: facts)
    r = hlo_audit.audit_state_update()
    assert r["ok"] is (made_by == "parameter" and aliased)
    assert any("copied on the chip" in v
               for v in r["violations"]) is (made_by != "parameter")
    assert any("second pool" in v for v in r["violations"]) is (not aliased)


def test_a_state_layer_left_to_the_plain_lines_is_a_violation(monkeypatch):
    facts = dict(hlo_audit._state_update_artifact())
    facts["calls_kernel"] = facts["calls_kernel"][:1]
    monkeypatch.setattr(hlo_audit, "_state_update_artifact", lambda: facts)
    r = hlo_audit.audit_state_update()
    assert not r["ok"] and any("1 mamba_state_update call(s) for 2" in v
                               for v in r["violations"])


def test_a_ragged_dot_left_in_the_kernel_on_program_is_a_violation(
        monkeypatch):
    facts = dict(hlo_audit._expert_products_artifact())
    facts["products_on"] = (facts["products_on"][:-1]
                            + facts["products_off"][-1:])
    monkeypatch.setattr(hlo_audit, "_expert_products_artifact", lambda: facts)
    r = hlo_audit.audit_expert_products()
    assert not r["ok"] and any("1 ragged_dot(s)" in v for v in r["violations"])


def test_budget_violation_surfaces_in_report(monkeypatch):
    """Tighten the psum_bucket lock to an impossible bound: the audit
    must report the violation (and run_default_audits must raise)."""
    tight = dict(hlo_audit.TRAIN_COLLECTIVE_BUDGETS)
    tight["psum_bucket"] = {"all-reduce": (0, 0)}
    monkeypatch.setattr(hlo_audit, "TRAIN_COLLECTIVE_BUDGETS", tight)
    r = hlo_audit.audit_train_step("psum_bucket")
    assert not r["ok"] and any("locked maximum" in v
                               for v in r["violations"])
    with pytest.raises(hlo_audit.HLOAuditError, match="locked maximum") as ei:
        hlo_audit.run_default_audits()
    # the CLI publishes the artifact on failure: the completed reports
    # (showing WHAT failed) must ride the exception (review fix).  Only
    # the tightened psum_bucket TRAIN lock fails — the overlap audits
    # have their own invariants and stay green
    assert [rep["ok"] for rep in ei.value.reports] == [
        False, True, True, True, True, True, True, True, True]


def test_train_cfg_matches_the_locked_fixture():
    """The audit model must keep >=30 leaves or the bucket lock proves
    nothing (mirrors the PR 2 acceptance bar)."""
    r = hlo_audit.audit_train_step("psum_bucket")
    assert r["n_param_leaves"] >= 30
    assert np.isfinite(r["alias_count"])
