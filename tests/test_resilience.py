"""Chaos coverage for the resilience layer (docs/robustness.md).

Each documented degradation path is *proved* here: install a seeded
:class:`repro.resilience.FaultPlan` at the site, assert the fallback fires
(counter on a live obs capture), and — for the compute paths — that the
degraded result still matches the jnp oracle bit-for-semantics.  The kill
switch (``fallback.disabled()``) is asserted to re-raise, so error-path
tests elsewhere keep their semantics.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import csr_from_coo, csr_from_dense, loops_from_csr
from repro.core.spmm import loops_spmm, plan_and_convert
from repro.kernels import engine
from repro.obs import Obs, set_active
from repro.resilience import fallback, inject, validate
from repro.resilience.fallback import DeadlineExceeded, retry_with_backoff
from repro.resilience.inject import FaultClause, FaultPlan, InjectedFault
from repro.tune import PlanCache, SearchBudget, autotune
from repro.tune import cache as cache_mod
from repro.tune.search import search

HAVE_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None

ROOT = pathlib.Path(__file__).resolve().parent.parent


def random_sparse(rng, m, k, density=0.3, dtype=np.float32):
    a = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
    return a.astype(dtype)


@pytest.fixture(autouse=True)
def _clean_resilience():
    """No fault plan / default policy / no capture leaks across tests."""
    yield
    inject.set_plan(None)
    fallback.set_policy(fallback.FallbackPolicy())
    set_active(None)


def _counter_total(obs, name, **labels):
    total = 0.0
    for kind, inst in obs.metrics.instruments():
        if kind == "counter" and inst.name == name and all(
                inst.labels.get(k) == v for k, v in labels.items()):
            total += inst.value
    return total


# ---------------------------------------------------------------------------
# FaultPlan: parsing, counting, determinism
# ---------------------------------------------------------------------------

def test_fault_plan_parse_full_syntax():
    p = FaultPlan.parse("seed=7; engine.*.interpret:raise:0 ;"
                        "cache.read:corrupt-bytes:1:0")
    assert p.seed == 7
    assert p.clauses == (
        FaultClause("engine.*.interpret", "raise", 0, 1),
        FaultClause("cache.read", "corrupt-bytes", 1, 0))


def test_fault_plan_rejects_bad_kind_and_bad_clause():
    with pytest.raises(ValueError):
        FaultPlan.parse("site:explode")
    with pytest.raises(ValueError):
        FaultPlan.parse("just-a-site")


def test_fault_clause_nth_and_count_window():
    c = FaultClause("s", "raise", nth=1, count=2)
    assert [c.fires(n) for n in range(5)] == [False, True, True, False,
                                              False]
    every = FaultClause("s", "raise", nth=2, count=0)
    assert [every.fires(n) for n in range(5)] == [False, False, True, True,
                                                  True]


def test_fault_point_counts_per_site_and_resets():
    plan = FaultPlan.parse("s:raise:1")
    inject.set_plan(plan)
    assert inject.fault_point("s", "ok") == "ok"      # call 0: below nth
    with pytest.raises(InjectedFault):
        inject.fault_point("s")                        # call 1: fires
    assert inject.fault_point("s", "ok") == "ok"      # call 2: past window
    plan.reset()
    assert inject.fault_point("s", "ok") == "ok"      # counting restarts
    with pytest.raises(InjectedFault):
        inject.fault_point("s")


def test_corrupt_bytes_is_deterministic_and_unparseable():
    payload = json.dumps({"k": list(range(64))}).encode()
    inject.set_plan(FaultPlan.parse("seed=3;blob:corrupt-bytes:0:0"))
    a = inject.fault_point("blob", payload)
    inject.get_plan().reset()
    b = inject.fault_point("blob", payload)
    assert a == b and a != payload
    with pytest.raises(ValueError):
        json.loads(a.decode("utf-8", errors="replace"))


def test_nan_values_is_deterministic_on_numpy():
    x = np.ones((8, 8), np.float32)
    inject.set_plan(FaultPlan.parse("seed=5;w:nan-values:0:0"))
    a = inject.fault_point("w", x)
    inject.get_plan().reset()
    b = inject.fault_point("w", x)
    assert np.isnan(a).any() and not np.isnan(x).any()   # input untouched
    assert np.array_equal(np.isnan(a), np.isnan(b))


def test_install_from_env_and_disabled_state():
    assert inject.install_from_env({}) is None
    plan = inject.install_from_env({inject.ENV_VAR: "s:raise"})
    assert plan is not None and inject.get_plan() is plan
    inject.set_plan(None)
    assert inject.fault_point("s", 1) == 1    # no plan: pure pass-through


# ---------------------------------------------------------------------------
# Engine fallback chains: injected kernel faults degrade to the jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,density", [((32, 24), 0.3)])
def test_csr_part_falls_back_to_oracle(rng, shape, density):
    csr = csr_from_dense(random_sparse(rng, *shape, density))
    fmt = loops_from_csr(csr, csr.nrows, 4)            # pure CSR part
    b = jnp.asarray(rng.standard_normal((shape[1], 8)).astype(np.float32))
    ref = loops_spmm(fmt, b, backend="jnp")
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("engine.csr.spmm.interpret:raise:0:0"))
    got = loops_spmm(fmt, b, backend="interpret")
    assert jnp.allclose(got, ref, atol=1e-5)
    assert _counter_total(obs, "engine.fallback", part="csr",
                          op="spmm") >= 1
    assert _counter_total(obs, "inject.fired") >= 1


def test_bcsr_part_falls_back_to_oracle(rng):
    csr = csr_from_dense(random_sparse(rng, 32, 24))
    fmt = loops_from_csr(csr, 0, 4)                    # pure BCSR part
    b = jnp.asarray(rng.standard_normal((24, 8)).astype(np.float32))
    ref = loops_spmm(fmt, b, backend="jnp")
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("engine.bcsr.spmm.interpret:raise:0:0"))
    got = loops_spmm(fmt, b, backend="interpret")
    assert jnp.allclose(got, ref, atol=1e-5)
    assert _counter_total(obs, "engine.fallback", part="bcsr",
                          op="spmm") >= 1


def test_fused_exhaustion_degrades_to_parts_path(rng):
    csr = csr_from_dense(random_sparse(rng, 32, 24))
    fmt = loops_from_csr(csr, 16, 4)                   # hybrid, aligned
    assert fmt.r_boundary % 4 == 0 and 0 < fmt.r_boundary < fmt.nrows
    b = jnp.asarray(rng.standard_normal((24, 8)).astype(np.float32))
    ref = loops_spmm(fmt, b, backend="jnp")
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("engine.fused.spmm.*:raise:0:0"))
    got = loops_spmm(fmt, b, backend="interpret")
    assert jnp.allclose(got, ref, atol=1e-5)
    assert _counter_total(obs, "engine.fallback", part="fused",
                          op="spmm") >= 1
    # the parts path itself stayed healthy: no csr/bcsr fallbacks
    assert _counter_total(obs, "engine.fallback", part="csr") == 0


def test_sdd_falls_back_to_oracle(rng):
    csr = csr_from_dense(random_sparse(rng, 16, 12))
    fmt = loops_from_csr(csr, 8, 4)
    dy = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((12, 4)).astype(np.float32))
    ref = engine.loops_sdd(fmt, dy, b, backend="jnp")
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("engine.loops.sdd.interpret:raise:0:0"))
    got = engine.loops_sdd(fmt, dy, b, backend="interpret")
    for g, r in zip(got, ref):
        assert jnp.allclose(g, r, atol=1e-5)
    assert _counter_total(obs, "engine.fallback", part="loops",
                          op="sdd") >= 1


def test_kill_switch_propagates_the_failure(rng):
    csr = csr_from_dense(random_sparse(rng, 16, 12))
    fmt = loops_from_csr(csr, csr.nrows, 4)
    b = jnp.asarray(rng.standard_normal((12, 4)).astype(np.float32))
    inject.set_plan(FaultPlan.parse("engine.csr.spmm.interpret:raise:0:0"))
    with fallback.disabled():
        with pytest.raises(Exception):
            loops_spmm(fmt, b, backend="interpret")
    # same plan, chains re-enabled: degrades instead
    inject.get_plan().reset()
    ref = loops_spmm(fmt, b, backend="jnp")
    assert jnp.allclose(loops_spmm(fmt, b, backend="interpret"), ref,
                        atol=1e-5)


def test_no_fallback_env_kill_switch():
    assert fallback.FallbackPolicy().chain_for("csr", "spmm", "pallas") == \
        ("pallas", "interpret", "jnp")
    assert fallback.FallbackPolicy(enabled=False).chain_for(
        "csr", "spmm", "pallas") == ("pallas",)
    # a caller already on a degraded link never climbs back up
    assert fallback.FallbackPolicy().chain_for("csr", "spmm", "jnp") == \
        ("jnp",)
    assert fallback.FallbackPolicy().chain_for("fused", "spmm", "pallas") \
        == ("pallas", "interpret")


@pytest.mark.parametrize("fmt_kind", ["csr", "fused"])
def test_tpu_pallas_failure_raises_and_never_degrades(rng, monkeypatch,
                                                     fmt_kind):
    """On a TPU a failing pallas kernel raises: neither the per-part chain
    nor the fused catch in ``core.spmm`` may swap in the interpreter or the
    oracle.  The platform probe is steered to ``tpu`` inside the test; the
    fault fires at the pallas link's own fault point, before any lowering."""
    monkeypatch.setattr(fallback, "on_tpu", lambda: True)
    assert fallback.FallbackPolicy().chain_for("csr", "spmm", "pallas") == \
        ("pallas",)
    assert not fallback.degrades("pallas")
    csr = csr_from_dense(random_sparse(rng, 32, 24))
    r_b = csr.nrows if fmt_kind == "csr" else 16       # pure CSR / hybrid
    fmt = loops_from_csr(csr, r_b, 4)
    b = jnp.asarray(rng.standard_normal((24, 8)).astype(np.float32))
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse(
        f"engine.{fmt_kind}.spmm.pallas:raise:0:0"))
    with pytest.raises(InjectedFault):
        loops_spmm(fmt, b, backend="pallas")
    assert _counter_total(obs, "engine.fallback") == 0
    # the CPU keeps its chains: the same fault degrades off the TPU
    monkeypatch.setattr(fallback, "on_tpu", lambda: False)
    assert fallback.degrades("pallas")
    assert fallback.FallbackPolicy().chain_for("csr", "spmm", "pallas") == \
        ("pallas", "interpret", "jnp")


# ---------------------------------------------------------------------------
# Plan-cache resilience: quarantine, read-retry, merge-on-save
# ---------------------------------------------------------------------------

def _rec(gflops=1.0):
    from repro.tune.api import make_record
    return make_record([0.0] * 4, dtype=np.float32, n_cols=8, backend="jnp",
                       r_frac=0.5, t_vpu=2, t_mxu=6, br=4, gflops=gflops)


def test_cache_corrupt_file_is_quarantined(tmp_path, monkeypatch):
    monkeypatch.setattr(cache_mod, "_retry_sleep", lambda s: None)
    f = tmp_path / "plans.json"
    f.write_text("{not json")
    c = PlanCache(str(tmp_path))
    assert c.get("k") is None
    assert c.stats.quarantined == 1
    assert (tmp_path / "plans.json.quarantined").exists()
    assert not f.exists()
    c.put("k", _rec())                         # cache heals
    assert PlanCache(str(tmp_path)).peek("k") is not None


def test_cache_reader_racing_writer_retries_not_quarantines(tmp_path,
                                                            monkeypatch):
    """Regression: a half-written blob must be re-read, not quarantined."""
    f = tmp_path / "plans.json"
    good = json.dumps({"version": cache_mod.CACHE_VERSION,
                       "entries": {"k": _rec()}})
    f.write_text(good[: len(good) // 2])       # torn write in flight

    def finish_write(_delay):                  # the writer completes
        f.write_text(good)

    monkeypatch.setattr(cache_mod, "_retry_sleep", finish_write)
    c = PlanCache(str(tmp_path))
    assert c.peek("k") is not None
    assert c.stats.quarantined == 0
    assert not (tmp_path / "plans.json.quarantined").exists()


def test_cache_injected_corruption_quarantines_and_counts(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(cache_mod, "_retry_sleep", lambda s: None)
    c = PlanCache(str(tmp_path))
    c.put("k", _rec())
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("cache.read:corrupt-bytes:0:0"))
    c2 = PlanCache(str(tmp_path))              # fresh instance: re-reads
    assert c2.get("k") is None
    assert c2.stats.quarantined == 1
    assert _counter_total(obs, "tune.cache.quarantined") >= 1
    assert _counter_total(obs, "inject.fired") >= 1


def test_cache_concurrent_writers_both_survive(tmp_path):
    c1 = PlanCache(str(tmp_path))
    c2 = PlanCache(str(tmp_path))
    c2._load()                                 # c2 snapshots BEFORE c1 writes
    c1.put("a", _rec(1.0))
    c2.put("b", _rec(2.0))                     # merge-on-save folds "a" in
    fresh = PlanCache(str(tmp_path))
    assert fresh.peek("a") is not None and fresh.peek("b") is not None


def test_cache_clear_does_not_resurrect(tmp_path):
    c1 = PlanCache(str(tmp_path))
    c1.put("a", _rec())
    c2 = PlanCache(str(tmp_path))
    c2.clear()
    assert PlanCache(str(tmp_path)).peek("a") is None


# ---------------------------------------------------------------------------
# Tuner: trial isolation + all-fail degraded plan
# ---------------------------------------------------------------------------

def _cheap_measure(csr, p, b):
    fmt = loops_from_csr(csr, p.r_boundary, p.br, panel_g=p.panel_g)
    return fmt, 1.0 + p.r_boundary / max(csr.nrows, 1)


def test_search_skips_failed_trial_and_counts_it(rng):
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("tune.trial:raise:0"))   # first only
    res = search(csr, n_cols=8, budget=SearchBudget(top_k=3),
                 measure=_cheap_measure)
    assert res.gflops > 0 and res.measured >= 1
    assert _counter_total(obs, "tune.search.trial_failed") == 1
    assert _counter_total(obs, "tune.search.degraded") == 0


def test_search_trial_failure_raises_on_tpu_pallas(rng, monkeypatch):
    """A pallas trial that fails on a TPU is a refused kernel: the search
    raises instead of skipping it.  The platform probe is steered to
    ``tpu`` inside the test."""
    monkeypatch.setattr(fallback, "on_tpu", lambda: True)
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    obs = Obs(source="t")
    set_active(obs)
    inject.set_plan(FaultPlan.parse("tune.trial:raise:0"))
    with pytest.raises(InjectedFault):
        search(csr, n_cols=8, budget=SearchBudget(top_k=3),
               backend="pallas", measure=_cheap_measure)
    assert _counter_total(obs, "tune.search.trial_failed") == 0


def test_search_all_trials_failed_degrades_to_model_plan(rng):
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    obs = Obs(source="t")
    set_active(obs)

    def boom(c, p, bb):
        raise RuntimeError("measurement backend down")

    res = search(csr, n_cols=8, budget=SearchBudget(top_k=3), measure=boom)
    assert res.measured == 0 and res.gflops == 0.0
    assert res.plan is not None and res.fmt is not None
    b = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    assert loops_spmm(res.fmt, b).shape == (32, 8)
    assert _counter_total(obs, "tune.search.degraded") == 1
    assert _counter_total(obs, "tune.search.trial_failed") == 3


def test_search_trial_timeout_counts_as_failed(rng):
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    obs = Obs(source="t")
    set_active(obs)
    res = search(csr, n_cols=8,
                 budget=SearchBudget(top_k=2, trial_timeout_s=0.0),
                 measure=_cheap_measure)       # any elapsed > 0.0 overruns
    assert res.gflops == 0.0                   # every trial timed out
    assert _counter_total(obs, "tune.search.trial_failed",
                          reason="timeout") == 2


def test_autotune_on_miss_model_skips_measurement(tmp_path, rng):
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    cache = PlanCache(str(tmp_path))

    def forbidden(c, p, bb):                   # pragma: no cover
        raise AssertionError("on_miss='model' must never measure")

    fmt, plan = autotune(csr, n_cols=8, cache=cache, on_miss="model")
    assert cache.stats.misses == 1
    rec = next(iter(cache._load().values()))
    assert rec["gflops"] == 0.0 and rec["trials"] == 0
    fmt2, plan2 = autotune(csr, n_cols=8, cache=cache, on_miss="model")
    assert cache.stats.hits == 1 and plan2 == plan
    with pytest.raises(ValueError):
        autotune(csr, n_cols=8, cache=cache, on_miss="yolo")


# ---------------------------------------------------------------------------
# retry_with_backoff / deadlines
# ---------------------------------------------------------------------------

def test_retry_with_backoff_recovers_and_reports():
    calls, retries = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    out = retry_with_backoff(flaky, retries=3, backoff_s=0.001,
                             on_retry=lambda n, e: retries.append(n))
    assert out == "ok" and len(calls) == 3 and retries == [1, 2]


def test_retry_with_backoff_exhaustion_reraises():
    def always():
        raise ValueError("permanent")

    with pytest.raises(ValueError, match="permanent"):
        retry_with_backoff(always, retries=1, backoff_s=0.001)


def test_retry_deadline_raises_instead_of_sleeping_past():
    def always():
        raise OSError("down")

    with pytest.raises(DeadlineExceeded):
        retry_with_backoff(always, retries=50, backoff_s=10.0,
                           deadline_s=0.01)


# ---------------------------------------------------------------------------
# Validated ingestion
# ---------------------------------------------------------------------------

def _toy_csr(rng):
    return csr_from_dense(random_sparse(rng, 16, 12, 0.4))


def test_validate_classifies_each_defect(rng):
    import dataclasses
    csr = _toy_csr(rng)

    bad_ptr = csr.row_ptr.copy()
    bad_ptr[2] = bad_ptr[1] - 1 if bad_ptr[1] > 0 else bad_ptr[3] + 99
    kinds = validate.csr_defects(bad_ptr, csr.col_idx, csr.vals, csr.shape)
    assert "nonmonotone-indptr" in kinds

    oob = csr.col_idx.copy()
    oob[0] = csr.shape[1] + 5
    with pytest.raises(validate.SparseInputError) as ei:
        validate.validate_csr(dataclasses.replace(csr, col_idx=oob))
    assert ei.value.kind == "out-of-range-index"

    neg = csr.col_idx.copy()
    neg[0] = -1
    with pytest.raises(validate.SparseInputError) as ei:
        validate.validate_csr(dataclasses.replace(csr, col_idx=neg))
    assert ei.value.kind == "negative-index"

    nanv = csr.vals.copy()
    nanv[0] = np.nan
    with pytest.raises(validate.SparseInputError) as ei:
        validate.validate_csr(dataclasses.replace(csr, vals=nanv))
    assert ei.value.kind == "nonfinite-value"


def test_validate_repair_drop_yields_clean_csr(rng):
    import dataclasses
    csr = _toy_csr(rng)
    bad_cols = csr.col_idx.copy()
    bad_cols[0] = csr.shape[1] + 3
    bad_vals = csr.vals.copy()
    bad_vals[1] = np.inf
    bad = dataclasses.replace(csr, col_idx=bad_cols, vals=bad_vals)
    obs = Obs(source="t")
    set_active(obs)
    fixed, report = validate.validate_csr(bad, repair="drop")
    assert report.repaired and not validate.csr_defects(
        fixed.row_ptr, fixed.col_idx, fixed.vals, fixed.shape)
    assert _counter_total(obs, "validate.repaired") >= 1
    # repaired matrix still multiplies
    b = jnp.ones((fixed.shape[1], 4), jnp.float32)
    fmt = loops_from_csr(fixed, fixed.nrows, 4)
    assert loops_spmm(fmt, b).shape == (fixed.shape[0], 4)


def test_csr_from_coo_rejects_and_repairs_bad_coords():
    rows = np.array([0, 1, -1, 2])
    cols = np.array([0, 9, 1, 2])              # 9 is OOB for shape (4, 4)
    vals = np.ones(4, np.float32)
    with pytest.raises(validate.SparseInputError):
        csr_from_coo(rows, cols, vals, (4, 4))
    csr = csr_from_coo(rows, cols, vals, (4, 4), validate="drop")
    # two bad entries dropped (remaining stored entries are empty-row padding)
    assert int(np.count_nonzero(csr.vals)) == 2
    dense = np.zeros((4, 4), np.float32)
    dense[0, 0] = dense[2, 2] = 1.0
    b = np.eye(4, dtype=np.float32)
    fmt = loops_from_csr(csr, csr.nrows, 2)
    assert np.allclose(np.asarray(loops_spmm(fmt, jnp.asarray(b))), dense)


def test_plan_and_convert_validates_strictly(rng):
    import dataclasses
    csr = _toy_csr(rng)
    bad = dataclasses.replace(csr, vals=np.where(
        np.arange(csr.vals.size) == 0, np.nan, csr.vals).astype(np.float32))
    with pytest.raises(validate.SparseInputError):
        plan_and_convert(bad)
    fmt, plan = plan_and_convert(bad, validate="clip")   # repaired instead
    assert fmt is not None and plan is not None


def test_validate_loops_checks_both_parts(rng):
    csr = _toy_csr(rng)
    fmt = loops_from_csr(csr, 8, 4)
    validate.validate_loops(fmt)               # clean format passes
    import dataclasses
    bad_part = dataclasses.replace(
        fmt.bcsr_part, tile_vals=np.full_like(fmt.bcsr_part.tile_vals,
                                              np.nan))
    with pytest.raises(validate.SparseInputError):
        validate.validate_loops(dataclasses.replace(fmt,
                                                    bcsr_part=bad_part))


def test_check_finite_tree_flags_nan_checkpoint():
    good = {"a": np.ones(3, np.float32), "b": {"c": jnp.zeros(2)}}
    validate.check_finite_tree(good)
    bad = {"a": np.array([1.0, np.nan], np.float32)}
    with pytest.raises(validate.SparseInputError) as ei:
        validate.check_finite_tree(bad, what="restored params")
    assert "restored params" in str(ei.value)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_validate_property_classify_or_accept():
    from hypothesis import given, strategies as st

    @given(st.data())
    def run(data):
        n_rows = data.draw(st.integers(1, 6))
        n_cols = data.draw(st.integers(1, 6))
        nnz = data.draw(st.integers(0, 8))
        ptr_steps = data.draw(st.lists(st.integers(-2, 4),
                                       min_size=n_rows, max_size=n_rows))
        row_ptr = np.concatenate([[0], np.cumsum(ptr_steps)]).astype(
            np.int64)
        row_ptr = np.clip(row_ptr, -3, nnz + 3)
        row_ptr[-1] = nnz
        col_idx = np.asarray(data.draw(st.lists(
            st.integers(-2, n_cols + 1), min_size=nnz, max_size=nnz)),
            np.int64)
        vals = np.asarray(data.draw(st.lists(
            st.sampled_from([0.0, 1.0, np.nan, np.inf]),
            min_size=nnz, max_size=nnz)), np.float32)
        kinds = validate.csr_defects(row_ptr, col_idx, vals,
                                     (n_rows, n_cols))
        for k in kinds:       # every defect is in the documented taxonomy
            assert k in validate.DEFECT_KINDS
        import dataclasses

        from repro.core.formats import CSR
        if "length-mismatch" in kinds:
            return            # unrepairable by construction
        csr = CSR(row_ptr=row_ptr, col_idx=col_idx, vals=vals,
                  row_ids=np.arange(n_rows), shape=(n_rows, n_cols)) \
            if hasattr(CSR, "row_ids") else None
        if csr is None:
            return
        if kinds:
            with pytest.raises(validate.SparseInputError):
                validate.validate_csr(csr)
        fixed, _ = validate.validate_csr(csr, repair="drop")
        assert not validate.csr_defects(fixed.row_ptr, fixed.col_idx,
                                        fixed.vals, fixed.shape)

    run()


# ---------------------------------------------------------------------------
# Collective fallback (multi-device: subprocess)
# ---------------------------------------------------------------------------

def test_compressed_psum_falls_back_to_plain(tmp_path):
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": str(ROOT / "src")}
    body = """
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.dist.compress import compressed_psum
        from repro.obs import Obs, set_active
        from repro.resilience.inject import FaultPlan, set_plan

        mesh = make_mesh((2,), ("d",))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((2, 64)).astype(np.float32))
        want = np.asarray(x).sum(0)
        obs = Obs(source="t")
        set_active(obs)
        set_plan(FaultPlan.parse("dist.psum.int8:raise:0:0"))

        @partial(shard_map, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        def f(xs):
            return compressed_psum(xs[0], "d", "int8")[None]

        got = np.asarray(f(x))
        assert np.allclose(got[0], want, atol=1e-5)      # exact fp32 psum
        c = sum(inst.value for kind, inst in obs.metrics.instruments()
                if kind == "counter" and inst.name == "dist.fallback")
        assert c >= 1, c
        print("OK")
    """
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


# ---------------------------------------------------------------------------
# obs_report: degradations section and gates
# ---------------------------------------------------------------------------

def _saved_capture(tmp_path, *, degraded: bool):
    obs = Obs(source="gate-test")
    obs.counter("engine.dispatch", part="csr", op="spmm").inc(3)
    if degraded:
        obs.counter("engine.fallback", part="csr", op="spmm",
                    reason="injected").inc(2)
        obs.counter("tune.cache.quarantined").inc(1)
    jsonl, _ = obs.save(str(tmp_path), stem="gate")
    return jsonl


def _report(path, *flags):
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"), str(path),
         *flags],
        capture_output=True, text=True, timeout=120)


def test_obs_report_degradation_gates(tmp_path):
    clean = _saved_capture(tmp_path / "clean", degraded=False)
    dirty = _saved_capture(tmp_path / "dirty", degraded=True)

    r = _report(clean, "--fail-on-degraded")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "degradations" not in r.stdout

    r = _report(dirty, "--fail-on-degraded")
    assert r.returncode == 4, r.stdout + r.stderr
    assert "engine.fallback" in r.stdout

    r = _report(dirty, "--require-degraded", "engine.fallback",
                "--require-degraded", "tune.cache.quarantined")
    assert r.returncode == 0, r.stdout + r.stderr

    r = _report(clean, "--require-degraded", "engine.fallback")
    assert r.returncode == 5, r.stdout + r.stderr
