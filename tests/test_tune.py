"""repro.tune: fingerprint stability, cache persistence/invalidation,
model-pruned search correctness, and end-to-end autotune numerics."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr_from_dense, csr_to_dense, loops_spmm, suite
from repro.core.spmm import SpmmPlan, plan_and_convert
from repro.tune import (CACHE_VERSION, PlanCache, SearchBudget, Tuner,
                        autotune, cache_key, enumerate_plans,
                        feature_distance, fingerprint, search)
from repro.tune import api as tune_api


def _dense(seed, m, k, density):
    rng = np.random.default_rng(seed)
    return ((rng.random((m, k)) < density)
            * rng.standard_normal((m, k))).astype(np.float32)


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------

def test_fingerprint_stable_across_reconstruction():
    """Same structure -> identical fingerprint and key, however built."""
    a = _dense(0, 64, 48, 0.2)
    fp1 = fingerprint(csr_from_dense(a))
    fp2 = fingerprint(csr_from_dense(csr_to_dense(csr_from_dense(a))))
    assert fp1 == fp2
    k1 = cache_key(fp1, n_cols=32, dtype=np.float32, backend="jnp")
    k2 = cache_key(fp2, n_cols=32, dtype=np.float32, backend="jnp")
    assert k1 == k2


def test_fingerprint_value_invariant():
    """Fingerprints key on structure, not values (pruned layers share)."""
    a = _dense(1, 32, 32, 0.3)
    b = a * 3.5
    assert fingerprint(csr_from_dense(a)) == fingerprint(csr_from_dense(b))


def test_fingerprint_sensitive_to_structure():
    band = suite.banded(256, 256, 4, seed=0)
    power = suite.powerlaw(256, 256, 6.0, seed=0)
    fpb, fpp = fingerprint(band), fingerprint(power)
    assert feature_distance(fpb.features(), fpp.features()) > 0.25
    assert cache_key(fpb, n_cols=32, dtype=np.float32, backend="jnp") != \
        cache_key(fpp, n_cols=32, dtype=np.float32, backend="jnp")


def test_cache_key_separates_execution_context():
    fp = fingerprint(suite.banded(128, 128, 3, seed=0))
    base = cache_key(fp, n_cols=32, dtype=np.float32, backend="jnp")
    assert base != cache_key(fp, n_cols=64, dtype=np.float32, backend="jnp")
    assert base != cache_key(fp, n_cols=32, dtype=jnp.bfloat16, backend="jnp")
    assert base != cache_key(fp, n_cols=32, dtype=np.float32,
                             backend="interpret")


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def _record(features, gflops=1.0, backend="jnp"):
    return {"version": CACHE_VERSION, "fingerprint": list(features),
            "dtype": "float32", "n_cols": 32, "backend": backend,
            "plan": {"r_frac": 0.25, "t_vpu": 2, "t_mxu": 6, "br": 8},
            "gflops": gflops, "trials": 3}


def test_cache_round_trip(tmp_path):
    c1 = PlanCache(str(tmp_path))
    c1.put("k1", _record([1.0, 2.0]))
    # A fresh instance reads the same file from disk.
    c2 = PlanCache(str(tmp_path))
    rec = c2.get("k1")
    assert rec is not None and rec["plan"]["t_mxu"] == 6
    assert c2.stats.hits == 1
    assert c2.get("absent") is None
    assert c2.stats.misses == 1


def test_cache_version_mismatch_invalidates(tmp_path):
    c1 = PlanCache(str(tmp_path))
    c1.put("k1", _record([1.0]))
    blob = json.loads((tmp_path / "plans.json").read_text())
    blob["version"] = CACHE_VERSION + 1
    (tmp_path / "plans.json").write_text(json.dumps(blob))
    c2 = PlanCache(str(tmp_path))
    assert c2.get("k1") is None   # stale-version entries are discarded
    assert len(c2) == 0


def test_cache_corrupt_file_is_empty_not_fatal(tmp_path):
    (tmp_path / "plans.json").write_text("{not json")
    c = PlanCache(str(tmp_path))
    assert c.get("k") is None
    c.put("k", _record([0.0]))    # and the file heals on the next put
    assert PlanCache(str(tmp_path)).peek("k") is not None


def test_cache_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "envdir"))
    c = PlanCache()
    assert c.dir == str(tmp_path / "envdir")
    c.put("k", _record([1.0]))
    assert (tmp_path / "envdir" / "plans.json").exists()


def test_cache_near_match_lookup(tmp_path):
    c = PlanCache(str(tmp_path))
    c.put("k1", _record([1.0, 2.0, 3.0]))
    # close by but not exact: near-hit within distance
    rec = c.lookup("other-key", features=[1.05, 2.0, 3.0], dtype="float32",
                   n_cols=32, backend="jnp", max_distance=0.25)
    assert rec is not None
    assert c.stats.near_hits == 1
    # far away: miss
    assert c.lookup("other-key", features=[5.0, 2.0, 3.0], dtype="float32",
                    n_cols=32, backend="jnp", max_distance=0.25) is None
    assert c.stats.misses == 1
    # same features, different execution context: miss
    assert c.lookup("other-key", features=[1.0, 2.0, 3.0], dtype="bfloat16",
                    n_cols=32, backend="jnp", max_distance=0.25) is None


def test_cache_lru_front_bounded(tmp_path):
    c = PlanCache(str(tmp_path), lru_size=2)
    for i in range(5):
        c.put(f"k{i}", _record([float(i)]))
    assert len(c._lru) <= 2       # front stays bounded...
    assert len(c) == 5            # ...while disk keeps everything
    assert c.get("k0") is not None


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def test_search_matches_exhaustive_on_tiny_space():
    """With a deterministic score and a budget covering the whole space, the
    search must return the exhaustive argmax."""
    csr = csr_from_dense(_dense(2, 32, 24, 0.2))

    def score(plan):   # deterministic, maximised at (r_b high, br=4, G=8)
        return plan.r_boundary * 0.1 + (10.0 if plan.br == 4 else 0.0) \
            + plan.t_mxu * 0.01 + plan.panel_g * 0.001

    def measure(c, plan, b):
        from repro.core import loops_from_csr
        return loops_from_csr(c, plan.r_boundary, plan.br,
                              panel_g=plan.panel_g), score(plan)

    plans = enumerate_plans(csr, total_workers=4, br_choices=(2, 4),
                            g_choices=(1, 8))
    # budget large enough that pruning keeps every distinct conversion
    n_convs = len({(p.r_boundary, p.br, p.panel_g) for p in plans})
    res = search(csr, n_cols=8, total_workers=4, br_choices=(2, 4),
                 g_choices=(1, 8),
                 budget=SearchBudget(top_k=n_convs, max_trials=n_convs),
                 measure=measure)
    best_conv = max(plans, key=score)
    assert (res.plan.r_boundary, res.plan.br, res.plan.panel_g) == \
        (best_conv.r_boundary, best_conv.br, best_conv.panel_g)
    assert res.gflops == pytest.approx(max(g for _, g in res.trials))


@pytest.mark.parametrize("dtype,unit", [(np.float32, 8),
                                        (jnp.bfloat16, 16)])
def test_search_on_pallas_keeps_tile_heights_the_chip_takes(dtype, unit):
    """On the pallas backend every candidate's Br fills whole sublane
    tiles: the chip refuses a (2, bn) or (4, bn) BCSR output block."""
    csr = csr_from_dense(_dense(3, 40, 16, 0.15)).astype(dtype)
    seen = []

    def measure(c, plan, b):
        from repro.core import loops_from_csr
        seen.append(plan.br)
        return loops_from_csr(c, plan.r_boundary, plan.br), 1.0

    res = search(csr, n_cols=8, total_workers=4, backend="pallas",
                 budget=SearchBudget(top_k=4, max_trials=4), measure=measure)
    assert seen and all(br % unit == 0 for br in seen)
    assert res.plan.br % unit == 0


def test_search_prunes_to_budget():
    csr = csr_from_dense(_dense(3, 40, 16, 0.15))
    calls = []

    def measure(c, plan, b):
        from repro.core import loops_from_csr
        calls.append(plan)
        return loops_from_csr(c, plan.r_boundary, plan.br), 1.0

    res = search(csr, n_cols=8, total_workers=8,
                 budget=SearchBudget(top_k=3, max_trials=3), measure=measure)
    assert len(calls) <= 3
    assert res.measured == len(calls)


def test_search_warm_start_spans_conversions():
    """The prior must rank conversions, not just splits: at the default
    budget the measured set has to include an *interior* (hybrid) boundary
    from the Eq. 1 sweep, not only the enumeration-order pure plans."""
    csr = csr_from_dense(_dense(8, 256, 64, 0.1))
    measured = []

    def measure(c, plan, b):
        from repro.core import loops_from_csr
        measured.append(plan)
        return loops_from_csr(c, plan.r_boundary, plan.br), 1.0

    search(csr, n_cols=8, total_workers=8, measure=measure)
    r_bs = {p.r_boundary for p in measured}
    assert any(0 < r < csr.nrows for r in r_bs), r_bs
    assert len({(p.r_boundary, p.br, p.panel_g)
                for p in measured}) == len(measured)


def test_plan_from_record_preserves_pure_plans():
    """A pure-CSR winner must rehydrate to r_boundary == nrows even when
    nrows is not a br multiple (and pure-BCSR to 0) — the floor-to-tile
    snap applies only to interior boundaries."""
    from repro.tune import make_record, plan_from_record
    rec = make_record([0.0], dtype=np.float32, n_cols=8, backend="jnp",
                      r_frac=1.0, t_vpu=8, t_mxu=0, br=4)
    plan = plan_from_record(rec, nrows=130)
    assert plan.r_boundary == 130          # not floored to 128
    rec = make_record([0.0], dtype=np.float32, n_cols=8, backend="jnp",
                      r_frac=0.0, t_vpu=0, t_mxu=8, br=4)
    assert plan_from_record(rec, nrows=130).r_boundary == 0
    # boundary forced consistent with a degenerate split
    rec = make_record([0.0], dtype=np.float32, n_cols=8, backend="jnp",
                      r_frac=0.5, t_vpu=8, t_mxu=0, br=4)
    assert plan_from_record(rec, nrows=130).r_boundary == 130


def test_autotune_near_hit_promotes_to_exact_key(tmp_path):
    """A near-match is promoted under the matrix's own exact key, so the
    next lookup is exact and reporting paths (tune_suite) never see NaN."""
    from repro.tune import make_record, tune_suite
    cache = PlanCache(str(tmp_path))
    csr = suite.table2_like("m12", scale_rows=128, seed=3)
    fp = fingerprint(csr)
    neighbour = make_record(fp.features() + 0.05, dtype=np.float32,
                            n_cols=8, backend="jnp", r_frac=0.25,
                            t_vpu=2, t_mxu=6, br=8, gflops=1.5, trials=3)
    cache.put("neighbour-key", neighbour)
    _, plan = autotune(csr, n_cols=8, cache=cache)
    assert cache.stats.near_hits == 1 and cache.stats.misses == 0
    exact = cache_key(fp, n_cols=8, dtype=np.float32, backend="jnp")
    assert cache.peek(exact) is not None   # promoted
    # and tune_suite reports the borrowed gflops, never NaN
    report = tune_suite({"m": csr}, n_cols=8, cache=cache)
    assert np.isfinite(report["m"][1])
    assert cache.stats.hits >= 1           # follow-up lookups are exact


def test_enumerate_plans_no_degenerate_splits():
    csr = csr_from_dense(_dense(4, 24, 24, 0.2))
    for p in enumerate_plans(csr, total_workers=4):
        if p.r_boundary > 0:
            assert p.t_vpu > 0    # a non-empty CSR region needs VPU workers
        if p.r_boundary < csr.nrows:
            assert p.t_mxu > 0


# ---------------------------------------------------------------------------
# autotune end-to-end
# ---------------------------------------------------------------------------

def test_autotune_repeat_is_pure_cache_hit(tmp_path, monkeypatch):
    """Acceptance criterion: the second call is an exact hit that performs
    zero measurements (search is never entered)."""
    cache = PlanCache(str(tmp_path))
    csr = suite.table2_like("m12", scale_rows=128, seed=1)
    budget = SearchBudget(top_k=2, repeats=1, warmup=0)
    fmt1, plan1 = autotune(csr, n_cols=8, cache=cache, budget=budget)
    assert cache.stats.misses == 1 and cache.stats.hits == 0

    def no_search(*a, **k):
        raise AssertionError("cache hit must skip the search entirely")
    monkeypatch.setattr(tune_api, "search", no_search)
    fmt2, plan2 = autotune(csr, n_cols=8, cache=cache, budget=budget)
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert plan2 == plan1
    assert fmt2.r_boundary == fmt1.r_boundary


def test_autotune_numerics_match_loops_spmm(tmp_path):
    """autotune's (fmt, plan) executes to the same result as the dense
    ground truth — tuning never changes semantics."""
    cache = PlanCache(str(tmp_path))
    a = _dense(5, 48, 32, 0.25)
    csr = csr_from_dense(a)
    fmt, plan = autotune(csr, n_cols=8, cache=cache,
                         budget=SearchBudget(top_k=2, repeats=1, warmup=0))
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    out = loops_spmm(fmt, b, backend="jnp")
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)
    assert 0 <= plan.r_boundary <= csr.nrows


def test_plan_and_convert_tuner_path(tmp_path):
    """core front door: `tuner=` replaces the model-only path and shares the
    cache across call sites (the sparse-FFN / GCN reuse story)."""
    tuner = Tuner(cache=PlanCache(str(tmp_path)), n_cols=8,
                  budget=SearchBudget(top_k=2, repeats=1, warmup=0))
    a = _dense(6, 40, 24, 0.2)
    fmt, plan = plan_and_convert(csr_from_dense(a), tuner=tuner)
    assert isinstance(plan, SpmmPlan)
    # second call site with the same structure: a hit, same plan
    _, plan2 = plan_and_convert(csr_from_dense(a), tuner=tuner)
    assert plan2 == plan
    assert tuner.cache.stats.hits == 1 and tuner.cache.stats.misses == 1


# ---------------------------------------------------------------------------
# satellite regressions (hypothesis-free home: runs in minimal environments
# where tests/test_formats.py / test_perf_model.py are collect-ignored)
# ---------------------------------------------------------------------------

def test_coo_duplicates_coalesced_in_structure():
    """csr_from_coo must *sum* colliding (row, col) coordinates during
    construction: un-coalesced duplicates inflate nnz and every statistic
    derived from it (row stats, perf-model inputs, tuner fingerprints)."""
    from repro.core import csr_from_coo
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 16, 200)
    cols = rng.integers(0, 16, 200)
    vals = rng.standard_normal(200).astype(np.float32)
    csr = csr_from_coo(rows, cols, vals, (16, 16))
    coords = list(zip(csr.row_ids.tolist(), csr.col_idx.tolist()))
    assert len(coords) == len(set(coords))
    # Regression vs csr_to_dense: reconstruction equals the summed scatter.
    want = np.zeros((16, 16), np.float32)
    np.add.at(want, (rows, cols), vals)
    np.testing.assert_allclose(csr_to_dense(csr), want, rtol=1e-6)


def test_suite_uniform_has_no_duplicate_coords():
    """suite.uniform draws colliding coordinates; construction coalesces."""
    csr = suite.uniform(64, 64, 0.2, seed=0)
    coords = list(zip(csr.row_ids.tolist(), csr.col_idx.tolist()))
    assert len(coords) == len(set(coords))


def test_perf_model_rank_deficient_fit_is_ridge():
    """< 5 distinct (x, y) points underdetermine Eq. 2: the fit must stay
    finite, interpolate the measurements, and keep best_allocation sane."""
    from repro.core.perf_model import fit_perf_model
    pts = [(1, 1), (2, 2), (4, 4)] * 2
    perfs = [2.0, 4.0, 8.0] * 2
    m = fit_perf_model(pts, perfs)
    assert np.isfinite(m.coef).all()
    for (x, y), p in zip(pts, perfs):
        assert float(m.predict(x, y)) == pytest.approx(p, rel=1e-3)
    x, y = m.best_allocation(8)
    assert 0 < x + y <= 8
    # Collinear axis-only samples: predictions off-axis stay bounded.
    m2 = fit_perf_model([(x, 0) for x in range(6)],
                        [float(x) for x in range(6)])
    assert np.isfinite(m2.coef).all()
    assert abs(float(m2.predict(0, 8))) < 1e3


def test_perf_model_panel_terms():
    """(x, y, g) samples fit the panel-extended model: g is ranked by its
    own concave terms and best_allocation_g recovers the sweet spot, while
    5-coefficient models keep ignoring g (backward compatibility)."""
    from repro.core.perf_model import calibrate, fit_perf_model

    def perf(x, y, g):  # saturating panel win, peak at g = 8
        return 2.0 * x + 5.0 * y + 3.0 * g - 0.18 * g * g

    samples = [(x, y, g) for x in range(5) for y in range(5 - x)
               for g in (1, 4, 8)]
    m = fit_perf_model(samples, [perf(*s) for s in samples])
    assert m.has_panel_terms
    assert float(m.predict(2, 2, 8)) == pytest.approx(perf(2, 2, 8), rel=1e-6)
    x, y, g = m.best_allocation_g(8, g_choices=(1, 4, 8))
    assert (x + y <= 8) and g == 8
    # calibrate() crosses the representative splits with g_choices
    m2 = calibrate(lambda x, y, g: perf(x, y, g), 8, g_choices=(1, 4, 8))
    assert m2.has_panel_terms
    # a plain Eq. 2 model ignores g entirely
    flat = fit_perf_model([(x, y) for x in range(5) for y in range(5)],
                          [2.0 * x + 5.0 * y for x in range(5)
                           for y in range(5)])
    assert not flat.has_panel_terms
    assert float(flat.predict(1, 1, 8)) == float(flat.predict(1, 1, 1))


def test_cached_plan_replays_panel_g(tmp_path):
    """A tuned plan's panel width survives the cache round trip and drives
    the rehydrated conversion."""
    from repro.tune import make_record, plan_from_record
    rec = make_record([0.0], dtype=np.float32, n_cols=8, backend="jnp",
                      r_frac=0.5, t_vpu=4, t_mxu=4, br=8, panel_g=4)
    plan = plan_from_record(rec, nrows=64)
    assert plan.panel_g == 4
    from repro.core import loops_from_csr
    fmt = loops_from_csr(csr_from_dense(_dense(1, 64, 32, 0.2)),
                         plan.r_boundary, plan.br, panel_g=plan.panel_g)
    assert fmt.panel_g == 4
    assert fmt.csr_panels.g == 4 and fmt.bcsr_panels.g == 4


def test_shard_loops_auto_consults_cache(tmp_path):
    from repro.core import loops_from_csr
    from repro.core.distributed import shard_loops_auto
    cache = PlanCache(str(tmp_path))
    a = _dense(7, 64, 32, 0.2)
    fmt = loops_from_csr(csr_from_dense(a), 32, 8)
    s1 = shard_loops_auto(fmt, 4, cache=cache)      # miss -> solve -> put
    assert cache.stats.misses == 1
    s2 = shard_loops_auto(fmt, 4, cache=cache)      # hit -> reuse split
    assert cache.stats.hits == 1
    assert s2.g_vpu == s1.g_vpu
    # a different device count is a different cache context
    shard_loops_auto(fmt, 8, cache=cache)
    assert cache.stats.misses == 2


@pytest.mark.parametrize("g", [4, 32])
def test_shard_loops_auto_records_the_format_width(tmp_path, g):
    """The device split a distributed re-plan stores carries the width the
    format's panels are packed at, and replays at that width."""
    from repro.core import loops_from_csr
    from repro.core.distributed import shard_loops_auto
    from repro.tune.fingerprint import cache_key, loops_fingerprint
    cache = PlanCache(str(tmp_path))
    fmt = loops_from_csr(csr_from_dense(_dense(7, 64, 32, 0.2)), 32, 8,
                         panel_g=g)
    shard_loops_auto(fmt, 4, cache=cache)
    key = cache_key(loops_fingerprint(fmt), n_cols=0,
                    dtype=np.dtype(np.float32), backend="dist4")
    rec = cache.peek(key)
    assert rec["plan"]["panel_g"] == g == fmt.csr_panels.g
    assert tune_api.plan_from_record(rec, fmt.nrows).panel_g == g


def test_effective_n_cols_and_batched_cache_key(tmp_path):
    """Batched operands key plans on prod(batch)*N — a (4, K, 16) workload
    and an unbatched n_cols=64 one share the key; n_cols=16 does not."""
    from repro.tune import effective_n_cols
    assert effective_n_cols((64, 16)) == 16
    assert effective_n_cols((4, 64, 16)) == 64
    assert effective_n_cols((2, 3, 64, 16)) == 96
    with pytest.raises(ValueError):
        effective_n_cols((64,))
    a = _dense(11, 96, 64, 0.2)
    csr = csr_from_dense(a)
    cache = PlanCache(str(tmp_path))
    budget = SearchBudget(top_k=1, repeats=1, warmup=0)
    autotune(csr, rhs_shape=(4, 64, 16), cache=cache, budget=budget)
    autotune(csr, n_cols=64, cache=cache, budget=budget)   # same effective
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    autotune(csr, n_cols=16, cache=cache, budget=budget)   # different
    assert cache.stats.misses == 2


def test_search_measures_batched_operand(tmp_path):
    """search(rhs_shape=...) hands the measurement fn a batched operand of
    exactly that shape, so candidates are timed on the real batched call."""
    a = _dense(12, 64, 32, 0.25)
    csr = csr_from_dense(a)
    seen = []

    def fake_measure(c, plan, b):
        seen.append(tuple(b.shape))
        from repro.core import loops_from_csr
        fmt = loops_from_csr(c, plan.r_boundary, plan.br,
                             panel_g=plan.panel_g)
        return fmt, 1.0

    search(csr, rhs_shape=(3, 32, 8), measure=fake_measure,
           budget=SearchBudget(top_k=2))
    assert seen and all(s == (3, 32, 8) for s in seen)
    with pytest.raises(ValueError, match="ncols"):
        search(csr, rhs_shape=(3, 16, 8), measure=fake_measure)
    # an explicit b that disagrees with rhs_shape is an error, not a
    # silently-unbatched measurement
    with pytest.raises(ValueError, match="rhs_shape"):
        search(csr, b=jnp.zeros((32, 8)), rhs_shape=(3, 32, 8),
               measure=fake_measure)


def test_cache_stats_counted_exactly_once(tmp_path):
    """One logical lookup lands in exactly one bucket — an exact-probe
    fall-through to the near scan that then misses is ONE miss, never an
    exact-miss plus a near-miss (the counted-exactly-once contract the
    obs ``tune.cache.*`` gauges rely on)."""
    cache = PlanCache(str(tmp_path))
    a = _dense(21, 96, 64, 0.2)
    csr = csr_from_dense(a)
    fp = fingerprint(csr)
    key = cache_key(fp, n_cols=32, dtype="float32", backend="jnp")
    # miss with the near scan enabled: exact probe + near scan = 1 miss
    cache.lookup(key, features=fp.features, dtype="float32",
                 n_cols=32, backend="jnp", max_distance=0.25)
    assert (cache.stats.hits, cache.stats.near_hits,
            cache.stats.misses) == (0, 0, 1)
    assert cache.stats.lookups == 1
    # get() routes through the same single accounting point
    cache.put("k", {"plan": 1})
    assert cache.get("k") is not None
    assert (cache.stats.hits, cache.stats.misses) == (1, 1)
    assert cache.stats.lookups == 2
    # peek/nearest are side-effect-free internals
    cache.peek("k")
    cache.peek("absent")
    cache.nearest(fp.features, dtype="float32", n_cols=32, backend="jnp",
                  max_distance=0.25)
    assert cache.stats.lookups == 2


def test_cache_stats_reset(tmp_path):
    cache = PlanCache(str(tmp_path))
    cache.put("k", {"plan": 1})
    cache.lookup("k")
    cache.lookup("absent")
    assert cache.stats.lookups == 2 and cache.stats.hit_rate == 0.5
    cache.stats.reset()
    assert (cache.stats.hits, cache.stats.near_hits,
            cache.stats.misses) == (0, 0, 0)
    assert cache.stats.lookups == 0 and cache.stats.hit_rate == 0.0
    cache.lookup("k")                      # a fresh measurement window
    assert cache.stats.hits == 1 and cache.stats.lookups == 1


# ---------------------------------------------------------------------------
# prewarm: the serving warm-pool bulk-install path
# ---------------------------------------------------------------------------

def _tuned_record(features, **kw):
    from repro.tune import make_record
    defaults = dict(dtype=np.float32, n_cols=8, backend="jnp", r_frac=0.5,
                    t_vpu=4, t_mxu=6, br=8)
    defaults.update(kw)
    return make_record(features, **defaults)


def test_prewarm_counts_each_new_key_exactly_once(tmp_path):
    from repro.tune.fingerprint import cache_key_from_features
    cache = PlanCache(str(tmp_path))
    recs = [_tuned_record([1.0, 2.0]), _tuned_record([3.0, 4.0])]
    assert cache.prewarm(recs) == 2
    assert cache.stats.prewarmed == 2
    # re-prewarming the same set is a no-op that counts ZERO...
    before = (tmp_path / "plans.json").stat().st_mtime_ns
    assert cache.prewarm(recs) == 0
    assert cache.stats.prewarmed == 2
    # ...and never touches disk (one atomic save on install, none on no-op)
    assert (tmp_path / "plans.json").stat().st_mtime_ns == before
    # a partially-fresh batch counts only the newcomers
    assert cache.prewarm(recs + [_tuned_record([5.0, 6.0])]) == 1
    assert cache.stats.prewarmed == 3
    # installed records are served as plain hits under their rebuilt key
    key = cache_key_from_features([1.0, 2.0], n_cols=8, dtype=np.float32,
                                  backend="jnp")
    assert cache.get(key)["plan"]["t_mxu"] == 6


def test_prewarm_keys_match_cache_key_of_source_matrix(tmp_path):
    """A record tuned via the normal put(cache_key(...)) path and the same
    record bulk-installed via prewarm land under ONE key — the warm pool
    actually front-loads the hits the tuner would have minted."""
    cache = PlanCache(str(tmp_path))
    fp = fingerprint(csr_from_dense(_dense(2, 64, 48, 0.2)))
    key = cache_key(fp, n_cols=8, dtype=np.float32, backend="jnp")
    rec = _tuned_record(fp.features())
    cache.put(key, rec)
    pool = PlanCache(str(tmp_path / "pool"))
    assert pool.prewarm([rec]) == 1
    assert pool.peek(key) is not None      # rebuilt key == minted key
    assert pool.get(key)["fingerprint"] == rec["fingerprint"]


def test_prewarm_accepts_explicit_key_mapping(tmp_path):
    cache = PlanCache(str(tmp_path))
    assert cache.prewarm({"a": {"plan": 1}, "b": {"plan": 2}}) == 2
    cache.put("c", {"plan": 3})
    # mapping form skips present keys too, whoever installed them
    assert cache.prewarm({"b": {"plan": 9}, "c": {"plan": 9},
                          "d": {"plan": 4}}) == 1
    assert cache.peek("b")["plan"] == 2    # prewarm never overwrites
    assert cache.stats.prewarmed == 3
    # a fresh instance reads everything back (the one save was real)
    assert PlanCache(str(tmp_path)).peek("d")["plan"] == 4


def test_prewarm_survives_round_trip_through_disk(tmp_path):
    """serve.py's flow: tune into one cache, prewarm a pool from the tuned
    records, reload the pool in a fresh process."""
    tuned = PlanCache(str(tmp_path / "tuned"))
    fp = fingerprint(csr_from_dense(_dense(3, 48, 32, 0.3)))
    key = cache_key(fp, n_cols=8, dtype=np.float32, backend="jnp")
    tuned.put(key, _tuned_record(fp.features()))
    pool = PlanCache(str(tmp_path / "pool"))
    pool.prewarm([tuned.peek(key)])
    fresh = PlanCache(str(tmp_path / "pool"))
    assert fresh.get(key) is not None and fresh.stats.hits == 1
    # and the stats line surfaces the prewarm count
    assert "prewarmed=1" in str(pool.stats)
