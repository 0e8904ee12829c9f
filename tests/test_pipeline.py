"""Pipelined panel kernels: depth/macro parity, grid-step accounting,
packed half-precision, knob plumbing, and the default_bn regression.

The contracts under test (docs/architecture.md §"Pipelined panels"):

  * ``pipeline_depth ∈ {1, 2}`` NEVER changes results — unbatched results
    are *bitwise* identical across depths (the piped compute stream replays
    the depth-1 expression from scratch); batched results agree to ~1 ulp
    (XLA contracts multiply-adds differently across the two graphs);
  * ``macro_m`` panelizes at the effective width ``panel_g·macro_m`` and
    agrees with the oracle to dtype tolerance;
  * grid steps = ``(panels_at_g_eff + depth - 1) × col_blocks`` per
    non-empty part, and ``perf.replay.predict_part_steps`` replicates the
    conversion exactly;
  * ``default_bn`` picks the largest lane-aligned divisor ≤ 512 (the
    ``N=600`` ValueError regression);
  * plans round-trip the knobs through the v4 tuner cache, and dispatch
    notes carry ``scratch_bytes``/``prefetch_overlap`` into obs gauges.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr_from_dense, loops_from_csr, loops_spmm
from repro.core.spmm import SpmmPlan, loops_grid_steps, plan_and_convert
from repro.kernels.panel_common import default_bn
from repro.perf.replay import predict_part_steps

try:
    from hypothesis import given
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # minimal env: property test skipped below
    HAVE_HYPOTHESIS = False

DTYPES = [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)]
M, K, N = 21, 17, 16         # awkward: not multiples of br/g/panel widths


def _sparse(rng, m, k, density, dtype):
    a = ((rng.random((m, k)) < density) * rng.standard_normal((m, k)))
    return np.asarray(jnp.asarray(a, dtype))


def _fmt(csr, g, depth, macro, r_frac=0.5, br=4):
    r_b = min(max(int(r_frac * csr.nrows) // br * br, 0), csr.nrows)
    return loops_from_csr(csr, r_b, br, panel_g=g, pipeline_depth=depth,
                          macro_m=macro)


# -- forward parity vs oracle: dtypes x G x depth x macro -------------------

@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", [1, 8])
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("macro", [1, 4])
def test_piped_fused_path_matches_oracle(rng, dtype, tol, g, depth, macro):
    """The fused single-pass engine path (input_output_aliases carry) under
    every knob combination must agree with the dense oracle."""
    a = _sparse(rng, M, K, 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    fmt = _fmt(csr_from_dense(a), g, depth, macro)
    got = loops_spmm(fmt, b, backend="interpret")
    want = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=10 * tol, atol=10 * tol)


def test_fp64_piped_matches_oracle(rng):
    jax.config.update("jax_enable_x64", True)
    try:
        a = _sparse(rng, M, K, 0.3, jnp.float64)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float64)
        for g in (1, 8):
            fmt = _fmt(csr_from_dense(a), g, 2, 4)
            got = loops_spmm(fmt, b, backend="interpret")
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(a) @ np.asarray(b),
                                       rtol=1e-12)
    finally:
        jax.config.update("jax_enable_x64", False)


# -- the depth contract: bitwise unbatched, ~ulp batched --------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("macro", [1, 4])
def test_depth_is_bitwise_invariant_unbatched(rng, dtype, g, macro):
    """pipeline_depth=2 must be EXACTLY depth-1, bit for bit (unbatched):
    the piped kernels stage raw B rows + the mask panel and replay the
    depth-1 expression, so the float graphs are identical."""
    a = _sparse(rng, M, K, 0.3, dtype)
    csr = csr_from_dense(a)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    y1 = loops_spmm(_fmt(csr, g, 1, macro), b, backend="interpret")
    y2 = loops_spmm(_fmt(csr, g, 2, macro), b, backend="interpret")
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_depth_parity_batched(rng):
    """Batched (rank-3) depth parity: allclose, not bitwise — XLA contracts
    the multiply-adds of the two graphs differently at bz > 1."""
    a = _sparse(rng, M, K, 0.3, jnp.float32)
    csr = csr_from_dense(a)
    b3 = jnp.asarray(rng.standard_normal((4, K, N)).astype(np.float32))
    y1 = loops_spmm(_fmt(csr, 4, 1, 1), b3, backend="interpret")
    y2 = loops_spmm(_fmt(csr, 4, 2, 1), b3, backend="interpret")
    assert y1.shape == (4, M, N)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-6, atol=1e-6)


def test_depth_parity_row_boundary_tails(rng):
    """Row-boundary tails: a conversion whose last CSR panel and last BCSR
    block-row are both partial must stay depth-invariant."""
    a = _sparse(rng, 23, 19, 0.4, jnp.float32)
    csr = csr_from_dense(a)
    b = jnp.asarray(rng.standard_normal((19, 8)).astype(np.float32))
    for r_b in (4, 20):     # tails in both parts
        y1 = loops_spmm(loops_from_csr(csr, r_b, 8, panel_g=4), b,
                        backend="interpret")
        y2 = loops_spmm(loops_from_csr(csr, r_b, 8, panel_g=4,
                                       pipeline_depth=2), b,
                        backend="interpret")
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_depth_parity_gradients(rng):
    """The SDD backward pipeline (depth-2 column-block reduction) must
    produce the same gradients as the serial path."""
    a = _sparse(rng, M, K, 0.3, jnp.float32)
    csr = csr_from_dense(a)
    b = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))

    def loss(fmt):
        return jax.grad(lambda bb: jnp.sum(
            loops_spmm(fmt, bb, backend="interpret")))(b)

    g1 = loss(_fmt(csr, 4, 1, 1))
    g2 = loss(_fmt(csr, 4, 2, 1))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-6, atol=1e-6)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2**31 - 1),
           g=st.sampled_from([1, 4, 8]),
           depth=st.sampled_from([1, 2]),
           macro=st.sampled_from([1, 2, 4]),
           density=st.floats(0.05, 0.6))
    def test_knobs_never_change_results_property(seed, g, depth, macro,
                                                 density):
        """Property: for ANY seeded matrix, (depth, macro) only reshape the
        schedule — the result still matches the knob-less execution to
        float32 tolerance, and depth alone is bitwise-invariant."""
        rng = np.random.default_rng(seed)
        a = _sparse(rng, 12, 10, density, jnp.float32)
        csr = csr_from_dense(a)
        b = jnp.asarray(rng.standard_normal((10, 8)).astype(np.float32))
        base = loops_spmm(_fmt(csr, g, 1, 1), b, backend="interpret")
        knobbed = loops_spmm(_fmt(csr, g, depth, macro), b,
                             backend="interpret")
        np.testing.assert_allclose(np.asarray(base), np.asarray(knobbed),
                                   rtol=1e-5, atol=1e-5)
        if macro == 1:
            np.testing.assert_array_equal(
                np.asarray(base), np.asarray(knobbed))
else:
    def test_knobs_never_change_results_property():
        pytest.skip("hypothesis not installed")


# -- default_bn: the N=600 regression --------------------------------------

def test_default_bn_units():
    assert default_bn(600) == 600       # no 128-lane divisor: whole row
    assert default_bn(1024) == 512
    assert default_bn(1536) == 512
    assert default_bn(640) == 128
    assert default_bn(512) == 512
    assert default_bn(32) == 32         # n <= 512: whole operand, one block
    assert default_bn(1) == 1
    for n in (600, 1000, 1536, 700, 640):
        bn = default_bn(n)
        # TPU tiling: a block's last dim is a 128-lane multiple or the row
        assert n % bn == 0 and (bn == n or bn % 128 == 0)


def test_wide_operand_n600_regression(rng):
    """N=600 used to raise (600 % min(600, 512) != 0); default_bn now picks
    a legal block (the whole row) and the kernels execute end to end."""
    a = _sparse(rng, 16, 12, 0.3, jnp.float32)
    csr = csr_from_dense(a)
    b = jnp.asarray(rng.standard_normal((12, 600)).astype(np.float32))
    got = loops_spmm(_fmt(csr, 4, 2, 4), b, backend="interpret")
    want = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


# -- grid-step accounting ---------------------------------------------------

def test_grid_steps_ramp_and_macro(rng):
    """Steps = (panels_at_g_eff + depth - 1) x col_blocks per non-empty
    part; macro_m shrinks the panel count, depth adds the ramp."""
    a = _sparse(rng, 24, 20, 0.4, jnp.float32)
    csr = csr_from_dense(a)
    base = loops_grid_steps(_fmt(csr, 4, 1, 1), 16)
    fused = loops_grid_steps(_fmt(csr, 4, 1, 4), 16)
    piped = loops_grid_steps(_fmt(csr, 4, 2, 1), 16)
    assert fused < base                  # macro fusion shrinks the grid
    assert piped == base + 2             # one ramp step per non-empty part


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("macro", [1, 4])
@pytest.mark.parametrize("n_cols", [16, 600])
def test_predict_part_steps_matches_conversion(rng, depth, macro, n_cols):
    """perf.replay's structural predictor must replicate the conversion's
    grid-step count exactly for every knob combination."""
    a = _sparse(rng, 32, 24, 0.25, jnp.float32)
    csr = csr_from_dense(a)
    for r_frac in (0.0, 0.5, 1.0):
        r_b = min(max(int(r_frac * 32) // 4 * 4, 0), 32)
        plan = SpmmPlan(r_boundary=r_b, t_vpu=2, t_mxu=2, br=4, panel_g=4,
                        pipeline_depth=depth, macro_m=macro)
        fmt = loops_from_csr(csr, r_b, 4, panel_g=4, pipeline_depth=depth,
                             macro_m=macro)
        s_csr, s_bcsr = predict_part_steps(csr, plan, n_cols)
        assert s_csr + s_bcsr == loops_grid_steps(fmt, n_cols)


# -- knob plumbing: plan/convert, tuner cache v4, dispatch notes ------------

def test_plan_and_convert_threads_knobs(rng):
    a = _sparse(rng, 24, 20, 0.3, jnp.float32)
    fmt, plan = plan_and_convert(csr_from_dense(a), total_workers=4,
                                 pipeline_depth=2, macro_m=4)
    assert plan.pipeline_depth == 2 and plan.macro_m == 4
    assert fmt.pipeline_depth == 2 and fmt.macro_m == 4
    assert fmt.panel_g_eff == max(fmt.panel_g, 1) * 4


def test_cache_v4_roundtrip_and_v3_miss(tmp_path, rng):
    """Records round-trip the knobs; a v3 (knob-less) cache file misses
    cleanly under CACHE_VERSION 4."""
    import json

    from repro.tune.api import make_record, plan_from_record
    from repro.tune.cache import CACHE_VERSION, PlanCache

    assert CACHE_VERSION == 4
    rec = make_record([1.0], dtype=np.float32, n_cols=32, backend="jnp",
                      r_frac=0.5, t_vpu=2, t_mxu=2, br=4, panel_g=8,
                      pipeline_depth=2, macro_m=4)
    plan = plan_from_record(rec, 48)
    assert plan.pipeline_depth == 2 and plan.macro_m == 4
    # knob-less records (a near-hit from an old neighbour) default to serial
    legacy = {"plan": {"r_frac": 0.5, "t_vpu": 2, "t_mxu": 2, "br": 4}}
    p0 = plan_from_record(legacy, 48)
    assert p0.pipeline_depth == 1 and p0.macro_m == 1

    stale = tmp_path / "stale"
    stale.mkdir()
    (stale / "plans.json").write_text(json.dumps(
        {"version": 3, "entries": {"k": {"version": 3}}}))
    cache = PlanCache(path=str(stale))
    assert len(cache) == 0 and cache.lookup("k") is None


def test_search_space_has_pipeline_axes(rng):
    from repro.tune.search import enumerate_plans
    a = _sparse(rng, 24, 20, 0.3, jnp.float32)
    plans = enumerate_plans(csr_from_dense(a), total_workers=4)
    assert {p.pipeline_depth for p in plans} == {1, 2}
    assert {p.macro_m for p in plans} == {1, 4}


def test_obs_gauges_scratch_and_overlap(rng):
    """Dispatch notes surface scratch bytes + prefetch overlap as gauges."""
    from repro.obs import Obs
    a = _sparse(rng, 24, 20, 0.3, jnp.float32)
    fmt = _fmt(csr_from_dense(a), 4, 2, 2)
    obs = Obs(source="pipeline-test")
    with obs.attach_engine():
        loops_spmm(fmt, jnp.ones((20, 16), jnp.float32),
                   backend="interpret")
    recs = obs.records()
    sb = [r for r in recs if r.get("metric") == "kernel.scratch_bytes"]
    ov = [r for r in recs if r.get("metric") == "engine.prefetch_overlap"]
    assert sb and all(r["value"] > 0 for r in sb)
    assert ov and any(r["value"] > 0 for r in ov)   # depth 2 => overlap
    # serial execution reports zero overlap
    obs2 = Obs(source="pipeline-test-serial")
    fmt1 = _fmt(csr_from_dense(a), 4, 1, 1)
    with obs2.attach_engine():
        loops_spmm(fmt1, jnp.ones((20, 16), jnp.float32),
                   backend="interpret")
    ov1 = [r for r in obs2.records()
           if r.get("metric") == "engine.prefetch_overlap"]
    assert ov1 and all(r["value"] == 0.0 for r in ov1)


def test_packed_halfprec_scratch_and_accumulate(rng):
    """bf16 B panels stay packed (b.dtype scratch) with fp32 accumulation:
    the bf16 result must match the fp32-upcast oracle to bf16 tolerance,
    and the scratch note must reflect the packed (2-byte) element size."""
    from repro.kernels.engine import _panel_note_fields
    a = _sparse(rng, M, K, 0.3, jnp.bfloat16)
    b16 = jnp.asarray(rng.standard_normal((K, N)), jnp.bfloat16)
    fmt = _fmt(csr_from_dense(a), 4, 2, 1)
    got = loops_spmm(fmt, b16, backend="interpret")
    want = np.asarray(a, np.float32) @ np.asarray(b16, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=3e-2, atol=3e-2)
    packed = _panel_note_fields(part="csr", depth=2, npanels=8, nb=1, n=N,
                                bn=None, g=4, br=1,
                                b_dtype=jnp.bfloat16,
                                value_dtype=jnp.bfloat16)
    wide = _panel_note_fields(part="csr", depth=2, npanels=8, nb=1, n=N,
                              bn=None, g=4, br=1,
                              b_dtype=jnp.float32,
                              value_dtype=jnp.float32)
    assert packed["scratch_bytes"] < wide["scratch_bytes"]
