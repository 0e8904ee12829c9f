"""Panel kernels at every width: results against the oracles, grid-step
accounting, packed half precision, plan plumbing, and the default_bn
regression.

The contracts under test (docs/architecture.md §"Packed half precision"):

  * ``panel_g`` is the one panel width: the fused path, each part alone,
    the batched grid, row-boundary tails and the custom VJP all agree with
    the jnp oracle at every width;
  * grid steps = ``panels_at_g × col_blocks`` for the CSR part plus the
    BCSR part's window steps, and ``perf.replay.predict_part_steps``
    replicates the conversion exactly;
  * ``default_bn`` picks the largest lane-aligned divisor ≤ 512 (the
    ``N=600`` ValueError regression);
  * plans round-trip ``panel_g`` through the v5 tuner cache, the tuner's
    width axis is ``{1, 4, 8, 16, 32}``, and dispatch notes carry
    ``scratch_bytes`` into obs gauges.
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


def _fmt(csr, g, r_frac=0.5, br=4):
    r_b = min(max(int(r_frac * csr.nrows) // br * br, 0), csr.nrows)
    return loops_from_csr(csr, r_b, br, panel_g=g)


def _oracle(fmt, b):
    return np.asarray(loops_spmm(fmt, b, backend="jnp"), np.float32)


# -- forward vs oracle: dtypes x G x N ---------------------------------------

@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", [1, 4, 8, 32])
@pytest.mark.parametrize("n", [16, 40])
def test_fused_path_matches_oracle(rng, dtype, tol, g, n):
    """The fused single-pass engine path (input_output_aliases carry) at
    every panel width must agree with the dense oracle."""
    a = _sparse(rng, M, K, 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((K, n)), dtype)
    fmt = _fmt(csr_from_dense(a), g)
    got = loops_spmm(fmt, b, backend="interpret")
    want = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=10 * tol, atol=10 * tol)


def test_fp64_fused_path_matches_oracle(rng):
    jax.config.update("jax_enable_x64", True)
    try:
        a = _sparse(rng, M, K, 0.3, jnp.float64)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float64)
        for g in (1, 8, 32):
            fmt = _fmt(csr_from_dense(a), g)
            got = loops_spmm(fmt, b, backend="interpret")
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(a) @ np.asarray(b),
                                       rtol=1e-12)
    finally:
        jax.config.update("jax_enable_x64", False)


# -- each part alone, the batched grid, tails and gradients vs oracle -------

@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("r_frac", [0.0, 1.0])
def test_each_part_alone_matches_oracle(rng, dtype, tol, g, r_frac):
    """A pure-BCSR (``r_frac`` 0) or pure-CSR (``r_frac`` 1) format runs
    one kernel through the parts path and must agree with the jnp oracle
    on the same format."""
    a = _sparse(rng, M, K, 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    fmt = _fmt(csr_from_dense(a), g, r_frac=r_frac)
    got = loops_spmm(fmt, b, backend="interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32), _oracle(fmt, b),
                               rtol=10 * tol, atol=10 * tol)


def test_batched_matches_oracle(rng):
    """The batched (rank-3) grid agrees with the jnp oracle slice by
    slice."""
    a = _sparse(rng, M, K, 0.3, jnp.float32)
    fmt = _fmt(csr_from_dense(a), 4)
    b3 = jnp.asarray(rng.standard_normal((4, K, N)).astype(np.float32))
    got = loops_spmm(fmt, b3, backend="interpret")
    assert got.shape == (4, M, N)
    np.testing.assert_allclose(np.asarray(got), _oracle(fmt, b3),
                               rtol=1e-5, atol=1e-5)


def test_row_boundary_tails_match_oracle(rng):
    """Row-boundary tails: a conversion whose last CSR panel and last BCSR
    block-row are both partial agrees with the oracle."""
    a = _sparse(rng, 23, 19, 0.4, jnp.float32)
    csr = csr_from_dense(a)
    b = jnp.asarray(rng.standard_normal((19, 8)).astype(np.float32))
    for r_b in (4, 20):     # tails in both parts
        fmt = loops_from_csr(csr, r_b, 8, panel_g=4)
        np.testing.assert_allclose(
            np.asarray(loops_spmm(fmt, b, backend="interpret")),
            _oracle(fmt, b), rtol=1e-5, atol=1e-5)


def test_gradients_match_reference(rng):
    """The custom VJP (Aᵀ through the panel kernels) agrees with
    ``jax.grad`` of the jnp reference, which differentiates natively."""
    a = _sparse(rng, M, K, 0.3, jnp.float32)
    fmt = _fmt(csr_from_dense(a), 4)
    b = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))

    def grad(backend):
        return jax.grad(lambda bb: jnp.sum(
            loops_spmm(fmt, bb, backend=backend) ** 2))(b)

    np.testing.assert_allclose(np.asarray(grad("interpret")),
                               np.asarray(grad("jnp")), rtol=1e-5,
                               atol=1e-5)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2**31 - 1),
           g=st.sampled_from([1, 4, 8, 16, 32]),
           br=st.sampled_from([2, 4, 8]),
           density=st.floats(0.05, 0.6))
    def test_knobs_never_change_results_property(seed, g, br, density):
        """Property: for ANY seeded matrix, the panel width and tile
        height only reshape the schedule — the result matches the G=1
        execution to float32 tolerance."""
        rng = np.random.default_rng(seed)
        a = _sparse(rng, 12, 10, density, jnp.float32)
        csr = csr_from_dense(a)
        b = jnp.asarray(rng.standard_normal((10, 8)).astype(np.float32))
        base = loops_spmm(_fmt(csr, 1, br=br), b, backend="interpret")
        knobbed = loops_spmm(_fmt(csr, g, br=br), b, backend="interpret")
        np.testing.assert_allclose(np.asarray(base), np.asarray(knobbed),
                                   rtol=1e-5, atol=1e-5)
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
    got = loops_spmm(_fmt(csr, 16), b, backend="interpret")
    want = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


# -- grid-step accounting ---------------------------------------------------

def test_grid_steps_at_panel_width(rng):
    """Steps = panels_at_g x col_blocks for the CSR part plus the BCSR
    part's window steps; a wider panel shrinks the CSR grid."""
    a = _sparse(rng, 24, 20, 0.4, jnp.float32)
    csr = csr_from_dense(a)
    narrow, wide = _fmt(csr, 4), _fmt(csr, 16)
    for fmt in (narrow, wide):
        assert loops_grid_steps(fmt, 16) == (
            fmt.csr_panels.npanels + fmt.bcsr_panels.steps[0].shape[0])
    assert wide.csr_panels.npanels < narrow.csr_panels.npanels
    assert loops_grid_steps(wide, 16) < loops_grid_steps(narrow, 16)


@pytest.mark.parametrize("g", [1, 4, 8, 32])
@pytest.mark.parametrize("n_cols", [16, 600])
def test_predict_part_steps_matches_conversion(rng, g, n_cols):
    """perf.replay's structural predictor must replicate the conversion's
    grid-step count exactly at every panel width."""
    a = _sparse(rng, 32, 24, 0.25, jnp.float32)
    csr = csr_from_dense(a)
    for r_frac in (0.0, 0.5, 1.0):
        r_b = min(max(int(r_frac * 32) // 4 * 4, 0), 32)
        plan = SpmmPlan(r_boundary=r_b, t_vpu=2, t_mxu=2, br=4, panel_g=g)
        fmt = loops_from_csr(csr, r_b, 4, panel_g=g)
        s_csr, s_bcsr = predict_part_steps(csr, plan, n_cols)
        assert s_csr + s_bcsr == loops_grid_steps(fmt, n_cols)


# -- plumbing: plan/convert, tuner cache v5, search space, dispatch notes ---

def test_plan_and_convert_threads_knobs(rng):
    """``br`` and ``panel_g`` reach the plan, the format and its panels."""
    a = _sparse(rng, 24, 20, 0.3, jnp.float32)
    fmt, plan = plan_and_convert(csr_from_dense(a), total_workers=4, br=4,
                                 panel_g=16)
    assert plan.br == 4 and plan.panel_g == 16
    assert fmt.panel_g == 16 and fmt.bcsr_part.br == 4
    assert fmt.csr_panels.g == 16 and fmt.bcsr_panels.g == 16
    assert fmt.bcsr_panels.br == 4


def test_cache_v5_roundtrip_and_v4_miss(tmp_path, rng):
    """Records round-trip the panel width; a v4 cache file misses cleanly
    under CACHE_VERSION 5."""
    import json

    from repro.tune.api import make_record, plan_from_record
    from repro.tune.cache import CACHE_VERSION, PlanCache

    assert CACHE_VERSION == 5
    rec = make_record([1.0], dtype=np.float32, n_cols=32, backend="jnp",
                      r_frac=0.5, t_vpu=2, t_mxu=2, br=4, panel_g=32)
    assert rec["version"] == 5
    plan = plan_from_record(rec, 48)
    assert plan.panel_g == 32 and plan.br == 4 and plan.r_boundary == 24
    # a record without a width (a near-hit from an old neighbour) is G=1
    legacy = {"plan": {"r_frac": 0.5, "t_vpu": 2, "t_mxu": 2, "br": 4}}
    assert plan_from_record(legacy, 48).panel_g == 1

    stale = tmp_path / "stale"
    stale.mkdir()
    (stale / "plans.json").write_text(json.dumps(
        {"version": 4, "entries": {"k": {"version": 4}}}))
    cache = PlanCache(path=str(stale))
    assert len(cache) == 0 and cache.lookup("k") is None


def test_search_space_width_axis(rng):
    """The tuner's width axis covers every panelization the product of the
    old width and macro-step axes reached, each plan once."""
    from repro.tune.search import enumerate_plans
    a = _sparse(rng, 24, 20, 0.3, jnp.float32)
    plans = enumerate_plans(csr_from_dense(a), total_workers=4)
    assert {p.panel_g for p in plans} == {1, 4, 8, 16, 32}
    assert len(set(plans)) == len(plans)


def test_obs_gauges_scratch(rng):
    """Dispatch notes surface each kernel's VMEM scratch as a gauge: the
    CSR kernel's fp32 accumulator row, the BCSR kernel's larger stage."""
    from repro.obs import Obs
    a = _sparse(rng, 24, 20, 0.3, jnp.float32)
    fmt = _fmt(csr_from_dense(a), 4)
    obs = Obs(source="pipeline-test")
    with obs.attach_engine():
        loops_spmm(fmt, jnp.ones((20, 16), jnp.float32),
                   backend="interpret")
    sb = {r["labels"]["part"]: r["value"] for r in obs.records()
          if r.get("metric") == "kernel.scratch_bytes"}
    assert sb["csr"] == 16 * 4
    assert sb["bcsr"] > sb["csr"]


def test_packed_halfprec_scratch_and_accumulate(rng):
    """bf16 B rows stay packed with fp32 accumulation: the bf16 result must
    match the fp32-upcast oracle to bf16 tolerance, and the scratch note
    shows the same fp32 accumulator as an fp32 operand's."""
    from repro.kernels.engine import _panel_note_fields
    a = _sparse(rng, M, K, 0.3, jnp.bfloat16)
    b16 = jnp.asarray(rng.standard_normal((K, N)), jnp.bfloat16)
    fmt = _fmt(csr_from_dense(a), 4)
    got = loops_spmm(fmt, b16, backend="interpret")
    want = np.asarray(a, np.float32) @ np.asarray(b16, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=3e-2, atol=3e-2)
    packed = _panel_note_fields(nb=1, n=N, bn=None,
                                value_dtype=jnp.bfloat16)
    wide = _panel_note_fields(nb=1, n=N, bn=None, value_dtype=jnp.float32)
    assert packed["scratch_bytes"] == wide["scratch_bytes"] == N * 4
