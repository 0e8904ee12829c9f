"""The documentation layer is part of tier-1: dead relative links or
references to renamed/removed symbols in README.md / docs/*.md fail the
suite, not just the CI docs job (``tools/check_docs.py`` is the single
implementation; CI invokes it standalone)."""
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_docs_links_and_symbol_refs_resolve():
    res = subprocess.run([sys.executable, str(ROOT / "tools/check_docs.py")],
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr


def test_docs_cover_the_training_surface():
    """training.md and api.md exist and mention the load-bearing entry
    points (a rename must update the docs, not silently orphan them)."""
    training = (ROOT / "docs" / "training.md").read_text()
    api = (ROOT / "docs" / "api.md").read_text()
    for needle in ("loops_spmm_values", "transposed", "spmm_sdd",
                   "loops_cotangent_psum"):
        assert needle in training, f"docs/training.md lost '{needle}'"
    for needle in ("loops_spmm", "loops_sdd", "CACHE_VERSION", "panel_g",
                   "grad?"):
        assert needle in api, f"docs/api.md lost '{needle}'"


def test_docs_cover_the_pipeline_surface():
    """The panel width and the BCSR kernel's DMA double buffer are
    documented: api.md states the ``panel_g`` contract (+ v5 cache entry),
    architecture.md has the packed-half-precision subsection and the
    window-step gather, and the README maps both to the paper's SME
    techniques."""
    api = (ROOT / "docs" / "api.md").read_text()
    arch = (ROOT / "docs" / "architecture.md").read_text()
    readme = (ROOT / "README.md").read_text()
    for needle in ("The `panel_g` contract", "default_bn", "currently **5**",
                   "BENCH_010"):
        assert needle in api, f"docs/api.md lost '{needle}'"
    for needle in ("Packed half precision", "BCSR window steps",
                   "two-slot", "scratch_bytes", "BENCH_010"):
        assert needle in arch, f"docs/architecture.md lost '{needle}'"
    for needle in ("double-buffered DMA gather", "kernels/bcsr_spmm.py",
                   "`panel_g`", "BENCH_010"):
        assert needle in readme, f"README.md lost '{needle}'"


def test_docs_cover_the_observability_surface():
    """observability.md and architecture.md §8 mention the load-bearing
    obs entry points and the jit-safety contract."""
    obs_doc = (ROOT / "docs" / "observability.md").read_text()
    arch = (ROOT / "docs" / "architecture.md").read_text()
    for needle in ("observe_in_jit", "attach_engine", "watch_cache",
                   "obs_report.py", "OBS_SCHEMA_VERSION", "Span.fence",
                   "spans_dropped_traced"):
        assert needle in obs_doc, f"docs/observability.md lost '{needle}'"
    assert "## 8. Runtime observability" in arch
    for needle in ("observe_in_jit", "tune.cache.", "obs_file"):
        assert needle in arch, f"architecture.md §8 lost '{needle}'"


def test_docs_cover_the_robustness_surface():
    """robustness.md and architecture.md §9 mention the load-bearing
    resilience entry points (taxonomy, chain order, site names, gates)."""
    rob = (ROOT / "docs" / "robustness.md").read_text()
    arch = (ROOT / "docs" / "architecture.md").read_text()
    readme = (ROOT / "README.md").read_text()
    for needle in ("SparseInputError", "DEFECT_KINDS", "run_chain",
                   "REPRO_FAULT_PLAN", "REPRO_NO_FALLBACK", "fault_point",
                   "pallas → interpret → jnp", "plans.json.quarantined",
                   "trial_timeout_s", "--fail-on-degraded",
                   "--require-degraded", "retry_with_backoff",
                   "on_miss"):
        assert needle in rob, f"docs/robustness.md lost '{needle}'"
    assert "## 9. Resilience" in arch
    for needle in ("fault_point", "engine.fallback", "REPRO_NO_FALLBACK"):
        assert needle in arch, f"architecture.md §9 lost '{needle}'"
    assert "docs/robustness.md" in readme


def test_docs_cover_the_serving_surface():
    """serving.md and architecture.md §10 mention the load-bearing serving
    entry points (lifecycle, knobs, clocks, warm pools, benchmark)."""
    serving = (ROOT / "docs" / "serving.md").read_text()
    arch = (ROOT / "docs" / "architecture.md").read_text()
    readme = (ROOT / "README.md").read_text()
    for needle in ("shape_key", "max_queue_depth", "max_in_flight",
                   "max_wait_s", "serve.rejected", "scheduler clock",
                   "wall clock", "padded_batch", "ExecutorPool", "prewarm",
                   "sample_token", "serve_traffic", "REPRO_TEST_SEED",
                   "prefill-first", "serve.ttft_us"):
        assert needle in serving, f"docs/serving.md lost '{needle}'"
    assert "## 10. Serving" in arch
    for needle in ("ServeQueue", "padded_batch", "prewarm",
                   "virtual clock"):
        assert needle in arch, f"architecture.md §10 lost '{needle}'"
    assert "docs/serving.md" in readme
