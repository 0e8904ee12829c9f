"""Runtime observability (`repro.obs`): jit-safety, exporters, seams.

The load-bearing claims:

  * **jit-safety** — `observe_in_jit` records once per *execution* (never
    once per trace); a `span()` entered during abstract tracing records
    NOTHING (dropped + counted), so no capture can silently report compile
    time as steady-state latency;
  * **exporter validity** — the Chrome trace round-trips `json.loads`,
    events are properly nested per thread, and the JSONL stream is
    schema-stamped with future-version rejection (the
    `repro.perf.trace` contract);
  * **seams** — the engine dispatch hook feeds per-(part, op) counters and
    chains to an already-installed `TraceRecorder`; a watched `PlanCache`
    exports `tune.cache.*` gauges; `compressed_psum` reports wire bytes;
    `wrap_step` lands per-call latency histograms.
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr_from_dense, loops_spmm, plan_and_convert
from repro.obs import (OBS_SCHEMA_VERSION, Histogram, MetricsRegistry, Obs,
                       SpanSink, current_span, get_active, load_obs,
                       set_active)

ROOT = pathlib.Path(__file__).resolve().parent.parent


def random_sparse(rng, m, k, density=0.3):
    a = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
    return a.astype(np.float32)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_baspo():
    reg = MetricsRegistry()
    reg.counter("c", part="csr").inc()
    reg.counter("c", part="csr").inc(2)
    reg.counter("c", part="bcsr").inc()
    assert reg.find("counter", "c", part="csr").value == 3
    assert reg.find("counter", "c", part="bcsr").value == 1
    assert reg.find("counter", "c", part="nope") is None
    reg.gauge("g").set(7)
    reg.gauge("g").set(9)
    assert reg.find("gauge", "g").value == 9.0


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("m")


def test_histogram_quantiles_single_sample_and_spread():
    h = Histogram("h", {})
    h.observe(42.0)
    s = h.summary()
    # single sample: clamping pins every quantile to the observation
    assert s["p50"] == s["p99"] == s["min"] == s["max"] == 42.0
    h2 = Histogram("h2", {})
    for v in range(1, 1001):
        h2.observe(float(v))
    s2 = h2.summary()
    assert s2["count"] == 1000 and s2["min"] == 1.0 and s2["max"] == 1000.0
    assert s2["p50"] <= s2["p90"] <= s2["p99"] <= s2["max"]
    assert 300.0 < s2["p50"] < 700.0          # interpolated, not a bound


def test_histogram_bucket_validation():
    with pytest.raises(ValueError, match="ascending"):
        Histogram("h", {}, buckets=[1.0, 1.0, 2.0])
    with pytest.raises(ValueError, match="ascending"):
        Histogram("h", {}, buckets=[2.0, 1.0])


def test_histogram_overflow_bucket():
    h = Histogram("h", {}, buckets=[10.0, 20.0])
    h.observe(1e9)
    assert h.counts[-1] == 1
    assert h.percentile(0.5) == 1e9   # clamped to observed max


# ---------------------------------------------------------------------------
# jit-safety: record once per EXECUTION, never per trace
# ---------------------------------------------------------------------------

def test_observe_in_jit_records_once_per_execution():
    reg = MetricsRegistry()

    @jax.jit
    def f(x):
        reg.observe_in_jit("jit.lat_us", x * 2.0)
        return x + 1.0

    for i in range(3):                 # one compilation, three executions
        f(jnp.float32(i)).block_until_ready()
    jax.effects_barrier()
    h = reg.find("hist", "jit.lat_us")
    assert h.count == 3, "must count executions, not compilations"


def test_count_in_jit_records_once_per_execution():
    reg = MetricsRegistry()

    @jax.jit
    def f(x):
        reg.count_in_jit("jit.calls")
        return x * 2.0

    for _ in range(4):
        f(jnp.ones(2)).block_until_ready()
    jax.effects_barrier()
    assert reg.find("counter", "jit.calls").value == 4


def test_span_inside_jit_records_nothing_and_counts_drop():
    obs = Obs(source="t")

    @jax.jit
    def f(x):
        with obs.span("traced.region"):
            return x * 2.0

    f(jnp.ones(2)).block_until_ready()       # compile 1
    f(jnp.ones(2)).block_until_ready()       # cached: no trace, no span
    f(jnp.ones(3)).block_until_ready()       # compile 2 (new shape)
    assert obs.sink.events == [], "no span may be emitted during tracing"
    drops = obs.metrics.find("counter", "obs.spans_dropped_traced",
                             span="traced.region")
    assert drops is not None and drops.value == 2   # once per compilation


def test_trace_state_probe_flags_jit_tracing():
    from repro.obs.spans import _tracing
    seen = []

    @jax.jit
    def f(x):
        seen.append(_tracing())
        return x + 1.0

    assert not _tracing()
    f(jnp.ones(2)).block_until_ready()
    assert seen == [True]
    assert not _tracing()


def test_span_records_on_host():
    obs = Obs(source="t")
    with obs.span("host.region", cat="test", k=1) as sp:
        sp.fence(jnp.ones(4) * 2)
    (ev,) = obs.sink.events
    assert ev["name"] == "host.region" and ev["cat"] == "test"
    assert ev["args"] == {"k": 1} and ev["dur"] >= 0.0


def test_span_nesting_depth_and_order():
    obs = Obs(source="t")
    with obs.span("outer"):
        assert current_span().name == "outer"
        with obs.span("inner"):
            assert current_span().name == "inner"
    assert current_span() is None
    inner, outer = obs.sink.events            # completion order
    assert inner["name"] == "inner" and inner["depth"] == 1
    assert outer["name"] == "outer" and outer["depth"] == 0
    # proper nesting: inner's interval inside outer's
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_span_exception_unwind_records_error():
    obs = Obs(source="t")
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (ev,) = obs.sink.events
    assert ev["args"]["error"] == "RuntimeError"
    assert current_span() is None


def test_spans_are_thread_local():
    obs = Obs(source="t")
    seen = []

    def worker():
        with obs.span("thread.region"):
            seen.append(current_span().name)

    with obs.span("main.region"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert current_span().name == "main.region"
    names = {e["name"]: e for e in obs.sink.events}
    assert seen == ["thread.region"]
    assert names["thread.region"]["depth"] == 0    # own stack, not nested
    assert names["thread.region"]["tid"] != names["main.region"]["tid"]


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def _capture(tmp_path):
    obs = Obs(source="t")
    with obs.span("outer", cat="test"):
        with obs.span("inner", cat="test"):
            pass
    obs.counter("c", part="csr").inc(2)
    obs.gauge("g").set(3.5)
    obs.histogram("h").observe(10.0)
    return obs


def test_jsonl_round_trip(tmp_path):
    obs = _capture(tmp_path)
    jsonl, chrome = obs.save(tmp_path, stem="t")
    assert jsonl.name == "t.jsonl" and chrome.name == "t.trace.json"
    recs = load_obs(jsonl)
    assert recs[0]["kind"] == "meta" and recs[0]["spans"] == 2
    kinds = {r["kind"] for r in recs}
    assert kinds == {"meta", "span", "counter", "gauge", "hist"}
    assert all(r["schema"] == OBS_SCHEMA_VERSION for r in recs)
    assert all(r["source"] == "t" for r in recs)
    hist = next(r for r in recs if r["kind"] == "hist")
    assert hist["count"] == 1 and hist["p50"] == 10.0
    assert sum(hist["counts"]) == 1
    # directory load merges every *.jsonl
    assert len(load_obs(tmp_path)) == len(recs)


def test_jsonl_rejects_future_schema_and_unknown_kind(tmp_path):
    p = tmp_path / "future.jsonl"
    p.write_text(json.dumps({"schema": OBS_SCHEMA_VERSION + 1,
                             "kind": "span"}) + "\n")
    with pytest.raises(ValueError, match="schema"):
        load_obs(p)
    p2 = tmp_path / "weird.jsonl"
    p2.write_text(json.dumps({"schema": OBS_SCHEMA_VERSION,
                              "kind": "wat"}) + "\n")
    with pytest.raises(ValueError, match="kind"):
        load_obs(p2)


def test_chrome_trace_is_valid_and_nested(tmp_path):
    obs = _capture(tmp_path)
    _, chrome_path = obs.save(tmp_path, stem="t")
    blob = json.loads(chrome_path.read_text())    # round-trips json.loads
    evs = blob["traceEvents"]
    assert blob["otherData"]["schema"] == OBS_SCHEMA_VERSION
    assert {e["ph"] for e in evs} == {"M", "X", "C"}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    for e in xs.values():                          # complete-event shape
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0 and e["pid"] == 0
    assert xs["inner"]["ts"] >= xs["outer"]["ts"]
    assert (xs["inner"]["ts"] + xs["inner"]["dur"]
            <= xs["outer"]["ts"] + xs["outer"]["dur"] + 1e-6)
    assert xs["inner"]["args"]["depth"] == 1
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert "c{part=csr}" in counters and "g" in counters
    # histograms are report-rendered, never counter tracks
    assert not any(n.startswith("h") for n in counters)


# ---------------------------------------------------------------------------
# Engine seam
# ---------------------------------------------------------------------------

def test_attach_engine_counts_dispatches(rng):
    csr = csr_from_dense(random_sparse(rng, 64, 32))
    fmt, _ = plan_and_convert(csr, total_workers=4)
    b = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    obs = Obs(source="t")
    with obs.attach_engine():
        loops_spmm(fmt, b, backend="jnp")
    total = sum(inst.value for kind, inst in obs.metrics.instruments()
                if kind == "counter" and inst.name == "engine.dispatch")
    assert total >= 1
    for kind, inst in obs.metrics.instruments():
        if inst.name == "engine.dispatch":
            assert set(inst.labels) == {"part", "op", "backend", "impl"}
    # grid-step accounting rode along
    steps = [inst for kind, inst in obs.metrics.instruments()
             if inst.name == "engine.grid_steps_compiled"]
    assert steps and all(inst.value > 0 for inst in steps)
    assert obs.summary()["engine_dispatches"] == int(total)


def test_attach_engine_chains_to_trace_recorder(rng):
    from repro.perf.trace import TraceRecorder
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    fmt, _ = plan_and_convert(csr, total_workers=2)
    b = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
    rec = TraceRecorder(source="t")
    obs = Obs(source="t")
    with rec.attach_engine():
        with obs.attach_engine():
            loops_spmm(fmt, b, backend="jnp")
    n_obs = sum(inst.value for kind, inst in obs.metrics.instruments()
                if kind == "counter" and inst.name == "engine.dispatch")
    n_rec = sum(1 for r in rec.records if r["kind"] == "dispatch")
    assert n_obs >= 1 and n_rec == n_obs, \
        "chained tracer must forward every dispatch"


def test_attach_engine_restores_previous_tracer():
    from repro.kernels import engine
    before = engine.get_tracer()
    obs = Obs(source="t")
    with obs.attach_engine():
        assert engine.get_tracer() is not before
    assert engine.get_tracer() is before


# ---------------------------------------------------------------------------
# Tuner seam
# ---------------------------------------------------------------------------

def test_watch_cache_exports_hit_rate(tmp_path):
    from repro.tune import PlanCache
    cache = PlanCache(str(tmp_path))
    cache.put("k1", {"plan": 1})
    cache.lookup("k1")
    cache.lookup("k2")
    obs = Obs(source="t")
    obs.watch_cache(cache, name="test")
    recs = obs.records()
    gauges = {(r["metric"], r["labels"]["cache"]): r["value"]
              for r in recs if r["kind"] == "gauge"}
    assert gauges[("tune.cache.hits", "test")] == 1.0
    assert gauges[("tune.cache.misses", "test")] == 1.0
    assert gauges[("tune.cache.hit_rate", "test")] == 0.5


# ---------------------------------------------------------------------------
# Step seam
# ---------------------------------------------------------------------------

def test_wrap_step_records_latency_and_spans():
    obs = Obs(source="t")
    fn = jax.jit(lambda x: x * 2.0)
    wrapped = obs.wrap_step(fn, op="toy")
    for _ in range(3):
        wrapped(jnp.ones(4))
    h = obs.metrics.find("hist", "step.wall_us", op="toy")
    assert h.count == 3
    assert [e["name"] for e in obs.sink.events] == ["step.toy"] * 3
    assert [e["args"]["step"] for e in obs.sink.events] == [0, 1, 2]


# ---------------------------------------------------------------------------
# Collective seam
# ---------------------------------------------------------------------------

def test_compressed_psum_reports_bytes():
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh, shard_map
    from repro.dist.compress import compressed_psum

    mesh = make_mesh((1,), ("d",))
    obs = Obs(source="t")
    prev = set_active(obs)
    try:
        @partial(shard_map, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        def f(xs):
            return compressed_psum(xs[0], "d", precision="int8")[None]

        f(jnp.ones((1, 16), jnp.float32))
    finally:
        set_active(prev)
    g = obs.metrics.find("gauge", "dist.collective_bytes",
                         kind="psum", precision="int8")
    assert g is not None and g.value == 0.0    # D==1: nothing on the wire
    c = obs.metrics.find("counter", "dist.collective_sites",
                         kind="psum", precision="int8")
    assert c is not None and c.value >= 1


def test_active_capture_set_and_restore():
    assert get_active() is None
    obs = Obs(source="t")
    prev = set_active(obs)
    assert prev is None and get_active() is obs
    set_active(prev)
    assert get_active() is None


# ---------------------------------------------------------------------------
# Report CLI
# ---------------------------------------------------------------------------

def test_obs_report_cli_renders_capture(tmp_path, rng):
    csr = csr_from_dense(random_sparse(rng, 32, 16))
    fmt, _ = plan_and_convert(csr, total_workers=2)
    obs = Obs(source="cli-test")
    with obs.attach_engine():
        loops_spmm(fmt, jnp.ones((16, 4), jnp.float32), backend="jnp")
    obs.histogram("serve.decode_token_us").observe(123.0)
    jsonl, chrome = obs.save(tmp_path, stem="cli-test")

    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"), str(jsonl),
         "--require-dispatch"],
        capture_output=True, text=True, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    assert "engine.dispatch" in out.stdout
    assert "serve.decode_token_us" in out.stdout

    # the Chrome serialisation renders through the same CLI
    out2 = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"), str(chrome)],
        capture_output=True, text=True, cwd=ROOT)
    assert out2.returncode == 0, out2.stderr


def test_obs_report_cli_failure_modes(tmp_path):
    obs = Obs(source="empty-ish")          # spans/metrics but no dispatches
    obs.counter("c").inc()
    jsonl, _ = obs.save(tmp_path, stem="nodispatch")
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"), str(jsonl),
         "--require-dispatch"],
        capture_output=True, text=True, cwd=ROOT)
    assert out.returncode == 3

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    out2 = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"), str(empty)],
        capture_output=True, text=True, cwd=ROOT)
    assert out2.returncode == 2
