"""``repro.obs`` — runtime observability for live train/serve runs.

What ``repro.perf.trace`` is to *benchmark capture* (measured SpMM cells,
cost-model fitting, CI gating), this package is to *live runs*: labeled
metrics (counters / gauges / fixed-bucket histograms with p50/p90/p99),
wall-clock spans with thread-local nesting, and exporters producing a
versioned JSONL stream plus a Perfetto-loadable Chrome trace.  See
``docs/observability.md`` for the API tour and
``docs/architecture.md`` §8 for where the layer sits.

Quick start::

    from repro.obs import Obs

    obs = Obs(source="serve")
    with obs.attach_engine():                 # (part, op) dispatch counters
        with obs.span("prefill") as sp:
            cache, logits = prefill_fn(params, batch)
            sp.fence(logits)
        obs.histogram("serve.prefill_us").observe(...)
    jsonl, chrome = obs.save()                # benchmarks/results/obs/
    print(obs.summary())

``tools/obs_report.py`` renders a saved capture as a terminal table.
"""
from .export import (OBS_KINDS, OBS_SCHEMA_VERSION, chrome_trace,
                     default_obs_dir, load_obs, obs_records,
                     write_chrome_trace, write_jsonl)
from .metrics import (DEFAULT_BUCKETS_US, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .runtime import (Obs, count, get_active, note_collective, set_active,
                      span)
from .spans import Span, SpanSink, current_span

__all__ = [
    "Obs", "set_active", "get_active", "span", "count", "note_collective",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_BUCKETS_US",
    "Span", "SpanSink", "current_span",
    "OBS_SCHEMA_VERSION", "OBS_KINDS", "obs_records", "chrome_trace",
    "write_jsonl", "write_chrome_trace", "load_obs", "default_obs_dir",
]
