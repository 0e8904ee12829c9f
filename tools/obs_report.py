#!/usr/bin/env python
"""Render a captured obs run into a terminal summary.

Reads either serialisation an :class:`repro.obs.Obs` capture produces —
the versioned JSONL record stream (``*.jsonl``, preferred: it carries the
full metric instruments) or the Chrome/Perfetto trace (``*.trace.json`` /
any ``{"traceEvents": [...]}`` file, from which spans and counter tracks
are reconstructed) — and prints:

  * top ops by total span time (count / total / mean / max per span name),
  * per-``(part, op)`` engine dispatch counters and grid-step totals,
  * every latency histogram with count / p50 / p90 / p99,
  * tuner plan-cache hit rate (``tune.cache.*`` gauges, per watched cache),
  * serving section — the continuous-batching queue's admission/latency
    surface: ``serve.queue_depth`` gauge, request/reject/evict counters,
    and ``serve.request_us`` / ``serve.ttft_us`` p50/p99,
  * throughput gauges (``serve.tokens_per_s``, ``train.steps_per_s``, ...).

  * degradations — every resilience counter the run recorded
    (``engine.fallback``, ``serve.degraded``, ``tune.cache.quarantined``,
    ``tune.search.trial_failed``, ``dist.fallback``, ``validate.repaired``,
    ``inject.fired``, ...; see docs/robustness.md).

Exit codes: 0 on a rendered report, 2 on an empty capture, 1 on an
unreadable/invalid file.  ``--require-dispatch`` additionally exits 3 when
the capture holds no nonzero ``engine.dispatch`` counter — CI uses this to
assert the serve smoke run actually exercised the kernel engine —
and ``--require-serving`` exits 3 when it holds no nonzero
``serve.requests`` counter (the serving-CI analogue: a batching capture
need not touch the sparse engine at all).
``--fail-on-degraded`` exits 4 when ANY degradation counter is nonzero
(the normal CI path asserts a clean run); ``--require-degraded METRIC``
(repeatable) exits 5 unless that degradation metric is nonzero (the chaos
CI asserts its injected faults actually degraded, not crashed).

Run:  python tools/obs_report.py benchmarks/results/obs/serve.jsonl
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from collections import defaultdict
from typing import Dict, List

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.obs.export import load_obs  # noqa: E402

# Resilience counters (docs/robustness.md): any nonzero value here means the
# run degraded somewhere — fell back, retried, repaired, or quarantined.
DEGRADATION_METRICS = (
    "engine.fallback", "serve.degraded", "serve.retries",
    "tune.cache.quarantined", "tune.search.trial_failed",
    "tune.search.degraded", "dist.fallback", "validate.repaired",
    "inject.fired",
)


def records_from_chrome(path: pathlib.Path) -> List[Dict]:
    """Reconstruct obs-style records from a Chrome trace: ``X`` events
    become span records, ``C`` counter tracks become gauge records (the
    JSONL keeps richer data — histograms don't survive the round trip)."""
    with open(path) as f:
        blob = json.load(f)
    events = blob.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a Chrome trace (no traceEvents)")
    source = str(blob.get("otherData", {}).get("source", path.stem))
    recs: List[Dict] = []
    for ev in events:
        if ev.get("ph") == "X":
            recs.append({"kind": "span", "source": source,
                         "name": ev.get("name", "?"),
                         "cat": ev.get("cat", ""),
                         "ts": float(ev.get("ts", 0.0)),
                         "dur": float(ev.get("dur", 0.0)),
                         "tid": int(ev.get("tid", 0)),
                         "depth": int(ev.get("args", {}).get("depth", 0)),
                         "args": ev.get("args", {})})
        elif ev.get("ph") == "C":
            name = str(ev.get("name", "?"))
            labels = {}
            if "{" in name and name.endswith("}"):
                name, _, lab = name.partition("{")
                for pair in lab[:-1].split(","):
                    if "=" in pair:
                        k, _, v = pair.partition("=")
                        labels[k] = v
            recs.append({"kind": "gauge", "source": source, "metric": name,
                         "labels": labels,
                         "value": float(ev.get("args", {})
                                        .get("value", 0.0))})
    return recs


def load_records(path: pathlib.Path) -> List[Dict]:
    if path.is_dir() or path.suffix == ".jsonl":
        return load_obs(path)
    return records_from_chrome(path)


def _label_str(labels: Dict) -> str:
    if not labels:
        return ""
    return "{" + ",".join(f"{k}={v}"
                          for k, v in sorted(labels.items())) + "}"


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:8.2f}s "
    if us >= 1e3:
        return f"{us / 1e3:8.2f}ms"
    return f"{us:8.1f}us"


def report(records: List[Dict], *, top: int = 10,
           out=print) -> Dict[str, int]:
    """Print the report; returns counters the caller gates on
    (``spans``, ``dispatches``)."""
    spans = [r for r in records if r.get("kind") == "span"]
    counters = [r for r in records if r.get("kind") == "counter"]
    gauges = [r for r in records if r.get("kind") == "gauge"]
    hists = [r for r in records if r.get("kind") == "hist"]
    sources = sorted({r.get("source", "?") for r in records})

    out(f"obs report: source={','.join(sources) or '?'}  "
        f"spans={len(spans)}  counters={len(counters)}  "
        f"gauges={len(gauges)}  hists={len(hists)}")

    if spans:
        agg = defaultdict(lambda: [0, 0.0, 0.0])   # count, total, max
        for s in spans:
            a = agg[s["name"]]
            a[0] += 1
            a[1] += float(s["dur"])
            a[2] = max(a[2], float(s["dur"]))
        out(f"\ntop ops by total span time (top {top}):")
        out(f"  {'span':<28} {'count':>6} {'total':>10} {'mean':>10} "
            f"{'max':>10}")
        ranked = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
        for name, (cnt, tot, mx) in ranked:
            out(f"  {name:<28} {cnt:>6} {_fmt_us(tot):>10} "
                f"{_fmt_us(tot / cnt):>10} {_fmt_us(mx):>10}")

    disp = [c for c in counters if c.get("metric") == "engine.dispatch"]
    n_disp = int(sum(c.get("value", 0) for c in disp))
    if disp:
        out("\nengine dispatches (compiled workloads, per (part, op)):")
        steps = {_label_str({k: v for k, v in g.get("labels", {}).items()
                             if k in ("part", "op")}): g.get("value")
                 for g in counters
                 if g.get("metric") == "engine.grid_steps_compiled"}
        for c in sorted(disp, key=lambda c: -c.get("value", 0)):
            lab = c.get("labels", {})
            key = _label_str({k: v for k, v in lab.items()
                              if k in ("part", "op")})
            extra = f"  grid_steps={int(steps[key])}" if key in steps else ""
            out(f"  {c['metric']}{_label_str(lab):<50} "
                f"{int(c.get('value', 0)):>8}{extra}")
        out(f"  total dispatches: {n_disp}")

    # VMEM scratch footprint of each kernel, per (part, op).
    kern = {}
    for g in gauges:
        if g.get("metric", "") == "kernel.scratch_bytes":
            key = _label_str({k: v for k, v in g.get("labels", {}).items()
                              if k in ("part", "op")})
            kern[key] = float(g.get("value", 0.0))
    if kern:
        out("\nkernel scratch (per (part, op)):")
        out(f"  {'labels':<40} {'scratch':>12}")
        for key, sb in sorted(kern.items()):
            out(f"  {key:<40} {int(sb):>10}B")

    if hists:
        out("\nlatency histograms:")
        out(f"  {'metric':<40} {'count':>6} {'p50':>10} {'p90':>10} "
            f"{'p99':>10}")
        for h in hists:
            name = f"{h['metric']}{_label_str(h.get('labels', {}))}"
            out(f"  {name:<40} {int(h.get('count', 0)):>6} "
                f"{_fmt_us(float(h.get('p50', 0))):>10} "
                f"{_fmt_us(float(h.get('p90', 0))):>10} "
                f"{_fmt_us(float(h.get('p99', 0))):>10}")

    cache_rows = defaultdict(dict)
    for g in gauges:
        m = g.get("metric", "")
        if m.startswith("tune.cache."):
            name = g.get("labels", {}).get("cache", "?")
            cache_rows[name][m.rsplit(".", 1)[1]] = g.get("value", 0.0)
    if cache_rows:
        out("\ntuner plan-cache:")
        for name, row in sorted(cache_rows.items()):
            out(f"  cache={name}: hits={int(row.get('hits', 0))} "
                f"near={int(row.get('near_hits', 0))} "
                f"misses={int(row.get('misses', 0))} "
                f"hit_rate={row.get('hit_rate', 0.0):.2f}")

    # Serving: the continuous-batching queue's admission / latency surface
    # (docs/serving.md).  Counters roll up across sources; the queue-depth
    # and in-flight gauges report the last captured value per source.
    serve_ctr = defaultdict(float)
    for c in counters:
        if c.get("metric", "").startswith("serve.") \
                and c.get("metric") not in DEGRADATION_METRICS:
            serve_ctr[c["metric"]] += float(c.get("value", 0))
    serve_gauge = {g["metric"]: float(g.get("value", 0.0)) for g in gauges
                   if g.get("metric") in ("serve.queue_depth",
                                          "serve.in_flight")}
    serve_hist = [h for h in hists
                  if h.get("metric") in ("serve.request_us",
                                         "serve.ttft_us")]
    if serve_ctr or serve_gauge or serve_hist:
        out("\nserving (continuous-batching queue):")
        for m in ("serve.requests", "serve.rejected", "serve.evicted",
                  "serve.prefill_calls", "serve.decode_calls",
                  "serve.tokens_generated"):
            if m in serve_ctr:
                out(f"  {m:<28} {int(serve_ctr[m]):>8}")
        for m, v in sorted(serve_gauge.items()):
            out(f"  {m:<28} {int(v):>8}")
        for h in serve_hist:
            out(f"  {h['metric']:<28} count={int(h.get('count', 0)):>5} "
                f"p50={_fmt_us(float(h.get('p50', 0))).strip()} "
                f"p99={_fmt_us(float(h.get('p99', 0))).strip()}")

    thr = [g for g in gauges
           if g.get("metric", "").endswith(("_per_s", "tokens_per_s"))]
    if thr:
        out("\nthroughput:")
        for g in thr:
            out(f"  {g['metric']}{_label_str(g.get('labels', {}))} = "
                f"{float(g.get('value', 0.0)):.2f}")

    # Degradations: per-metric totals across counters, plus the
    # tune.cache.quarantined gauge (counter and gauge describe the same
    # events — take the max per metric, never the sum, to avoid double
    # counting a quarantine that landed on both).
    degr_rows = defaultdict(float)
    degraded: Dict[str, float] = defaultdict(float)
    gauge_q = 0.0
    for c in counters:
        m = c.get("metric", "")
        if m in DEGRADATION_METRICS:
            degr_rows[f"{m}{_label_str(c.get('labels', {}))}"] += \
                float(c.get("value", 0))
            degraded[m] += float(c.get("value", 0))
    for g in gauges:
        if g.get("metric") == "tune.cache.quarantined_files":
            gauge_q += float(g.get("value", 0.0))
    if gauge_q > degraded.get("tune.cache.quarantined", 0.0):
        degraded["tune.cache.quarantined"] = gauge_q
        degr_rows["tune.cache.quarantined (gauge)"] = gauge_q
    if degr_rows:
        out("\ndegradations (fallbacks / retries / repairs / quarantines):")
        for name, v in sorted(degr_rows.items()):
            out(f"  {name:<60} {int(v):>6}")
    n_degraded = sum(v for v in degraded.values() if v > 0)

    return {"spans": len(spans), "dispatches": n_disp,
            "served": int(serve_ctr.get("serve.requests", 0)),
            "degraded": dict(degraded), "n_degraded": int(n_degraded)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", type=pathlib.Path,
                    help="obs .jsonl (or a directory of them), or a "
                         "Chrome .trace.json")
    ap.add_argument("--top", type=int, default=10,
                    help="rows in the top-ops table")
    ap.add_argument("--require-dispatch", action="store_true",
                    help="exit 3 unless a nonzero engine.dispatch counter "
                         "is present (CI smoke gate)")
    ap.add_argument("--require-serving", action="store_true",
                    help="exit 3 unless a nonzero serve.requests counter is "
                         "present (serving-CI smoke gate; serving captures "
                         "need not touch the sparse engine, so this is "
                         "their analogue of --require-dispatch)")
    ap.add_argument("--fail-on-degraded", action="store_true",
                    help="exit 4 if ANY degradation counter is nonzero "
                         "(normal-path CI gate)")
    ap.add_argument("--require-degraded", action="append", default=[],
                    metavar="METRIC",
                    help="exit 5 unless this degradation metric is nonzero "
                         "(repeatable; chaos-CI gate)")
    args = ap.parse_args(argv)

    try:
        records = load_records(args.path)
    except (OSError, ValueError) as e:
        print(f"obs_report: {e}", file=sys.stderr)
        return 1
    if not records:
        print(f"obs_report: {args.path}: empty capture", file=sys.stderr)
        return 2
    stats = report(records, top=args.top)
    if args.require_dispatch and stats["dispatches"] <= 0:
        print("obs_report: no nonzero engine.dispatch counters "
              "(--require-dispatch)", file=sys.stderr)
        return 3
    if args.require_serving and stats["served"] <= 0:
        print("obs_report: no nonzero serve.requests counter "
              "(--require-serving)", file=sys.stderr)
        return 3
    if args.fail_on_degraded and stats["n_degraded"] > 0:
        print(f"obs_report: degradations recorded "
              f"({stats['degraded']}) (--fail-on-degraded)",
              file=sys.stderr)
        return 4
    for metric in args.require_degraded:
        if stats["degraded"].get(metric, 0) <= 0:
            print(f"obs_report: degradation metric {metric!r} is zero "
                  f"(--require-degraded)", file=sys.stderr)
            return 5
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
