"""Per-segment performance attribution report (obs/perf.py consumer).

Renders the cost / achieved / bound / bottleneck table that closes the
"~250x between roofline and e2e, but WHERE?" question from the ROADMAP:
one row per fused segment with XLA's own cost numbers, the measured wall
per batch, the roofline bound, their ratio, the dominant bottleneck label,
and the exemplar trace ids that link a row back to concrete Perfetto
timelines.

Three sources:

  python tools/perf_report.py --url http://worker:8899     # live server
  python tools/perf_report.py --trace spans.jsonl          # JSONL dump
  python tools/perf_report.py --demo                       # image chain

``--url`` reads ``/_mmlspark/stats`` (fusion.roofline + segment_costs +
latency_histogram exemplars + slo + tuner). ``--trace`` aggregates
``segment:*`` spans from a ``Tracer.export_jsonl`` dump (cost attrs ride on
the spans). ``--demo`` builds the image chain the flagship bench measures
(ImageTransformer -> ImageFeaturizer), runs it fused on this host WITH a
cost-model tuner pass, and prints its table — the zero-setup smoke path.
``--json`` emits the rows as one JSON object instead of the table.

When the server (or demo) carries an auto-tuner (core/tune.py), a second
section renders the chosen-vs-default knobs and the model's
predicted-vs-measured error per (segment, bucket) — the honesty check the
ISSUE's acceptance criteria ask for.

When the server runs the model lifecycle plane (serving/lifecycle), a
per-version section renders from the ``lifecycle`` stats key: state,
traffic share, request/shadow counters, divergence rate, and worst SLO
burn for every registered version, plus the canary controller's rollout
counters and the online trainer's progress.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

# runnable as `python tools/perf_report.py` on an uninstalled checkout
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


COLUMNS = (("segment", "segment"), ("batches", "n_batches"),
           ("rows", "rows"), ("ms/batch", "measured_ms_per_batch"),
           ("bound ms", "bound_ms_per_batch"), ("roofline", "roofline_ratio"),
           ("bottleneck", "bottleneck"), ("disp%", "dispatch_share"),
           ("spec", "partition_spec"),
           ("variant", "variant"), ("stitched", "stitched"),
           ("layout", "layout"),
           ("coll ms", "collective_ms_per_batch"),
           ("flops/batch", "flops_per_batch"),
           ("bytes/batch", "bytes_per_batch"),
           ("nnz bytes", "nnz_bytes_per_batch"),
           ("exemplars", "exemplars"))


def _fmt(v: Any) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e6 or abs(v) < 1e-3:
            return f"{v:.3g}"
        return f"{v:.4g}"
    if isinstance(v, (list, tuple)):
        return ",".join(str(x) for x in v) or "-"
    return str(v)


def render_table(rows: List[Dict[str, Any]]) -> str:
    """Aligned per-segment attribution table."""
    if not rows:
        return "(no fused segments with recorded batches)"
    cells = [[h for h, _ in COLUMNS]]
    for r in rows:
        cells.append([_fmt(r.get(k)) for _, k in COLUMNS])
    widths = [max(len(row[i]) for row in cells) for i in range(len(COLUMNS))]
    lines = []
    for j, row in enumerate(cells):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     .rstrip())
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def rows_from_fusion(fusion: Dict[str, Any],
                     exemplars: Optional[Dict[str, Any]] = None
                     ) -> List[Dict[str, Any]]:
    """fusion_stats() payload -> table rows (roofline section is the base;
    cost columns fall back to segment_costs when roofline lacks them)."""
    roofline = fusion.get("roofline") or {}
    costs = fusion.get("segment_costs") or {}
    # compiler-search columns: the per-bucket kernel variants in force and
    # the transpiled shims stitched through (both absent — rendered "-" —
    # until the tuner moves those knobs)
    variants = (fusion.get("tuning") or {}).get("kernel_variants") or {}
    stitched = fusion.get("stitched") or {}
    ex_ids = sorted({v.get("trace_id") for v in (exemplars or {}).values()
                     if v.get("trace_id")})
    rows = []
    for label in sorted(set(roofline) | set(costs) | set(stitched)):
        rec = dict(roofline.get(label) or {})
        rec["segment"] = label
        if variants.get(label):
            rec["variant"] = ";".join(
                f"{b}={v}" for b, v in sorted(variants[label].items()))
        if stitched.get(label):
            rec["stitched"] = ",".join(stitched[label])
        # the Python submit cost mega-dispatch amortizes, as its own column
        share = (rec.get("stage_share") or {}).get("dispatch")
        if share is not None:
            rec["dispatch_share"] = share
        if rec.get("spec"):
            rec["partition_spec"] = (
                f"{rec['spec']}x{rec['shards']}" if rec.get("shards")
                else str(rec["spec"]))
        if "flops_per_batch" not in rec and costs.get(label):
            shapes = costs[label]
            for src, dst in (("flops", "flops_per_batch"),
                             ("bytes_accessed", "bytes_per_batch")):
                vals = [v[src] for v in shapes.values() if src in v]
                if vals:
                    rec[dst] = sum(vals) / len(vals)
        rec["exemplars"] = ex_ids
        rows.append(rec)
    return rows


def rows_from_stats(stats: Dict[str, Any]) -> List[Dict[str, Any]]:
    fusion = stats.get("fusion") or {}
    hist = stats.get("latency_histogram") or {}
    return rows_from_fusion(fusion, hist.get("exemplars"))


def render_tuner(tuner: Dict[str, Any]) -> str:
    """Tuner section: chosen-vs-default knobs + predicted-vs-measured
    error per (segment, bucket) — from a Tuner.stats() payload."""
    lines = [
        f"Tuner: calibrated={tuner.get('calibrated')} "
        f"applies={tuner.get('applies')} rollbacks={tuner.get('rollbacks')} "
        f"epochs={tuner.get('epochs')}"]
    knobs = tuner.get("knobs") or {}
    default = tuner.get("default_knobs") or {}
    names = sorted(set(knobs) | set(default) |
                   {"buckets", "window_seed_ms", "inflight", "replicas"})
    cells = [["knob", "default", "chosen"]]
    for name in names:
        if name in ("fuse", "kernel_variants", "stitch", "layout") \
                and not knobs.get(name):
            continue
        chosen = knobs.get(name)
        if name == "buckets":
            chosen = "; ".join(f"{k}={v}" for k, v in
                               sorted((chosen or {}).items())) or \
                "(power-of-two)"
            dflt = "(power-of-two)"
        elif name == "kernel_variants":
            chosen = "; ".join(
                f"{seg}:{b}={v}" for seg, kv in sorted(chosen.items())
                for b, v in sorted(kv.items()))
            dflt = "(built-in)"
        elif name == "stitch":
            chosen = "; ".join(sorted(k for k, v in chosen.items() if v))
            dflt = "(split)"
        elif name == "layout":
            chosen = "; ".join(f"{k}={v}"
                               for k, v in sorted(chosen.items()))
            dflt = "(densify)"
        else:
            dflt = _fmt(default.get(name, "(static)")) \
                if name in default else "(static)"
            chosen = _fmt(chosen)
        cells.append([name, str(dflt), str(chosen)])
    widths = [max(len(r[i]) for r in cells) for i in range(3)]
    for j, row in enumerate(cells):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     .rstrip())
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    pvm = tuner.get("predicted_vs_measured") or {}
    if pvm:
        lines.append("")
        cells = [["segment", "bucket", "analytic ms", "measured ms",
                  "err ratio", "batches"]]
        for seg, buckets in sorted(pvm.items()):
            for bucket, rec in sorted(buckets.items(),
                                      key=lambda kv: int(kv[0])):
                cells.append([seg, bucket, _fmt(rec.get("analytic_ms")),
                              _fmt(rec.get("measured_ms")),
                              _fmt(rec.get("error_ratio")),
                              _fmt(rec.get("batches"))])
        widths = [max(len(r[i]) for r in cells) for i in range(len(cells[0]))]
        for j, row in enumerate(cells):
            lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                         .rstrip())
            if j == 0:
                lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render_pipeline(pipe: Dict[str, Any]) -> str:
    """Pipeline section (``fusion_stats()["pipeline"]``): stream shape +
    GPipe bubble ratio, then one row per stage — member segments, its
    sub-mesh size, occupancy (busy / stream wall), and the inter-stage
    d2d transfer it paid. Callers gate on the key itself: no pipe plan
    ran -> no section (and with --json, no ``pipeline`` key at all), so
    an unpipelined report is byte-identical to one from a build that
    never heard of pipelines."""
    lines = [
        f"Pipeline: depth={pipe.get('depth')} "
        f"micro_batches={pipe.get('micro_batches')} "
        f"bubble_ratio={_fmt(pipe.get('bubble_ratio'))} "
        f"handoff={_fmt(pipe.get('handoff_ms'))}ms/"
        f"{pipe.get('handoff_bytes')}B "
        f"serial_fallbacks={pipe.get('serial_fallback_partitions')} "
        f"replans={pipe.get('replans')}"]
    cells = [["stage", "segments", "devices", "occupancy", "handoff ms",
              "handoff B", "requeues"]]
    for st in pipe.get("stages") or []:
        devs = st.get("devices") or []
        cells.append([
            str(st.get("index")), "|".join(st.get("segments") or []),
            str(len(devs)), _fmt(st.get("busy_ratio")),
            _fmt(st.get("handoff_ms")), _fmt(st.get("handoff_bytes")),
            _fmt(st.get("requeues"))])
    widths = [max(len(r[i]) for r in cells) for i in range(len(cells[0]))]
    for j, row in enumerate(cells):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     .rstrip())
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render_fleet(fleet: Optional[Dict[str, Any]],
                 cache: Optional[Dict[str, Any]]) -> str:
    """Fleet section: planner recommendation vs live config (from the
    controller's /_mmlspark/capacity summary) plus the two-tier compile
    cache's cold-start story — persistent hit rate and the compile
    seconds the warm path eliminated (serving/fleet/)."""
    lines: List[str] = []
    if fleet:
        dec = fleet.get("decisions") or {}
        lines.append(
            f"Fleet: state={fleet.get('state')} "
            f"forecast="
            f"{_fmt((fleet.get('forecast') or {}).get('forecast_rps'))}rps "
            + " ".join(f"{k}={v}" for k, v in sorted(dec.items())))
        rec = fleet.get("recommended") or {}
        live = fleet.get("live") or {}
        if rec or live:
            cells = [["knob", "live", "recommended"]]
            for name in ("replicas", "inflight", "bucket", "mega_k"):
                cells.append([name, _fmt(live.get(name)),
                              _fmt(rec.get(name))])
            widths = [max(len(r[i]) for r in cells) for i in range(3)]
            for j, row in enumerate(cells):
                lines.append("  ".join(c.ljust(w)
                                       for c, w in zip(row, widths))
                             .rstrip())
                if j == 0:
                    lines.append("  ".join("-" * w for w in widths))
        if rec:
            lines.append(
                f"plan: meets_slo={rec.get('meets_slo')} "
                f"predicted={_fmt(rec.get('predicted_latency_ms'))}ms "
                f"utilization={_fmt(rec.get('utilization'))} "
                f"({rec.get('reason')})")
    if cache:
        tier = cache.get("persistent")
        lines.append(
            f"compile cache [memory]: hits={cache.get('hits')} "
            f"misses={cache.get('misses')} "
            f"compile_s={_fmt(cache.get('compile_time_s'))}")
        if tier:
            lines.append(
                f"compile cache [persistent]: entries={tier.get('entries')} "
                f"hit_rate={_fmt(tier.get('hit_rate'))} "
                f"stores={tier.get('stores')} "
                f"load_errors={tier.get('load_errors')}")
            if cache.get("misses") == 0 and cache.get("hits", 0) > 0:
                lines.append(
                    "cold start: AOT-warmed — every served signature was a "
                    "memory hit (zero jit compiles this process)")
    return "\n".join(lines)


def render_lifecycle(lc: Dict[str, Any]) -> str:
    """Lifecycle section: one row per model version (state, traffic share,
    request/shadow counters, divergence, worst SLO burn) plus the canary
    controller's rollout counters — from the server's ``lifecycle`` stats
    key (serving/lifecycle/, docs/lifecycle.md)."""
    reg = lc.get("registry") or {}
    canary = lc.get("canary") or {}
    lines = [
        f"Lifecycle: live={reg.get('live')} "
        f"active={canary.get('active') or '-'} "
        f"rollouts={canary.get('rollouts', 0)} "
        f"promotions={canary.get('promotions', 0)} "
        f"rollbacks={canary.get('rollbacks', 0)}"]
    versions = reg.get("versions") or []
    if versions:
        cells = [["version", "state", "share", "live req", "canary req",
                  "shadow", "div rate", "max burn"]]
        for v in versions:
            reqs = v.get("requests") or {}
            shadow = v.get("shadow") or {}
            burn = v.get("burn") or {}
            cells.append([
                str(v.get("version")), str(v.get("state")),
                _fmt(v.get("traffic_share")),
                _fmt(reqs.get("live", 0)), _fmt(reqs.get("canary", 0)),
                f"{shadow.get('scored', 0)}/{shadow.get('issued', 0)}",
                _fmt(v.get("divergence_rate")),
                _fmt(max(burn.values()) if burn else None)])
        widths = [max(len(r[i]) for r in cells) for i in range(len(cells[0]))]
        for j, row in enumerate(cells):
            lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                         .rstrip())
            if j == 0:
                lines.append("  ".join("-" * w for w in widths))
    online = lc.get("online")
    if online:
        lines.append(
            f"online trainer [{online.get('adapter')}]: "
            f"step={online.get('step')} consumed={online.get('consumed')} "
            f"pending={online.get('pending')} "
            f"published={online.get('published')} "
            f"publish_failed={online.get('publish_failed')}")
    return "\n".join(lines)


def rows_from_trace(path: str) -> List[Dict[str, Any]]:
    """Aggregate ``segment:*`` spans from a JSONL trace dump: mean duration
    per segment, the cost attrs the spans carry, and the trace ids seen
    (every one of which IS an exemplar — it resolves in the same file)."""
    agg: Dict[str, Dict[str, Any]] = {}
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            s = json.loads(line)
            name = s.get("name", "")
            if not name.startswith("segment:"):
                continue
            label = name[len("segment:"):]
            a = agg.setdefault(label, {"n": 0, "dur": 0.0, "tids": set(),
                                       "attrs": {}})
            a["n"] += 1
            a["dur"] += float(s.get("dur_s") or 0.0)
            if s.get("trace_id"):
                a["tids"].add(s["trace_id"])
            for k in ("flops", "bytes_accessed", "peak_memory_bytes"):
                v = (s.get("attrs") or {}).get(k)
                if isinstance(v, (int, float)):
                    a["attrs"][k] = v
    rows = []
    for label, a in sorted(agg.items()):
        rows.append({
            "segment": label, "n_batches": a["n"],
            "measured_ms_per_batch": round(a["dur"] / a["n"] * 1e3, 4)
            if a["n"] else None,
            "flops_per_batch": a["attrs"].get("flops"),
            "bytes_per_batch": a["attrs"].get("bytes_accessed"),
            "exemplars": sorted(a["tids"])[:4]})
    return rows


def demo_rows() -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """Build + fuse the flagship image chain, run it on synthetic images with a
    cost-model tuner pass, and attribute it — the zero-setup path to a
    real table. Returns (segment rows, tuner stats)."""
    import jax
    import numpy as np

    from mmlspark_tpu.core.costmodel import SegmentCostModel
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.core.device_stage import CompileCache
    from mmlspark_tpu.core.fusion import FusedPipelineModel
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import ImageSchema
    from mmlspark_tpu.core.tune import Tuner
    from mmlspark_tpu.image.featurizer import ImageFeaturizer
    from mmlspark_tpu.image.stages import ImageTransformer
    from mmlspark_tpu.models.module import (BatchNorm, Conv2D, Dense,
                                            FunctionModel, GlobalAvgPool,
                                            Sequential, relu)

    size = 24
    mod = Sequential([("conv", Conv2D(8, (3, 3))), ("bn", BatchNorm()),
                      ("act", relu()), ("pool", GlobalAvgPool()),
                      ("head", Dense(4))], name="democnn")
    params, _ = mod.init(jax.random.PRNGKey(0), (size, size, 3))
    backbone = FunctionModel(mod, params, (size, size, 3),
                             layer_names=["head", "pool"], name="democnn")

    rng = np.random.default_rng(0)
    n = 64
    rows = np.empty(n, dtype=object)
    for i in range(n):
        rows[i] = ImageSchema.make(
            rng.integers(0, 256, (32, 32, 3), dtype=np.uint8), f"img{i}")
    df = DataFrame.from_dict({"image": rows}, num_partitions=2)
    pm = PipelineModel([
        ImageTransformer().resize(size, size).flip(1),
        ImageFeaturizer(scaleFactor=1 / 255., batchSize=16)
        .set_model(backbone)])
    model = SegmentCostModel(min_obs=2)
    fused = FusedPipelineModel(pm.stages, cache=CompileCache(),
                               cost_model=model)
    fused.transform(df)       # cold: compiles + records costs
    fused.transform(df)       # warm: the measured pass
    tuner = Tuner(fused=fused, model=model)
    tuner.refit()
    tuner.apply(tuner.propose())
    fused.transform(df)       # tuned pass: measured under applied knobs
    return rows_from_fusion(fused.fusion_stats()), tuner.stats()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="server base URL (reads /_mmlspark/stats)")
    src.add_argument("--trace", help="JSONL span dump (Tracer.export_jsonl)")
    src.add_argument("--demo", action="store_true",
                     help="run the fused image chain locally and report it")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit rows as JSON instead of the table")
    ap.add_argument("--timeout", type=float, default=10.0)
    args = ap.parse_args(argv)

    slo = tuner = fleet = cache = lifecycle = pipeline = None
    if args.url:
        url = args.url.rstrip("/") + "/_mmlspark/stats"
        with urllib.request.urlopen(url, timeout=args.timeout) as resp:
            stats = json.loads(resp.read())
        rows = rows_from_stats(stats)
        slo = stats.get("slo")
        tuner = stats.get("tuner")
        fleet = stats.get("fleet")
        cache = (stats.get("fusion") or {}).get("compile_cache")
        lifecycle = stats.get("lifecycle")
        pipeline = (stats.get("fusion") or {}).get("pipeline")
    elif args.trace:
        rows = rows_from_trace(args.trace)
    else:
        rows, tuner = demo_rows()

    if args.as_json:
        payload = {"segments": rows, "slo": slo, "tuner": tuner,
                   "fleet": fleet, "compile_cache": cache,
                   "lifecycle": lifecycle}
        if pipeline:
            # key only when a pipe plan ran: unpipelined JSON stays
            # byte-identical to the pre-pipeline report
            payload["pipeline"] = pipeline
        print(json.dumps(payload))
        return 0
    print(render_table(rows))
    if pipeline:
        print()
        print(render_pipeline(pipeline))
    if tuner:
        print()
        print(render_tuner(tuner))
    if fleet or (cache or {}).get("persistent"):
        print()
        print(render_fleet(fleet, cache))
    if lifecycle and not lifecycle.get("error"):
        print()
        print(render_lifecycle(lifecycle))
    if slo:
        burns = ", ".join(f"{w}s={rec['burn_rate']}"
                          for w, rec in sorted(
                              slo.get("windows", {}).items(),
                              key=lambda kv: int(kv[0])))
        print(f"\nSLO {slo['name']}: objective {slo['objective_ms']}ms "
              f"@ p{slo['target'] * 100:g}, burn rate {burns}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
