"""Performance-attribution layer tests (mmlspark_tpu/obs/perf.py + wiring).

Covers:
  - getattr-gated XLA cost harvesting: ``cost_analysis()`` absent / raising
    / list / dict, ``memory_analysis()`` absent — every shape degrades to
    "no record", never to an error (CPU-only, must pass under
    JAX_PLATFORMS=cpu);
  - device memory telemetry: ``memory_stats()`` returning None (CPU) or a
    dict (stubbed TPU) -> absent vs present families, never scrape errors;
  - CompileCache cost capture under the cache lock + the reset()-vs-record
    race (a reset racing a build never mixes epochs in hit/miss/
    compile_time_s);
  - histogram exemplars (OpenMetrics syntax behind the flag, snapshot
    always), per-metric bucket registration (conflicts raise, defaults
    golden byte-for-byte);
  - SLO burn-rate math over multi-window buckets with an injected clock;
  - roofline attribution math + bottleneck labels;
  - TransferRing slot-occupancy gauges;
  - serving integration: a fused pipeline's /_mmlspark/metrics exposes
    mmlspark_segment_cost_* / mmlspark_segment_bottleneck /
    mmlspark_slo_burn_rate, latency buckets carry trace-id exemplars that
    resolve against /_mmlspark/trace and the JSONL export, and the
    RoutingFront now serves /_mmlspark/trace too;
  - tools/perf_report.py table rendering from stats and trace dumps.
"""

import json
import sys
import threading
import urllib.request

import numpy as np
import pytest

import jax

from mmlspark_tpu.obs import perf
from mmlspark_tpu.obs.metrics import (DEFAULT_BUCKETS, MetricsRegistry,
                                      SERVING_LATENCY_BUCKETS)
from mmlspark_tpu.obs.perf import SLOConfig, SLOTracker
from mmlspark_tpu.core.device_stage import CompileCache


def http_get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers.items())


def http_post(url, body, timeout=10):
    req = urllib.request.Request(url, data=body, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers.items())


# -- cost harvesting ---------------------------------------------------------


class _Compiled:
    """Configurable stand-in for a jax compiled executable."""

    def __init__(self, ca=None, ma=None):
        if ca is not None:
            self.cost_analysis = lambda: ca
        if ma is not None:
            self.memory_analysis = lambda: ma


class _Mem:
    temp_size_in_bytes = 100.0
    argument_size_in_bytes = 40.0
    output_size_in_bytes = 10.0


class TestExtractCost:
    def test_absent_hooks(self):
        assert perf.extract_cost(object()) is None

    def test_dict_form_and_memory(self):
        c = _Compiled(ca={"flops": 5}, ma=_Mem())
        out = perf.extract_cost(c)
        assert out["flops"] == 5.0
        assert out["peak_memory_bytes"] == 150.0
        assert out["output_bytes"] == 10.0

    def test_empty_and_none_reports(self):
        assert perf.extract_cost(_Compiled(ca={"weird": 1})) is None

    def test_real_jax_compiled(self):
        # the real thing on this container's backend: either a usable
        # record or None — never an exception
        compiled = jax.jit(lambda x: x * 2.0).lower(
            jax.ShapeDtypeStruct((4,), np.float32)).compile()
        out = perf.extract_cost(compiled)
        if out is not None:
            assert out.get("flops", 0) >= 0


# -- device peaks + memory telemetry ----------------------------------------


class TestDevicePeaks:
    def test_unknown_kind_has_no_numbers(self):
        # this container's device (cpu) is not in the table: no stand-in
        p = perf.device_peaks()
        assert p["peak_source"] == "unknown"
        assert p["flops"] is None and p["bytes_per_s"] is None
        assert perf.device_peaks(data_shards=4)["flops"] is None
        assert perf.peaks_for_kind("Some Future Chip") is None

    def test_table_rows_by_longest_prefix(self):
        assert perf.peaks_for_kind("TPU v5 lite")["flops"] == 197e12
        assert perf.peaks_for_kind("TPU v5")["flops"] == 459e12

    def test_unknown_peaks_yield_no_bound_or_ratio(self):
        rep = perf.attribute_segments(
            {"seg": {"n_batches": 2, "rows": 8, "wall_s": 0.5,
                     "compute_s": 0.4}},
            {"seg": {"x=4:float32": {"flops": 1e9, "bytes_accessed": 1e6}}})
        assert rep["seg"]["peak_source"] == "unknown"
        assert "roofline_ratio" not in rep["seg"]
        assert "bound_ms_per_batch" not in rep["seg"]


class _StubDev:
    def __init__(self, name, stats):
        self._name = name
        self._stats = stats

    def __str__(self):
        return self._name

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


class _StubJax:
    def __init__(self, devices):
        self._devices = devices

    def local_devices(self):
        return self._devices


class TestDeviceMemory:
    def test_cpu_memory_stats_none_yields_no_family(self):
        # the real CPU backend: memory_stats() returns None -> no samples,
        # and registering the collector never breaks the scrape
        reg = MetricsRegistry()
        perf.fold_device_memory(reg)
        text = reg.exposition()
        assert "mmlspark_collector_errors" not in text

    def test_stubbed_device_reports(self, monkeypatch):
        stub = _StubJax([_StubDev("TPU_0", {"bytes_in_use": 123,
                                            "peak_bytes_in_use": 456}),
                         _StubDev("TPU_1", None),
                         _StubDev("TPU_2", RuntimeError("boom"))])
        monkeypatch.setitem(sys.modules, "jax", stub)
        fams = perf.device_memory_families()
        assert len(fams) == 1
        samples = {(s.labels["device"], s.labels["stat"]): s.value
                   for s in fams[0].samples}
        assert samples == {("TPU_0", "bytes_in_use"): 123.0,
                           ("TPU_0", "peak_bytes_in_use"): 456.0}

    def test_no_jax_module_yields_nothing(self, monkeypatch):
        monkeypatch.delitem(sys.modules, "jax")
        assert perf.device_memory_families() == []


# -- CompileCache cost capture + reset race ---------------------------------


class TestCompileCacheCosts:
    def test_cost_recorded_per_label_shape(self):
        cache = CompileCache()
        cache.get(("k1",), lambda: _Compiled(ca={"flops": 7.0}),
                  label="seg", shape="x=8:f32")
        cache.get(("k2",), lambda: _Compiled(ca={"flops": 9.0}),
                  label="seg", shape="x=16:f32")
        costs = cache.costs()
        assert set(costs["seg"]) == {"x=8:f32", "x=16:f32"}
        assert costs["seg"]["x=8:f32"]["flops"] == 7.0
        assert costs["seg"]["x=8:f32"]["compile_s"] >= 0
        mean = cache.segment_cost("seg")
        assert mean["flops"] == 8.0 and mean["shape_buckets"] == 2

    def test_no_label_records_nothing(self):
        cache = CompileCache()
        cache.get(("k",), lambda: object())
        assert cache.costs() == {}
        assert cache.segment_cost("nope") is None

    def test_reset_alias_clears_costs(self):
        cache = CompileCache()
        cache.get(("k",), lambda: _Compiled(ca={"flops": 1.0}),
                  label="s", shape="b")
        cache.reset()
        assert cache.costs() == {}
        assert cache.stats() == {"entries": 0, "capacity": 256, "hits": 0,
                                 "misses": 0, "evictions": 0,
                                 "hit_rate": None, "compile_time_s": 0.0}

    def test_reset_racing_build_never_mixes_epochs(self):
        # a reset() landing while a builder compiles must not book the
        # stale miss/compile-time/cost into the post-reset counters — a
        # scrape right after reset sees a coherent all-zero triple
        cache = CompileCache()
        building = threading.Event()
        release = threading.Event()

        def builder():
            building.set()
            assert release.wait(timeout=10)
            return _Compiled(ca={"flops": 3.0})

        t = threading.Thread(
            target=lambda: cache.get(("k",), builder,
                                     label="s", shape="b"))
        t.start()
        assert building.wait(timeout=10)
        cache.reset()
        release.set()
        t.join(timeout=10)
        s = cache.stats()
        assert (s["hits"], s["misses"], s["compile_time_s"]) == (0, 0, 0.0)
        assert cache.costs() == {}
        # the built executable itself survives: next get() is a pure hit
        cache.get(("k",), lambda: pytest.fail("rebuilt"),
                  label="s", shape="b")
        assert cache.stats()["hits"] == 1


# -- histogram exemplars + bucket registration ------------------------------


class TestExemplarsAndBuckets:
    def test_exemplar_rendered_only_behind_flag(self):
        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_lat_seconds", "h", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar={"trace_id": "abc123"})
        plain = reg.exposition()
        assert "abc123" not in plain
        om = reg.exposition(exemplars=True)
        assert '# {trace_id="abc123"} 0.05' in om
        assert om.endswith("# EOF\n")

    def test_exemplar_pins_to_landed_bucket_and_snapshot(self):
        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_lat_seconds", "h", buckets=(0.1, 1.0))
        h.observe(0.5, exemplar={"trace_id": "t1"})
        h.observe(5.0, exemplar={"trace_id": "tinf"})
        h.observe(0.01)  # no exemplar
        snap = h.snapshot()
        assert snap["count"] == 3
        assert snap["buckets"] == {"0.1": 1, "1": 2, "+Inf": 3}
        assert snap["exemplars"]["1"]["trace_id"] == "t1"
        assert snap["exemplars"]["+Inf"]["trace_id"] == "tinf"
        assert "0.1" not in snap["exemplars"]

    def test_bucket_conflict_raises_same_ok(self):
        reg = MetricsRegistry()
        reg.histogram("mmlspark_b_seconds", buckets=(1.0, 2.0))
        assert reg.histogram("mmlspark_b_seconds",
                             buckets=(2.0, 1.0)) is not None  # order-free
        with pytest.raises(ValueError):
            reg.histogram("mmlspark_b_seconds", buckets=(1.0, 3.0))

    def test_default_buckets_golden_exposition(self):
        # byte-for-byte pin of the DEFAULT_BUCKETS exposition: bucket
        # boundaries became configurable per metric — the defaults must
        # not have moved
        reg = MetricsRegistry()
        reg.histogram("mmlspark_g_seconds").observe(0.3)
        assert reg.exposition() == (
            "# TYPE mmlspark_g_seconds histogram\n"
            'mmlspark_g_seconds_bucket{le="0.001"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.0025"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.005"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.01"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.025"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.05"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.1"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.25"} 0\n'
            'mmlspark_g_seconds_bucket{le="0.5"} 1\n'
            'mmlspark_g_seconds_bucket{le="1"} 1\n'
            'mmlspark_g_seconds_bucket{le="2.5"} 1\n'
            'mmlspark_g_seconds_bucket{le="5"} 1\n'
            'mmlspark_g_seconds_bucket{le="10"} 1\n'
            'mmlspark_g_seconds_bucket{le="+Inf"} 1\n'
            "mmlspark_g_seconds_sum 0.3\n"
            "mmlspark_g_seconds_count 1\n")

    def test_preset_buckets_exist_and_are_sorted(self):
        from mmlspark_tpu.obs.metrics import COMPILE_BUCKETS

        assert DEFAULT_BUCKETS == (0.001, 0.0025, 0.005, 0.01, 0.025,
                                   0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                                   10.0)
        for preset in (SERVING_LATENCY_BUCKETS, COMPILE_BUCKETS):
            assert tuple(sorted(preset)) == preset
            assert len(preset) >= 10


# -- SLO burn rates ---------------------------------------------------------


class TestSLO:
    def test_config_validation(self):
        with pytest.raises(ValueError):
            SLOConfig(target=0.0)
        with pytest.raises(ValueError):
            SLOConfig(objective_ms=-1)
        with pytest.raises(ValueError):
            SLOConfig(windows_s=())

    def test_burn_rate_math(self):
        clock = [1000.0]
        t = SLOTracker(SLOConfig(objective_ms=100.0, target=0.9,
                                 windows_s=(10, 100)),
                       clock=lambda: clock[0])
        for _ in range(8):
            t.record(0.05)          # within objective
        for _ in range(2):
            t.record(0.5)           # breach
        # 20% breaches / 10% budget = burn 2.0 on both windows
        assert t.burn_rates() == {10: 2.0, 100: 2.0}
        clock[0] += 50              # short window ages out, long keeps
        assert t.burn_rates() == {10: 0.0, 100: 2.0}
        s = t.summary()
        assert s["requests_total"] == 10 and s["breaches_total"] == 2
        assert s["windows"]["100"]["burn_rate"] == 2.0

    def test_explicit_breach_flag(self):
        t = SLOTracker(SLOConfig(objective_ms=1e6, windows_s=(60,)),
                       clock=lambda: 0.0)
        t.record(0.001, breach=True)  # fast shed still burns budget
        assert t.breaches_total == 1

    def test_families_scrape(self):
        reg = MetricsRegistry()
        t = SLOTracker(SLOConfig(target=0.99), clock=lambda: 0.0)
        reg.register_collector(t.families)
        t.record(0.01)
        text = reg.exposition()
        assert 'mmlspark_slo_burn_rate{slo="latency",window="60s"}' in text
        assert "mmlspark_collector_errors" not in text

    def test_make_slo_coercions(self):
        assert perf.make_slo(False) is None
        assert isinstance(perf.make_slo(None), SLOTracker)
        assert perf.make_slo({"objective_ms": 5.0}).config.objective_ms == 5.0
        cfg = SLOConfig(objective_ms=7.0)
        assert perf.make_slo(cfg).config is cfg
        with pytest.raises(ValueError):
            perf.make_slo("nope")


# -- roofline attribution ---------------------------------------------------


class TestAttribution:
    PEAKS = {"flops": 1e9, "bytes_per_s": 1e9, "peak_source": "test"}

    def test_bound_ratio_and_bottleneck(self):
        per_seg = {"seg": {"n_batches": 2, "rows": 32, "wall_s": 0.2,
                           "queue_s": 0.01, "h2d_s": 0.12,
                           "compute_s": 0.02, "dispatch_s": 0.001,
                           "readback_s": 0.002}}
        costs = {"seg": {"shape": {"flops": 1e6, "bytes_accessed": 2e6}}}
        out = perf.attribute_segments(per_seg, costs, peaks=self.PEAKS)
        rec = out["seg"]
        assert rec["bottleneck"] == "h2d"
        # bound = max(1e6/1e9, 2e6/1e9) = 2ms; measured = 100ms/batch
        assert rec["bound_ms_per_batch"] == 2.0
        assert rec["measured_ms_per_batch"] == 100.0
        assert rec["roofline_ratio"] == pytest.approx(0.02)

    def test_no_cost_still_attributes_bottleneck(self):
        per_seg = {"seg": {"n_batches": 1, "wall_s": 0.1, "queue_s": 0.09,
                           "h2d_s": 0.001, "compute_s": 0.001,
                           "dispatch_s": 0.0, "readback_s": 0.0}}
        out = perf.attribute_segments(per_seg, {}, peaks=self.PEAKS)
        rec = out["seg"]
        assert rec["bottleneck"] == "queue"
        assert "roofline_ratio" not in rec

    def test_zero_batches_skipped(self):
        assert perf.attribute_segments({"seg": {"n_batches": 0}}, {},
                                       peaks=self.PEAKS) == {}


# -- TransferRing occupancy -------------------------------------------------


class TestRingOccupancy:
    def test_summary_reports_depth_and_occupancy(self):
        from mmlspark_tpu.parallel.ingest import IngestStats, TransferRing

        stats = IngestStats()
        ring = TransferRing(iter(np.ones((6, 4), dtype=np.float32)),
                            depth=3, stats=stats)
        assert list(ring) is not None
        s = stats.summary()
        assert s["ring_depth"] == 3
        assert 1 <= s["ring_occupancy_max"] <= 3
        assert 0 < s["ring_occupancy_mean"] <= 3

    def test_merge_carries_ring_fields(self):
        from mmlspark_tpu.parallel.ingest import BatchTiming, IngestStats

        a, b = IngestStats(), IngestStats()
        b.note_ring(2)
        b.note_occupancy(2)
        b.record(BatchTiming(rows=1))
        a.merge(b)
        assert a.ring_depth == 2
        assert a.summary()["ring_occupancy_max"] == 2

    def test_empty_summary_unchanged(self):
        from mmlspark_tpu.parallel.ingest import IngestStats

        assert IngestStats().summary() == {"n_batches": 0}


# -- fused serving integration ----------------------------------------------


def _toy_mlp(d_in=4):
    from mmlspark_tpu.models.module import (Dense, FunctionModel,
                                            Sequential, relu)

    mod = Sequential([("d1", Dense(8)), ("act", relu()), ("d2", Dense(3))],
                     name="toymlp")
    params, _ = mod.init(jax.random.PRNGKey(1), (d_in,))
    return FunctionModel(mod, params, (d_in,), layer_names=["d2", "d1"],
                         name="toymlp")


@pytest.fixture(scope="module")
def fused_server():
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.models.dnn_model import DNNModel
    from mmlspark_tpu.serving.server import serve_pipeline

    dnn = DNNModel(inputCol="x", outputCol="reply", batchSize=8)
    dnn.set_model(_toy_mlp())
    server = serve_pipeline(PipelineModel([dnn]), input_col="x",
                            reply_col="reply", parse="json", port=0,
                            fused=True, metrics_exemplars=True,
                            max_wait_ms=0.0)
    with server:
        body = json.dumps([0.5, -1.0, 2.0, 0.25]).encode()
        for _ in range(3):
            http_post(server.address, body)
        yield server


class TestFusedServingAttribution:
    def test_metrics_expose_perf_families(self, fused_server):
        base = f"http://{fused_server.host}:{fused_server.port}"
        status, body, headers = http_get(base + "/_mmlspark/metrics")
        text = body.decode()
        assert status == 200
        assert headers["Content-Type"].startswith(
            "application/openmetrics-text")
        # no roofline_ratio family here: this container's device is not in
        # the peaks table, so there is no bound to take a ratio against
        assert "mmlspark_segment_roofline_ratio{" not in text
        for family in ("mmlspark_segment_cost_flops{",
                       "mmlspark_segment_cost_bytes{",
                       "mmlspark_segment_bottleneck{",
                       "mmlspark_slo_burn_rate{",
                       "mmlspark_request_duration_seconds_bucket{",
                       "mmlspark_transfer_ring_depth"):
            assert family in text, family
        assert text.endswith("# EOF\n")

    def test_exemplar_resolves_to_sampled_trace(self, fused_server, tmp_path):
        base = f"http://{fused_server.host}:{fused_server.port}"
        stats = json.loads(http_get(base + "/_mmlspark/stats")[1])
        exemplars = stats["latency_histogram"]["exemplars"]
        assert exemplars, "no latency bucket captured an exemplar"
        ex_tids = {v["trace_id"] for v in exemplars.values()}
        # resolves against the live trace endpoint...
        trace = json.loads(http_get(base + "/_mmlspark/trace")[1])
        live_tids = {s["trace_id"] for s in trace["spans"]}
        assert ex_tids <= live_tids
        # ...and against the JSONL export (the offline path)
        dump = tmp_path / "spans.jsonl"
        fused_server.tracer.export_jsonl(str(dump))
        file_tids = {json.loads(line)["trace_id"]
                     for line in dump.read_text().splitlines()}
        assert ex_tids <= file_tids

    def test_exposed_exemplar_lines_parse(self, fused_server):
        base = f"http://{fused_server.host}:{fused_server.port}"
        text = http_get(base + "/_mmlspark/metrics")[1].decode()
        ex_lines = [ln for ln in text.splitlines() if " # {" in ln]
        assert ex_lines
        for ln in ex_lines:
            assert "mmlspark_request_duration_seconds_bucket{" in ln
            assert 'trace_id="' in ln

    def test_stats_carries_slo_and_roofline(self, fused_server):
        base = f"http://{fused_server.host}:{fused_server.port}"
        stats = json.loads(http_get(base + "/_mmlspark/stats")[1])
        assert stats["slo"]["windows"]["60"]["requests"] >= 3
        roofline = stats["fusion"]["roofline"]
        assert roofline, "no roofline attribution for the fused segment"
        rec = next(iter(roofline.values()))
        assert rec["bottleneck"] in (
            "queue", "h2d", "compute", "dispatch", "host")
        assert stats["fusion"]["segment_costs"]

    def test_segment_spans_carry_cost_attrs(self, fused_server):
        spans = fused_server.tracer.spans()
        seg = [s for s in spans if s["name"].startswith("segment:")]
        assert seg
        # the CPU backend reports cost analysis, so the attrs ride along
        assert any("flops" in (s["attrs"] or {}) for s in seg)


class TestServerKnobs:
    def test_obs_false_strips_perf_layer(self):
        from mmlspark_tpu.serving import ServingServer

        srv = ServingServer(lambda df: df, port=0, obs=False)
        assert srv._slo is None and srv._lat_hist is None

    def test_slo_false_disables_tracker_only(self):
        from mmlspark_tpu.serving import ServingServer

        srv = ServingServer(lambda df: df, port=0, slo=False)
        assert srv._slo is None and srv._lat_hist is not None
        assert "mmlspark_slo_burn_rate" not in srv.registry.exposition()

    def test_exemplars_off_by_default(self):
        from mmlspark_tpu.serving import ServingServer
        from mmlspark_tpu.serving.stages import parse_request

        def echo(df):
            parsed = parse_request(df, "data", parse="json")
            return parsed.with_column(
                "reply", lambda p: [float(np.sum(v)) for v in p["data"]])

        with ServingServer(echo, port=0, max_wait_ms=0.0) as srv:
            http_post(srv.address, json.dumps({"data": [1, 2]}).encode())
            base = f"http://{srv.host}:{srv.port}"
            status, body, headers = http_get(base + "/_mmlspark/metrics")
            assert headers["Content-Type"].startswith("text/plain")
            text = body.decode()
            assert " # {" not in text and "# EOF" not in text
            # ...but the stats surface always carries them
            stats = json.loads(http_get(base + "/_mmlspark/stats")[1])
            assert "latency_histogram" in stats


class TestFrontTraceEndpoint:
    def test_front_serves_trace_like_worker(self):
        from mmlspark_tpu.serving import (RoutingFront, ServingServer,
                                          register_worker)
        from mmlspark_tpu.serving.stages import parse_request

        def echo(df):
            parsed = parse_request(df, "data", parse="json")
            return parsed.with_column(
                "reply", lambda p: [float(np.sum(v)) for v in p["data"]])

        with ServingServer(echo, port=0, max_wait_ms=0.0) as srv:
            with RoutingFront(port=0) as front:
                register_worker(front.address, srv.address)
                http_post(front.address,
                          json.dumps({"data": [1, 2, 3]}).encode())
                base = front.address.rstrip("/")
                status, body, headers = http_get(base + "/_mmlspark/trace")
                assert status == 200
                assert headers["Content-Type"] == "application/json"
                doc = json.loads(body)
                names = {s["name"] for s in doc["spans"]}
                assert {"ingress", "forward"} <= names
                # cross-hop exemplar lookup: the worker's trace ids resolve
                # from the FRONT's endpoint too
                worker_tids = {s["trace_id"] for s in srv.tracer.spans()}
                front_tids = {s["trace_id"] for s in doc["spans"]}
                assert worker_tids and worker_tids <= front_tids
                # front burn-rate gauge exists alongside
                text = http_get(base + "/_mmlspark/metrics")[1].decode()
                assert "mmlspark_slo_burn_rate{" in text

    def test_front_trace_404_when_obs_off(self):
        from urllib.error import HTTPError

        from mmlspark_tpu.serving import RoutingFront

        with RoutingFront(port=0, obs=False) as front:
            with pytest.raises(HTTPError) as ei:
                http_get(front.address.rstrip("/") + "/_mmlspark/trace")
            assert ei.value.code == 404


# -- perf_report tool -------------------------------------------------------


class TestPerfReport:
    def _tool(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "perf_report.py")
        spec = importlib.util.spec_from_file_location("perf_report", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_rows_from_stats_and_render(self):
        tool = self._tool()
        stats = {"fusion": {
            "roofline": {"seg": {"n_batches": 2, "rows": 10,
                                 "measured_ms_per_batch": 5.0,
                                 "bound_ms_per_batch": 1.0,
                                 "roofline_ratio": 0.2,
                                 "bottleneck": "h2d"}},
            "segment_costs": {"seg": {"shape": {"flops": 100.0}}}},
            "latency_histogram": {"exemplars": {
                "0.25": {"trace_id": "tid1", "value": 0.1, "ts": 1.0}}}}
        rows = tool.rows_from_stats(stats)
        assert rows[0]["bottleneck"] == "h2d"
        assert rows[0]["exemplars"] == ["tid1"]
        table = tool.render_table(rows)
        assert "seg" in table and "h2d" in table and "tid1" in table

    def test_rows_from_trace_dump(self, tmp_path):
        tool = self._tool()
        dump = tmp_path / "spans.jsonl"
        spans = [
            {"name": "segment:A", "trace_id": "t1", "dur_s": 0.01,
             "attrs": {"flops": 50.0, "bytes_accessed": 10.0}},
            {"name": "segment:A", "trace_id": "t2", "dur_s": 0.03,
             "attrs": {}},
            {"name": "ingress", "trace_id": "t1", "dur_s": 0.05},
        ]
        dump.write_text("\n".join(json.dumps(s) for s in spans) + "\n")
        rows = tool.rows_from_trace(str(dump))
        assert len(rows) == 1
        assert rows[0]["n_batches"] == 2
        assert rows[0]["measured_ms_per_batch"] == 20.0
        assert rows[0]["flops_per_batch"] == 50.0
        assert set(rows[0]["exemplars"]) == {"t1", "t2"}

    def test_empty_table(self):
        tool = self._tool()
        assert "no fused segments" in tool.render_table([])

    def test_render_lifecycle_section(self):
        tool = self._tool()
        lc = {"registry": {
            "live": "v2",
            "versions": [
                {"version": "v1", "state": "retired", "traffic_share": 0.0,
                 "requests": {"live": 40, "canary": 0},
                 "shadow": {"issued": 0, "scored": 0, "divergent": 0,
                            "errors": 0},
                 "divergence_rate": 0.0},
                {"version": "v2", "state": "live", "traffic_share": 1.0,
                 "requests": {"live": 7, "canary": 5},
                 "shadow": {"issued": 12, "scored": 10, "divergent": 1,
                            "errors": 0},
                 "divergence_rate": 0.1,
                 "burn": {"60": 0.5, "300": 2.0}}],
            "transitions": {"promote": 1}},
            "canary": {"active": None, "rollouts": 1, "promotions": 1,
                       "rollbacks": 0},
            "online": {"adapter": "vw", "step": 3, "consumed": 24,
                       "pending": 2, "published": 1, "publish_failed": 0}}
        text = tool.render_lifecycle(lc)
        assert "live=v2" in text and "promotions=1" in text
        assert "retired" in text and "10/12" in text
        assert "2" in text  # worst burn window surfaces
        assert "online trainer [vw]: step=3" in text
