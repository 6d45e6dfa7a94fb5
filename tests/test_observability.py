"""Observability subsystem tests (mmlspark_tpu/obs/).

Covers the three pillars plus their serving integration:
  - MetricsRegistry semantics (get-or-create, label sets, concurrency) and
    the Prometheus text-format writer (golden output + format validation);
  - request tracing (header round-trip, parent/child linkage, head-based
    sampling determinism — incl. with a seeded FaultInjector active — and
    the JSONL/Perfetto exporters);
  - server + front integration: /_mmlspark/metrics on both, the cheap
    /_mmlspark/healthz probe, bridge parity between /_mmlspark/stats and
    the exposition, and >= 4 linked spans for a traced request crossing
    the front->worker hop;
  - training instrumentation (run_train_loop, GBDT fit, eval metrics) and
    the datagen Categorical extension the chaos tests feed on.
"""

import json
import re
import threading
import urllib.request
from urllib.error import HTTPError

import numpy as np
import pytest

from mmlspark_tpu.obs import (MetricsRegistry, TRACE_HEADER, Tracer,
                              batch_context, current_batch,
                              parse_trace_header, set_default_registry)
from mmlspark_tpu.obs.metrics import MetricFamily
from mmlspark_tpu.serving import RoutingFront, ServingServer, register_worker
from mmlspark_tpu.serving.stages import parse_request


# -- helpers ----------------------------------------------------------------

def echo_transform(df):
    parsed = parse_request(df, "data", parse="json")
    return parsed.with_column(
        "reply", lambda p: [float(np.sum(v)) for v in p["data"]])


PAYLOAD = json.dumps({"data": [1, 2, 3]}).encode()

#: exposition line grammar (text format 0.0.4)
_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^{}]*\})? [0-9eE.+asmInfN-]+)$")


def parse_prom(text):
    """Validate + parse an exposition into {(name, labels-frozenset): value}."""
    out = {}
    for line in text.strip().split("\n"):
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        if line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        if "{" in name_part:
            name, inner = name_part.split("{", 1)
            inner = inner.rstrip("}")
            labels = frozenset(
                tuple(kv.split("=", 1)) for kv in
                re.findall(r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"',
                           inner))
            labels = frozenset((k, v.strip('"')) for k, v in labels)
        else:
            name, labels = name_part, frozenset()
        out[(name, labels)] = float(value) if value not in ("+Inf", "-Inf",
                                                            "NaN") else value
    return out


def http_get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers.items())


def http_post(url, body=PAYLOAD, headers=None, timeout=10):
    req = urllib.request.Request(url, data=body, method="POST",
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers.items())


def base_url(server):
    return f"http://{server.host}:{server.port}"


@pytest.fixture
def fresh_default_registry():
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    yield reg
    set_default_registry(prev)


# -- registry ---------------------------------------------------------------

class TestRegistry:
    def test_counter_inc_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_t_total", "h", ("reason",))
        c.labels(reason="a").inc()
        c.labels(reason="a").inc(2)
        c.labels(reason="b").inc()
        assert c.labels(reason="a").value == 3
        assert c.labels(reason="b").value == 1

    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_t_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_and_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("mmlspark_depth")
        g.set(5)
        g.dec(2)
        assert g.value == 3

    def test_histogram_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_h_seconds", "h", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        vals = parse_prom(reg.exposition())
        assert vals[("mmlspark_h_seconds_bucket",
                     frozenset({("le", "0.1")}))] == 1
        assert vals[("mmlspark_h_seconds_bucket",
                     frozenset({("le", "1")}))] == 2  # cumulative
        assert vals[("mmlspark_h_seconds_bucket",
                     frozenset({("le", "+Inf")}))] == 3
        assert vals[("mmlspark_h_seconds_count", frozenset())] == 3
        assert abs(vals[("mmlspark_h_seconds_sum",
                         frozenset())] - 5.55) < 1e-9

    def test_get_or_create_returns_same(self):
        reg = MetricsRegistry()
        assert reg.counter("mmlspark_x_total") is \
            reg.counter("mmlspark_x_total")

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("mmlspark_x_total")
        with pytest.raises(ValueError):
            reg.gauge("mmlspark_x_total")
        with pytest.raises(ValueError):
            reg.counter("mmlspark_x_total", labelnames=("a",))

    def test_invalid_names_raise(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("mmlspark_ok_total", labelnames=("bad-label",))
        with pytest.raises(ValueError):
            reg.counter("mmlspark_l_total",
                        labelnames=("a",)).labels(wrong="x")

    def test_concurrent_increments_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_c_total")
        h = reg.histogram("mmlspark_ch_seconds", buckets=(1.0,))

        def worker():
            for _ in range(1000):
                c.inc()
                h.observe(0.5)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert reg.sample_value("mmlspark_ch_seconds_count") == 8000

    def test_collector_families(self):
        reg = MetricsRegistry()
        reg.register_collector(lambda: [MetricFamily(
            "mmlspark_bridge_value", "gauge", "from a collector").add(42.0)])
        assert reg.sample_value("mmlspark_bridge_value") == 42.0

    def test_collector_error_does_not_break_scrape(self):
        reg = MetricsRegistry()
        reg.gauge("mmlspark_ok").set(1)

        def bad():
            raise RuntimeError("boom")

        reg.register_collector(bad)
        vals = parse_prom(reg.exposition())
        assert vals[("mmlspark_ok", frozenset())] == 1
        assert ("mmlspark_collector_errors",
                frozenset({("error", "RuntimeError")})) in vals


class TestExposition:
    def test_golden_output(self):
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_requests_total", "requests", ("code",))
        c.labels(code="200").inc(3)
        reg.gauge("mmlspark_up", "liveness").set(1)
        assert reg.exposition() == (
            "# HELP mmlspark_requests_total requests\n"
            "# TYPE mmlspark_requests_total counter\n"
            'mmlspark_requests_total{code="200"} 3\n'
            "# HELP mmlspark_up liveness\n"
            "# TYPE mmlspark_up gauge\n"
            "mmlspark_up 1\n")

    def test_label_value_escaping(self):
        reg = MetricsRegistry()
        reg.counter("mmlspark_e_total", "h", ("msg",)).labels(
            msg='a"b\\c\nd').inc()
        text = reg.exposition()
        assert 'msg="a\\"b\\\\c\\nd"' in text

    def test_every_line_matches_grammar(self):
        reg = MetricsRegistry()
        reg.histogram("mmlspark_g_seconds", "hist", ("op",)).labels(
            op="x").observe(0.2)
        reg.counter("mmlspark_g_total", "count").inc()
        parse_prom(reg.exposition())  # raises on any malformed line


# -- tracing ----------------------------------------------------------------

class TestTrace:
    def test_header_roundtrip(self):
        t = Tracer(seed=7)
        ctx = t.ingress()
        back = parse_trace_header(ctx.to_header())
        assert (back.trace_id, back.span_id, back.sampled) == \
            (ctx.trace_id, ctx.span_id, True)

    def test_malformed_header_starts_fresh(self):
        t = Tracer(seed=0)
        for bad in ("", "zz-yy", "nothex-abc123-01", "a-b-c-d"):
            ctx = t.ingress({TRACE_HEADER: bad})
            assert ctx.parent_id is None  # new trace, not a crash

    def test_ingress_continues_incoming_trace(self):
        t1, t2 = Tracer(seed=1), Tracer(seed=2)
        upstream = t1.ingress()
        ctx = t2.ingress({TRACE_HEADER: upstream.to_header()})
        assert ctx.trace_id == upstream.trace_id
        assert ctx.parent_id == upstream.span_id
        assert t2.stats()["joined"] == 1

    def test_incoming_unsampled_flag_wins(self):
        t = Tracer(seed=3, sample_rate=1.0)
        ctx = t.ingress({TRACE_HEADER: "ab" * 16 + "-" + "cd" * 8 + "-00"})
        assert not ctx.sampled
        t.record("x", ctx, 0.0, 1.0)
        assert t.spans() == []

    def test_sampling_deterministic_with_seed_and_faults(self):
        # the sampling stream must replay exactly under a fixed seed, even
        # with a seeded FaultInjector driving chaos in the same process
        from mmlspark_tpu.core import faults

        def decisions(seed):
            inj = faults.FaultInjector(seed=123).plan(
                faults.HTTP_SEND, p=0.5, exc=RuntimeError)
            with inj:
                for _ in range(50):
                    try:
                        faults.fire(faults.HTTP_SEND)
                    except RuntimeError:
                        pass
                t = Tracer(seed=seed, sample_rate=0.3)
                return [t.ingress().sampled for _ in range(200)]

        a, b = decisions(42), decisions(42)
        assert a == b
        assert 0 < sum(a) < 200  # actually mixed at rate 0.3

    def test_rate_zero_and_one(self):
        t0 = Tracer(sample_rate=0.0, seed=0)
        assert not any(t0.ingress().sampled for _ in range(20))
        t1 = Tracer(sample_rate=1.0, seed=0)
        assert all(t1.ingress().sampled for _ in range(20))

    def test_record_batch_one_span_per_sampled_ctx(self):
        t = Tracer(seed=0)
        ctxs = [t.ingress(), t.ingress()]
        unsampled = t.ingress(
            {TRACE_HEADER: "ab" * 16 + "-" + "cd" * 8 + "-00"})
        t.record_batch("drain", ctxs + [unsampled, None], 0.0, 0.5, rows=3)
        spans = t.spans()
        assert len(spans) == 2
        assert {s["parent_id"] for s in spans} == \
            {c.span_id for c in ctxs}
        assert all(s["attrs"]["rows"] == 3 for s in spans)

    def test_batch_context_visible_and_reset(self):
        t = Tracer(seed=0)
        ctx = t.ingress()
        assert current_batch() is None
        with batch_context(t, [ctx]):
            tracer, ctxs = current_batch()
            assert tracer is t and ctxs == (ctx,)
        assert current_batch() is None
        with batch_context(None, [ctx]):
            assert current_batch() is None  # no tracer -> no binding

    def test_exporters(self, tmp_path):
        t = Tracer(seed=0, service="exp")
        ctx = t.ingress()
        with t.span("work", ctx, op="unit"):
            pass
        jl = tmp_path / "spans.jsonl"
        pf = tmp_path / "trace.json"
        assert t.export_jsonl(str(jl)) == 1
        line = json.loads(jl.read_text().strip())
        assert line["name"] == "work" and line["trace_id"] == ctx.trace_id
        assert t.export_perfetto(str(pf)) == 1
        doc = json.loads(pf.read_text())
        ev = doc["traceEvents"][0]
        assert ev["ph"] == "X" and ev["name"] == "work"
        assert ev["args"]["trace_id"] == ctx.trace_id
        assert ev["dur"] >= 0


# -- server integration -----------------------------------------------------

class TestServerObservability:
    def test_metrics_endpoint_and_stats_parity(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            for _ in range(3):
                http_post(srv.address)
            status, body, headers = http_get(
                base_url(srv) + "/_mmlspark/metrics")
            assert status == 200
            assert headers["Content-Type"].startswith("text/plain")
            vals = parse_prom(body.decode())
            stats = json.loads(http_get(
                base_url(srv) + "/_mmlspark/stats")[1])
            # bridge parity: one source of truth behind both endpoints
            assert vals[("mmlspark_requests_served_total",
                         frozenset())] == 3
            assert vals[("mmlspark_latency_window_requests",
                         frozenset())] == stats["n"]
            assert vals[("mmlspark_request_latency_ms",
                         frozenset({("component", "total"),
                                    ("stat", "p50")}))] == \
                stats["total_ms"]["p50"]

    def test_shed_counters_in_both_surfaces(self):
        with ServingServer(echo_transform, port=0) as srv:
            # expired deadline -> 504 deadline_ingress shed
            req = urllib.request.Request(
                srv.address, data=PAYLOAD, method="POST",
                headers={"X-MMLSpark-Deadline": "1.0"})
            with pytest.raises(HTTPError):
                urllib.request.urlopen(req, timeout=5)
            vals = parse_prom(http_get(
                base_url(srv) + "/_mmlspark/metrics")[1].decode())
            stats = json.loads(http_get(
                base_url(srv) + "/_mmlspark/stats")[1])
            shed = vals[("mmlspark_sheds_total",
                         frozenset({("kind", "reason"),
                                    ("value", "deadline_ingress")}))]
            assert shed == 1
            assert stats["shed"]["by_reason"]["deadline_ingress"] == 1

    def test_healthz_constant_cost(self):
        with ServingServer(echo_transform, port=0) as srv:
            for _ in range(5):
                http_post(srv.address)
            status, body, headers = http_get(
                base_url(srv) + "/_mmlspark/healthz")
            assert status == 200
            assert headers["Content-Type"] == "application/json"
            assert json.loads(body) == {"ok": True, "draining": False}
            # probe cost must NOT scale with traffic like /stats does
            assert len(body) < 64

    def test_obs_disabled(self):
        with ServingServer(echo_transform, port=0, obs=False) as srv:
            assert http_post(srv.address)[1] == b"6.0"  # serving unaffected
            with pytest.raises(HTTPError) as ei:
                http_get(base_url(srv) + "/_mmlspark/metrics")
            assert ei.value.code == 404
            assert srv.tracer is None

    @staticmethod
    def _spans_with(srv, want, timeout=3.0):
        """The ingress span is recorded by the HTTP thread AFTER it wrote
        the reply, so a client that has its answer can still beat it."""
        import time

        deadline = time.time() + timeout
        while True:
            spans = srv.tracer.spans()
            if want <= {s["name"] for s in spans} or time.time() > deadline:
                return spans
            time.sleep(0.01)

    def test_traced_request_linked_spans_sync(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            http_post(srv.address)
            want = {"ingress", "drain", "dispatch", "readback"}
            spans = self._spans_with(srv, want)
            names = {s["name"] for s in spans}
            assert want <= names
            assert len({s["trace_id"] for s in spans}) == 1
            ingress = next(s for s in spans if s["name"] == "ingress")
            for other in spans:
                if other["name"] != "ingress":
                    assert other["parent_id"] == ingress["span_id"]

    def test_traced_request_linked_spans_async(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0,
                           async_exec=True, inflight=2) as srv:
            http_post(srv.address)
            want = {"ingress", "drain", "dispatch", "readback"}
            spans = self._spans_with(srv, want)
            assert want <= {s["name"] for s in spans}
            assert len({s["trace_id"] for s in spans}) == 1

    def test_trace_endpoint(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            http_post(srv.address)
            status, body, headers = http_get(
                base_url(srv) + "/_mmlspark/trace")
            assert status == 200
            assert headers["Content-Type"] == "application/json"
            doc = json.loads(body)
            assert doc["stats"]["started"] == 1
            assert len(doc["spans"]) >= 4

    def test_trace_header_continued_from_client(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            client = Tracer(seed=9)
            up = client.ingress()
            http_post(srv.address, headers={TRACE_HEADER: up.to_header()})
            spans = srv.tracer.spans()
            assert spans and all(
                s["trace_id"] == up.trace_id for s in spans)
            assert srv.tracer.stats()["joined"] == 1


class TestFrontWorkerTracing:
    def test_trace_crosses_hop_with_linked_spans(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            with RoutingFront(port=0) as front:
                register_worker(front.address, srv.address)
                assert http_post(front.address)[1] == b"6.0"
                fs, ws = front.tracer.spans(), srv.tracer.spans()
                tids = {s["trace_id"] for s in fs + ws}
                assert len(tids) == 1  # ONE trace across the hop
                assert len(fs + ws) >= 4
                fwd = next(s for s in fs if s["name"] == "forward")
                wing = next(s for s in ws if s["name"] == "ingress")
                assert wing["parent_id"] == fwd["span_id"]  # linked chain
                assert fwd["attrs"]["status"] == 200

    def test_front_unsampled_decision_propagates(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            with RoutingFront(port=0, trace_sample_rate=0.0) as front:
                register_worker(front.address, srv.address)
                http_post(front.address)
                # the head decision (drop) made at the front is final: the
                # worker must not re-roll and start recording
                assert srv.tracer.spans() == []
                assert srv.tracer.stats()["joined"] == 1
                assert front.tracer.spans() == []

    def test_front_metrics_endpoint(self):
        with ServingServer(echo_transform, port=0, max_wait_ms=0.0) as srv:
            with RoutingFront(port=0) as front:
                register_worker(front.address, srv.address)
                http_post(front.address)
                vals = parse_prom(http_get(
                    front.address.rstrip("/") + "/_mmlspark/metrics"
                )[1].decode())
                assert vals[("mmlspark_front_requests_total",
                             frozenset({("outcome", "forwarded")}))] == 1
                key = ("mmlspark_worker_circuit_state",
                       frozenset({("worker", srv.address),
                                  ("state", "closed")}))
                assert vals[key] == 1

    def test_probe_path_is_healthz(self):
        assert RoutingFront.PROBE_PATH == "/_mmlspark/healthz"
        with ServingServer(echo_transform, port=0) as srv:
            front = RoutingFront(port=0)
            assert front._probe(srv.address)  # answered by the new endpoint

    def test_front_healthz(self):
        with RoutingFront(port=0) as front:
            status, body, headers = http_get(
                front.address.rstrip("/") + "/_mmlspark/healthz")
            assert status == 200
            assert headers["Content-Type"] == "application/json"
            assert json.loads(body) == {"ok": True, "workers": 0}


# -- training instrumentation ----------------------------------------------

class TestTrainingMetrics:
    def test_run_train_loop_emits_series(self, fresh_default_registry):
        from mmlspark_tpu.models.training import run_train_loop, TrainState

        state = TrainState(params={"w": np.zeros(2)}, opt_state=None,
                           step=0)

        def step_fn(st, batch):
            return TrainState(params=st.params, opt_state=None,
                              step=st.step + 1), {"loss": 0.5}

        batches = [np.zeros((4, 2)) for _ in range(5)]
        res = run_train_loop(state, step_fn, batches)
        assert res.steps_run == 5
        reg = fresh_default_registry
        assert reg.sample_value("mmlspark_train_steps_total",
                                {"engine": "dnn"}) == 5
        assert reg.sample_value("mmlspark_train_loss",
                                {"engine": "dnn"}) == 0.5
        assert reg.sample_value("mmlspark_train_step_seconds_count",
                                {"engine": "dnn"}) == 5
        eps = reg.sample_value("mmlspark_train_examples_per_second",
                               {"engine": "dnn"})
        assert eps is not None and eps > 0

    def test_gbdt_fit_emits_series(self, fresh_default_registry, rng):
        from mmlspark_tpu.gbdt.stages import LightGBMRegressor
        from mmlspark_tpu.core.dataframe import DataFrame

        n = 200
        X = rng.standard_normal((n, 4))
        y = X[:, 0] * 2 + rng.standard_normal(n) * 0.1
        feats = np.empty(n, dtype=object)
        for i in range(n):
            feats[i] = X[i]
        df = DataFrame([{"features": feats, "label": y}])
        LightGBMRegressor(labelCol="label", numIterations=3,
                          numLeaves=7).fit(df)
        reg = fresh_default_registry
        steps = reg.sample_value("mmlspark_train_steps_total",
                                 {"engine": "gbdt"}) or 0
        steps_native = reg.sample_value("mmlspark_train_steps_total",
                                        {"engine": "gbdt_native"}) or 0
        assert steps + steps_native == 3  # either engine, same series
        assert reg.sample_value(
            "mmlspark_train_fit_seconds",
            {"estimator": "LightGBMRegressor"}) is not None
        assert reg.sample_value(
            "mmlspark_train_fits_total",
            {"estimator": "LightGBMRegressor"}) == 1

    def test_eval_metrics_scrapeable(self, fresh_default_registry):
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.train import ComputeModelStatistics

        df = DataFrame.from_dict({
            "label": np.array([0.0, 1.0, 1.0, 0.0]),
            "scored_labels": np.array([0.0, 1.0, 0.0, 0.0])})
        ComputeModelStatistics(labelCol="label",
                               scoredLabelsCol="scored_labels",
                               evaluationMetric="classification"
                               ).transform(df)
        reg = fresh_default_registry
        acc = reg.sample_value("mmlspark_eval_metric",
                               {"metric": "accuracy"})
        assert acc == 0.75  # parity with the returned DataFrame


# -- datagen categorical (inherited TODO, DatasetOptions.scala:12) ----------

class TestDatagenCategorical:
    def test_categorical_column(self):
        from mmlspark_tpu.testing.datagen import (ColumnOptions,
                                                  GenConstraints,
                                                  generate_dataset)

        df = generate_dataset(
            GenConstraints(num_rows=64, num_cols=3,
                           randomize_column_names=False),
            seed=5, default=ColumnOptions(data_kinds=("categorical",)))
        for name in df.columns:
            levels = set(df.column(name))
            assert levels <= {f"cat_{i}" for i in range(8)}
            assert 1 <= len(levels) <= 8

    def test_categorical_missing_injection(self):
        from mmlspark_tpu.testing.datagen import (ColumnOptions,
                                                  GenConstraints,
                                                  MissingOptions,
                                                  generate_dataset)

        df = generate_dataset(
            GenConstraints(num_rows=400, num_cols=1,
                           randomize_column_names=False),
            seed=11, default=ColumnOptions(
                data_kinds=("categorical",),
                missing=MissingOptions(percent_missing=0.3,
                                       data_kinds=("categorical",))))
        col = df.column(df.columns[0])
        n_missing = sum(1 for v in col if v is None)
        assert 40 <= n_missing <= 200  # ~30% of 400

    def test_default_kind_stream_unchanged(self):
        # the extension must not perturb seeded draws from the DEFAULT kind
        # set (existing fuzz suites depend on them)
        from mmlspark_tpu.testing.datagen import (DATA_KINDS,
                                                  EXTENDED_DATA_KINDS,
                                                  GenConstraints,
                                                  generate_dataset)

        assert "categorical" not in DATA_KINDS
        assert "categorical" in EXTENDED_DATA_KINDS
        a = generate_dataset(GenConstraints(num_rows=10, num_cols=4),
                             seed=3)
        b = generate_dataset(GenConstraints(num_rows=10, num_cols=4),
                             seed=3)
        assert a.columns == b.columns
