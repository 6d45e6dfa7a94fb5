"""Chaos suite for the fault-tolerance layer (core/faults.py).

Every scenario is deterministic: seeded FaultInjector plans, seeded
RetryPolicy jitter, injected sleeps <= 0.2s. Covers the resilience contract
end to end (docs/faults.md): retry policy + deadline propagation, chaos
injection points, atomic-file helpers, journal crash recovery, circuit-
breaker routing with health-probe re-admission, bounded admission + graceful
drain, GBDT mid-train resume, and the preemption-aware DNN train loop.
"""

import errno
import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from mmlspark_tpu.core import faults
from mmlspark_tpu.core.faults import (
    DEADLINE_HEADER,
    Deadline,
    FaultInjector,
    InjectedFault,
    RetryPolicy,
    atomic_write_text,
    deadline_from_headers,
    rename_with_exdev_fallback,
)

pytestmark = pytest.mark.faults

#: seed matrix knob for the CI chaos lane (tools/ci/run_ci.sh chaos stage):
#: scenarios that draw randomness seed their injectors/policies from this,
#: so `MMLSPARK_CHAOS_SEED=7 pytest -m faults` replays a DIFFERENT but
#: still fully deterministic fault schedule
CHAOS_SEED = int(os.environ.get("MMLSPARK_CHAOS_SEED", "0"))


def _post(url, obj, timeout=15, headers=None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=json.dumps(obj).encode(),
                                 headers=hdrs, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


def _post_status(url, obj, timeout=15, headers=None):
    """Status + parsed body + headers, HTTP errors included."""
    try:
        return _post(url, obj, timeout, headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else {}), dict(e.headers)


# ---------------------------------------------------------------------------
# RetryPolicy / Deadline
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_jitter_is_deterministic_under_seed(self):
        p = RetryPolicy(max_retries=5, base_s=0.1, jitter=0.3, seed=7)
        assert list(p.backoffs()) == list(p.backoffs())
        q = RetryPolicy(max_retries=5, base_s=0.1, jitter=0.3, seed=8)
        assert list(p.backoffs()) != list(q.backoffs())

    def test_backoff_grows_and_caps(self):
        p = RetryPolicy(max_retries=6, base_s=0.1, multiplier=2.0,
                        max_backoff_s=0.4, jitter=0.0)
        waits = list(p.backoffs())
        assert waits == [0.1, 0.2, 0.4, 0.4, 0.4, 0.4]

    def test_budget_bounds_total_sleep(self):
        p = RetryPolicy(max_retries=50, base_s=1.0, jitter=0.0, budget_s=2.5)
        waits = list(p.backoffs())
        assert sum(waits) <= 2.5 + 1e-9

    def test_deadline_stops_run(self):
        """Each wait is capped at the remaining deadline and the retry loop
        stops once it lapses: a 10s backoff against a 50ms deadline sleeps at
        most ~50ms total, then re-raises."""
        p = RetryPolicy(max_retries=50, base_s=10.0, jitter=0.0)
        dl = Deadline.from_timeout(0.05)
        calls, slept = [], []

        def boom():
            calls.append(1)
            raise ValueError("down")

        with pytest.raises(ValueError):
            p.run(boom, deadline=dl,
                  sleep_fn=lambda s: (slept.append(s), time.sleep(s)))
        assert len(calls) <= 3
        assert all(w <= 0.05 + 1e-6 for w in slept)

    def test_run_retries_then_raises(self):
        calls = []

        def boom():
            calls.append(1)
            raise ValueError("nope")

        p = RetryPolicy(max_retries=3, base_s=0.001, jitter=0.0)
        slept = []
        with pytest.raises(ValueError):
            p.run(boom, sleep_fn=slept.append)
        assert len(calls) == 4 and len(slept) == 3

    def test_run_respects_should_retry(self):
        calls = []

        def boom():
            calls.append(1)
            raise KeyError("fatal")

        p = RetryPolicy(max_retries=5, base_s=0.001)
        with pytest.raises(KeyError):
            p.run(boom, should_retry=lambda e: not isinstance(e, KeyError),
                  sleep_fn=lambda s: None)
        assert len(calls) == 1


class TestDeadline:
    def test_header_round_trip(self):
        dl = Deadline.from_timeout(30)
        back = Deadline.from_header(dl.to_header())
        assert back is not None and abs(back.at - dl.at) < 1e-9

    def test_case_insensitive_lookup(self):
        dl = Deadline.from_timeout(30)
        got = deadline_from_headers({DEADLINE_HEADER.lower(): dl.to_header()})
        assert got is not None and abs(got.at - dl.at) < 1e-9
        assert deadline_from_headers({}) is None
        assert deadline_from_headers(None) is None
        assert deadline_from_headers({DEADLINE_HEADER: "garbage"}) is None

    def test_cap_and_expiry(self):
        dl = Deadline(time.time() - 1)
        assert dl.expired() and dl.remaining() == 0.0 and dl.cap(5.0) == 0.0


# ---------------------------------------------------------------------------
# Retry-After parsing + send_with_retries hardening
# ---------------------------------------------------------------------------


class TestRetryAfter:
    def test_numeric_seconds(self):
        from mmlspark_tpu.io.http import parse_retry_after

        assert parse_retry_after("2.5") == 2.5
        assert parse_retry_after("-3") == 0.0

    def test_http_date(self):
        from email.utils import formatdate

        from mmlspark_tpu.io.http import parse_retry_after

        now = time.time()
        wait = parse_retry_after(formatdate(now + 60, usegmt=True), now=now)
        assert wait is not None and 58 <= wait <= 61
        # a date in the past means "retry now", not a negative sleep
        assert parse_retry_after(formatdate(now - 60, usegmt=True),
                                 now=now) == 0.0

    def test_garbage_is_none(self):
        from mmlspark_tpu.io.http import parse_retry_after

        assert parse_retry_after("soon") is None
        assert parse_retry_after("") is None
        assert parse_retry_after(None) is None


class TestSendWithRetries:
    def _flaky(self, replies):
        """send_request stub yielding canned responses."""
        from mmlspark_tpu.io.http import HTTPResponseData

        it = iter(replies)

        def fake(req, timeout=60.0, deadline=None):
            code, headers = next(it)
            return HTTPResponseData(code, str(code), headers=headers)

        return fake

    def test_retry_after_http_date_honored(self, monkeypatch):
        from email.utils import formatdate

        import mmlspark_tpu.io.http as H

        ra = formatdate(time.time() + 40, usegmt=True)
        monkeypatch.setattr(H, "send_request", self._flaky(
            [(429, {"Retry-After": ra}), (200, None)]))
        slept = []
        resp = H.send_with_retries(H.HTTPRequestData("http://x"),
                                   sleep_fn=slept.append)
        assert resp.statusCode == 200
        assert len(slept) == 1 and 35 <= slept[0] <= 41

    def test_retry_after_capped_at_deadline(self, monkeypatch):
        import mmlspark_tpu.io.http as H

        monkeypatch.setattr(H, "send_request", self._flaky(
            [(429, {"Retry-After": "300"}), (200, None)]))
        slept = []
        resp = H.send_with_retries(
            H.HTTPRequestData("http://x"), sleep_fn=slept.append,
            deadline=Deadline.from_timeout(2.0))
        assert resp.statusCode == 200
        assert slept and slept[0] <= 2.0  # not the server's 300s

    def test_expired_deadline_returns_without_retry(self, monkeypatch):
        import mmlspark_tpu.io.http as H

        monkeypatch.setattr(H, "send_request", self._flaky(
            [(503, None)] * 5))
        slept = []
        resp = H.send_with_retries(
            H.HTTPRequestData("http://x"), sleep_fn=slept.append,
            deadline=Deadline(time.time() - 1))
        assert resp.statusCode == 503 and slept == []

    def test_policy_jitter_deterministic(self, monkeypatch):
        import mmlspark_tpu.io.http as H

        pol = RetryPolicy(max_retries=3, base_s=0.1, jitter=0.5, seed=3)
        runs = []
        for _ in range(2):
            monkeypatch.setattr(H, "send_request", self._flaky(
                [(503, None)] * 3 + [(200, None)]))
            slept = []
            H.send_with_retries(H.HTTPRequestData("http://x"),
                                sleep_fn=slept.append, policy=pol)
            runs.append(slept)
        assert runs[0] == runs[1] and len(runs[0]) == 3

    def test_legacy_backoffs_are_jittered(self, monkeypatch):
        import mmlspark_tpu.io.http as H

        monkeypatch.setattr(H, "send_request", self._flaky(
            [(500, None), (500, None), (500, None), (200, None)]))
        slept = []
        H.send_with_retries(H.HTTPRequestData("http://x"),
                            sleep_fn=slept.append)
        for base, got in zip((0.1, 0.5, 1.0), slept):
            assert abs(got - base) <= base * 0.2 + 1e-9


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------


class TestFaultInjector:
    def test_fires_on_exact_call_indices(self):
        with FaultInjector(seed=1).plan(faults.HTTP_SEND, at=(2, 4)) as inj:
            fired = []
            for i in range(5):
                try:
                    faults.fire(faults.HTTP_SEND)
                except InjectedFault:
                    fired.append(i + 1)
            assert fired == [2, 4]
        assert faults.active() is None

    def test_probability_stream_replays_under_seed(self):
        def run():
            with FaultInjector(seed=42).plan(faults.TRAIN_STEP, p=0.3,
                                             times=-1) as inj:
                hits = []
                for i in range(50):
                    try:
                        faults.fire(faults.TRAIN_STEP, iteration=i)
                    except InjectedFault:
                        hits.append(i)
                return hits

        a, b = run(), run()
        assert a == b and 5 <= len(a) <= 25

    def test_times_caps_fires_and_log_records(self):
        with FaultInjector().plan(faults.JOURNAL_WRITE, every=1,
                                  times=2) as inj:
            n_raised = 0
            for _ in range(5):
                try:
                    faults.fire(faults.JOURNAL_WRITE, epoch=9)
                except InjectedFault:
                    n_raised += 1
            assert n_raised == 2
            assert [c["epoch"] for _, _, c in inj.fired()] == [9, 9]
            assert inj.calls(faults.JOURNAL_WRITE) == 5

    def test_noop_when_not_installed(self):
        faults.fire(faults.HTTP_SEND)  # must not raise

    def test_delay_without_exception(self):
        with FaultInjector().plan(faults.INGEST_H2D, at=(1,), delay_s=0.05,
                                  exc=None):
            t0 = time.perf_counter()
            faults.fire(faults.INGEST_H2D)
            assert time.perf_counter() - t0 >= 0.045


# ---------------------------------------------------------------------------
# Atomic file helpers
# ---------------------------------------------------------------------------


class TestAtomicFiles:
    def test_atomic_write_replaces_whole_file(self, tmp_path):
        p = str(tmp_path / "f.txt")
        atomic_write_text(p, "one")
        atomic_write_text(p, "two")
        assert open(p).read() == "two"
        assert [f for f in os.listdir(tmp_path) if ".tmp" in f] == []

    def test_exdev_fallback_file(self, tmp_path, monkeypatch):
        src, dst = str(tmp_path / "src.bin"), str(tmp_path / "dst.bin")
        with open(src, "wb") as fh:
            fh.write(b"payload")
        real_rename = os.rename

        def exdev_once(a, b):
            if a == src:
                raise OSError(errno.EXDEV, "cross-device link")
            real_rename(a, b)

        rename_with_exdev_fallback(src, dst, _rename=exdev_once)
        assert open(dst, "rb").read() == b"payload"
        assert not os.path.exists(src)

    def test_exdev_fallback_directory(self, tmp_path):
        src = tmp_path / "srcdir"
        src.mkdir()
        (src / "a.txt").write_text("A")
        dst = str(tmp_path / "dstdir")

        def always_exdev(a, b):
            raise OSError(errno.EXDEV, "cross-device link")

        rename_with_exdev_fallback(str(src), dst, _rename=always_exdev)
        assert open(os.path.join(dst, "a.txt")).read() == "A"
        assert not os.path.exists(src)

    def test_non_exdev_errors_propagate(self, tmp_path):
        def eperm(a, b):
            raise OSError(errno.EPERM, "no")

        with pytest.raises(OSError) as ei:
            rename_with_exdev_fallback(str(tmp_path / "x"),
                                       str(tmp_path / "y"), _rename=eperm)
        assert ei.value.errno == errno.EPERM


# ---------------------------------------------------------------------------
# Journal chaos: crash windows around append/commit/compact
# ---------------------------------------------------------------------------


def _echo_transform(df):
    from mmlspark_tpu.serving.stages import parse_request

    parsed = parse_request(df, "data", parse="json")
    return parsed.with_column(
        "reply", lambda p: [{"sum": float(np.sum(v))} for v in p["data"]])


class TestJournalChaos:
    def test_crash_between_append_and_commit_replays(self, tmp_path):
        """The at-least-once window: entries journaled, commit never lands.
        Recovery must return exactly those requests."""
        from mmlspark_tpu.serving import RequestJournal, ServingServer

        jpath = str(tmp_path / "wal.jsonl")
        with FaultInjector(seed=0).plan(faults.JOURNAL_COMMIT, every=1):
            srv = ServingServer(_echo_transform, port=0, max_wait_ms=2.0,
                                journal_path=jpath)
            srv.start()
            try:
                status, body, _ = _post(srv.address, {"data": [1, 2]})
                assert status == 200 and body["sum"] == 3.0
            finally:
                srv.stop(drain=False)  # hard stop: the crash
        replay = RequestJournal.recover(jpath)
        assert [json.loads(b)["data"] for _, b, _ in replay] == [[1, 2]]

    def test_journal_write_failure_degrades_not_dies(self, tmp_path):
        """An injected append failure must not take serving down."""
        from mmlspark_tpu.serving import ServingServer

        jpath = str(tmp_path / "wal.jsonl")
        with FaultInjector(seed=0).plan(faults.JOURNAL_WRITE, at=(1,)):
            with ServingServer(_echo_transform, port=0, max_wait_ms=2.0,
                               journal_path=jpath) as srv:
                status, body, _ = _post(srv.address, {"data": [4]})
                assert status == 200 and body["sum"] == 4.0
                status, body, _ = _post(srv.address, {"data": [5]})
                assert status == 200 and body["sum"] == 5.0

    def test_commit_retries_after_transient_failure(self, tmp_path):
        """A commit that fails once lands on a later sweep — the epoch must
        not replay after a clean shutdown."""
        from mmlspark_tpu.serving import RequestJournal, ServingServer

        jpath = str(tmp_path / "wal.jsonl")
        with FaultInjector(seed=0).plan(faults.JOURNAL_COMMIT, at=(1,)):
            with ServingServer(_echo_transform, port=0, max_wait_ms=2.0,
                               journal_path=jpath) as srv:
                status, body, _ = _post(srv.address, {"data": [7]})
                assert status == 200
        assert RequestJournal.recover(jpath) == []

    def test_compact_crash_preserves_old_journal(self, tmp_path,
                                                 monkeypatch):
        """Crash mid-compact (fsync of the replacement raises) must leave the
        complete OLD journal, keep uncommitted epochs recoverable, and keep
        the journal writable."""
        from mmlspark_tpu.serving import RequestJournal

        jpath = str(tmp_path / "wal.jsonl")
        j = RequestJournal(jpath)
        j.append(1, 10, b"keep-me", {})
        j.commit(1)
        j.append(2, 11, b"uncommitted", {})
        before = open(jpath).read()

        real_fsync = os.fsync

        def fsync_boom(fd):
            raise OSError(errno.EIO, "injected fsync failure")

        monkeypatch.setattr(os, "fsync", fsync_boom)
        with pytest.raises(OSError):
            j.compact()
        monkeypatch.setattr(os, "fsync", real_fsync)

        assert open(jpath).read() == before  # old file intact, not torn
        assert [r for r, _, _ in RequestJournal.recover(jpath)] == [11]
        j.append(3, 12, b"still-writable", {})  # handle reopened
        j.close()
        assert [r for r, _, _ in RequestJournal.recover(jpath)] == [11, 12]

    def test_compact_keeps_uncommitted_and_drops_committed(self, tmp_path):
        from mmlspark_tpu.serving import RequestJournal

        jpath = str(tmp_path / "wal.jsonl")
        j = RequestJournal(jpath)
        j.append(1, 1, b"done", {})
        j.commit(1)
        j.append(2, 2, b"live", {})
        j.compact()
        j.close()
        assert [r for r, _, _ in RequestJournal.recover(jpath)] == [2]
        assert not os.path.exists(jpath + ".tmp")


# ---------------------------------------------------------------------------
# Routing chaos: circuit breaker, probes, worker kill mid-request
# ---------------------------------------------------------------------------


class _ToggleWorker:
    """Raw HTTP worker whose liveness flips under test control. When dead it
    resets connections (a killed process), when alive it answers JSON."""

    def __init__(self):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def _serve(self):
                if not outer.alive:
                    # simulate a killed worker: RST the connection (a dead
                    # process resets; a bare close() leaves keep-alive
                    # clients hanging on a half-open socket, which is a
                    # DIFFERENT failure — the watchdog/hedge tests cover it)
                    import socket as socket_mod
                    import struct

                    try:
                        self.connection.setsockopt(
                            socket_mod.SOL_SOCKET, socket_mod.SO_LINGER,
                            struct.pack("ii", 1, 0))
                    except OSError:
                        pass
                    self.close_connection = True
                    self.connection.close()
                    return
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                body = json.dumps({"worker": "toggle"}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            do_GET = _serve
            do_POST = _serve

        self.alive = True
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.address = f"http://127.0.0.1:{self._httpd.server_address[1]}/"
        self._t = threading.Thread(target=self._httpd.serve_forever,
                                   daemon=True)
        self._t.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


class TestRoutingChaos:
    def _front(self, **kw):
        from mmlspark_tpu.serving import RoutingFront

        kw.setdefault("probe_interval_s", 0.05)
        kw.setdefault("probe_timeout_s", 1.0)
        kw.setdefault("probe_policy", RetryPolicy(
            max_retries=1 << 30, base_s=0.05, multiplier=1.0,
            max_backoff_s=0.05, jitter=0.0, seed=0))
        return RoutingFront(port=0, max_failures=2, **kw)

    def test_no_workers_503_with_retry_after(self):
        with self._front() as front:
            status, body, headers = _post_status(front.address, {"x": 1})
            assert status == 503 and "Retry-After" in headers

    def test_breaker_opens_worker_stays_registered(self):
        dead = "http://127.0.0.1:9/"
        live = _ToggleWorker()
        try:
            with self._front() as front:
                front.register(live.address)
                front.register(dead)
                for _ in range(4):
                    status, body, _ = _post_status(front.address, {"x": 1})
                    assert status == 200 and body["worker"] == "toggle"
                assert front.workers == [live.address]  # dead one excluded
                assert front.worker_states[dead] == "open"  # NOT forgotten
        finally:
            live.stop()

    def test_worker_kill_mid_stream_recovers_via_reroute(self):
        """One worker dies (connection reset); the front re-routes to the
        survivor and every request still answers 200."""
        w1, w2 = _ToggleWorker(), _ToggleWorker()
        try:
            with self._front() as front:
                front.register(w1.address)
                front.register(w2.address)
                w1.alive = False  # kill one mid-traffic
                for i in range(6):
                    status, body, _ = _post_status(front.address, {"i": i})
                    assert status == 200 and body["worker"] == "toggle"
                assert front.worker_states[w1.address] == "open"
        finally:
            w1.stop()
            w2.stop()

    def test_health_probe_readmits_recovered_worker(self):
        w = _ToggleWorker()
        try:
            with self._front() as front:
                front.register(w.address)
                w.alive = False
                for _ in range(3):
                    _post_status(front.address, {"x": 1}, timeout=5)
                assert front.worker_states[w.address] == "open"
                w.alive = True  # worker comes back
                deadline = time.time() + 5
                while (front.worker_states[w.address] == "open"
                       and time.time() < deadline):
                    time.sleep(0.02)
                assert front.worker_states[w.address] in ("half_open",
                                                          "closed")
                status, body, _ = _post_status(front.address, {"x": 2})
                assert status == 200  # traffic flows again
                assert front.worker_states[w.address] == "closed"
        finally:
            w.stop()

    def test_expired_deadline_rejected_pre_forward(self):
        w = _ToggleWorker()
        try:
            with self._front() as front:
                front.register(w.address)
                expired = Deadline(time.time() - 5).to_header()
                status, body, _ = _post_status(
                    front.address, {"x": 1},
                    headers={DEADLINE_HEADER: expired})
                assert status == 504
                live = Deadline.from_timeout(30).to_header()
                status, body, _ = _post_status(
                    front.address, {"x": 1},
                    headers={DEADLINE_HEADER: live})
                assert status == 200
        finally:
            w.stop()

    def test_injected_forward_fault_exercises_retry(self):
        """A planned WORKER_FORWARD fault behaves like a transport failure:
        the front retries the other worker, the request still answers."""
        w1, w2 = _ToggleWorker(), _ToggleWorker()
        try:
            with self._front() as front:
                front.register(w1.address)
                front.register(w2.address)
                with FaultInjector(seed=0).plan(faults.WORKER_FORWARD,
                                                at=(1,)) as inj:
                    status, body, _ = _post_status(front.address, {"x": 1})
                    assert status == 200
                    assert len(inj.fired(faults.WORKER_FORWARD)) == 1
        finally:
            w1.stop()
            w2.stop()


# ---------------------------------------------------------------------------
# Serving hardening: deadline in queue, admission bound, graceful drain
# ---------------------------------------------------------------------------


class TestServingHardening:
    def test_expired_deadline_rejected_at_ingress(self):
        from mmlspark_tpu.serving import ServingServer

        with ServingServer(_echo_transform, port=0, max_wait_ms=2.0) as srv:
            expired = Deadline(time.time() - 5).to_header()
            status, body, _ = _post_status(
                srv.address, {"data": [1]},
                headers={DEADLINE_HEADER: expired})
            assert status == 504

    def test_deadline_expiring_in_queue_gets_504_not_compute(self):
        """A request whose deadline lapses while queued is answered 504 by
        the batcher without reaching the transform."""
        from mmlspark_tpu.serving import ServingServer

        seen = []

        def transform(df):
            seen.extend(int(r) for r in df.collect()["id"])
            return _echo_transform(df)

        gate = threading.Event()

        def gated(df):
            gate.wait(5)
            return transform(df)

        with ServingServer(gated, port=0, max_wait_ms=1.0,
                           max_batch_size=1) as srv:
            # first request occupies the loop inside the gated transform
            t1 = threading.Thread(target=_post_status, args=(
                srv.address, {"data": [1]}))
            t1.start()
            time.sleep(0.1)
            # second request: deadline lapses while it waits in the queue
            res = {}

            def second():
                hdr = {DEADLINE_HEADER: Deadline.from_timeout(0.2).to_header()}
                res["status"], _, _ = _post_status(
                    srv.address, {"data": [2]}, headers=hdr)

            t2 = threading.Thread(target=second)
            t2.start()
            time.sleep(0.4)  # let the deadline lapse before opening the gate
            gate.set()
            t1.join(10)
            t2.join(10)
            assert res["status"] == 504
            assert len(seen) == 1  # the expired request never hit compute

    def test_admission_queue_load_sheds_503(self):
        from mmlspark_tpu.serving import ServingServer

        gate = threading.Event()

        def slow(df):
            gate.wait(5)
            return _echo_transform(df)

        with ServingServer(slow, port=0, max_wait_ms=1.0, max_batch_size=1,
                           max_queue=1) as srv:
            threads = []
            codes = []
            lock = threading.Lock()

            def client(i):
                status, _, headers = _post_status(srv.address, {"data": [i]},
                                                  timeout=10)
                with lock:
                    codes.append((status, headers.get("Retry-After")))

            for i in range(6):
                threads.append(threading.Thread(target=client, args=(i,)))
                threads[-1].start()
                time.sleep(0.05)
            gate.set()
            for t in threads:
                t.join(10)
            shed = [c for c in codes if c[0] == 503]
            assert shed, f"expected load shedding, got {codes}"
            assert all(ra is not None for _, ra in shed)
            assert any(s == 200 for s, _ in codes)

    def test_graceful_drain_answers_inflight_then_rejects(self, tmp_path):
        from mmlspark_tpu.serving import RequestJournal, ServingServer

        jpath = str(tmp_path / "wal.jsonl")
        gate = threading.Event()

        def slow(df):
            gate.wait(5)
            return _echo_transform(df)

        srv = ServingServer(slow, port=0, max_wait_ms=1.0,
                            journal_path=jpath, drain_timeout_s=5.0)
        srv.start()
        res = {}

        def client():
            res["status"], res["body"], _ = _post_status(
                srv.address, {"data": [1, 2, 3]}, timeout=15)

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.2)  # request is in flight behind the gate

        stopper = threading.Thread(target=srv.stop)  # drain=True default
        stopper.start()
        time.sleep(0.2)
        gate.set()  # in-flight transform completes during the drain
        stopper.join(10)
        t.join(10)
        assert res["status"] == 200 and res["body"]["sum"] == 6.0
        # a clean drain leaves nothing to replay
        assert RequestJournal.recover(jpath) == []


# ---------------------------------------------------------------------------
# Async-front chaos: the PR-2 scenarios rerun under http_mode="async"
# ---------------------------------------------------------------------------


class TestAsyncFrontChaos:
    """Worker-kill / journal-crash / deadline cases over the event-loop
    transports (serving/aio.py) — the threaded-path chaos suite above only
    exercised ThreadingHTTPServer."""

    def _front(self, **kw):
        from mmlspark_tpu.serving import RoutingFront

        kw.setdefault("probe_interval_s", 0.05)
        kw.setdefault("probe_timeout_s", 1.0)
        kw.setdefault("probe_policy", RetryPolicy(
            max_retries=1 << 30, base_s=0.05, multiplier=1.0,
            max_backoff_s=0.05, jitter=0.0, seed=CHAOS_SEED))
        return RoutingFront(port=0, max_failures=2, http_mode="async", **kw)

    def test_worker_kill_mid_stream_reroutes_async(self):
        w1, w2 = _ToggleWorker(), _ToggleWorker()
        try:
            with self._front() as front:
                front.register(w1.address)
                front.register(w2.address)
                w1.alive = False  # kill one mid-traffic
                for i in range(6):
                    status, body, _ = _post_status(front.address, {"i": i})
                    assert status == 200 and body["worker"] == "toggle"
                assert front.worker_states[w1.address] == "open"
        finally:
            w1.stop()
            w2.stop()

    def test_health_probe_readmits_async(self):
        w = _ToggleWorker()
        try:
            with self._front() as front:
                front.register(w.address)
                w.alive = False
                for _ in range(3):
                    _post_status(front.address, {"x": 1}, timeout=5)
                assert front.worker_states[w.address] == "open"
                w.alive = True
                deadline = time.time() + 5
                while (front.worker_states[w.address] == "open"
                       and time.time() < deadline):
                    time.sleep(0.02)
                status, _, _ = _post_status(front.address, {"x": 2})
                assert status == 200
                assert front.worker_states[w.address] == "closed"
        finally:
            w.stop()

    def test_expired_deadline_rejected_async_front_and_worker(self):
        from mmlspark_tpu.serving import ServingServer

        with ServingServer(_echo_transform, port=0, max_wait_ms=2.0,
                           http_mode="async") as srv:
            # dead-on-arrival at the async worker ingress
            expired = Deadline(time.time() - 5).to_header()
            status, _, _ = _post_status(
                srv.address, {"data": [1]},
                headers={DEADLINE_HEADER: expired})
            assert status == 504
            with self._front() as front:
                front.register(srv.address)
                status, _, _ = _post_status(
                    front.address, {"data": [1]},
                    headers={DEADLINE_HEADER: expired})
                assert status == 504  # gated at the async front, pre-forward
                live = Deadline.from_timeout(30).to_header()
                status, body, _ = _post_status(
                    front.address, {"data": [2, 3]},
                    headers={DEADLINE_HEADER: live})
                assert status == 200 and body["sum"] == 5.0

    def test_journal_crash_replays_async_http(self, tmp_path):
        """The PR-2 at-least-once window under the async transport: commit
        never lands, hard stop, recovery returns the uncommitted batch."""
        from mmlspark_tpu.serving import RequestJournal, ServingServer

        jpath = str(tmp_path / "wal.jsonl")
        with FaultInjector(seed=CHAOS_SEED).plan(faults.JOURNAL_COMMIT,
                                                 every=1):
            srv = ServingServer(_echo_transform, port=0, max_wait_ms=2.0,
                                journal_path=jpath, http_mode="async")
            srv.start()
            try:
                status, body, _ = _post(srv.address, {"data": [1, 2]})
                assert status == 200 and body["sum"] == 3.0
            finally:
                srv.stop(drain=False)  # hard stop: the crash
        replay = RequestJournal.recover(jpath)
        assert [json.loads(b)["data"] for _, b, _ in replay] == [[1, 2]]

    def test_journal_write_failure_degrades_async_http(self, tmp_path):
        from mmlspark_tpu.serving import ServingServer

        jpath = str(tmp_path / "wal.jsonl")
        with FaultInjector(seed=CHAOS_SEED).plan(faults.JOURNAL_WRITE,
                                                 at=(1,)):
            with ServingServer(_echo_transform, port=0, max_wait_ms=2.0,
                               journal_path=jpath,
                               http_mode="async") as srv:
                status, body, _ = _post(srv.address, {"data": [4]})
                assert status == 200 and body["sum"] == 4.0


# ---------------------------------------------------------------------------
# Hung-dispatch watchdog + replica supervision (serving/supervisor.py)
# ---------------------------------------------------------------------------


class TestDispatchWatchdog:
    def _server(self, **kw):
        from mmlspark_tpu.serving import ServingServer

        kw.setdefault("max_wait_ms", 1.0)
        kw.setdefault("async_exec", True)
        kw.setdefault("adaptive_batching", False)
        return ServingServer(_echo_transform, port=0, **kw)

    @staticmethod
    def _supervisor(srv):
        return srv._executor.supervisor

    def _wait_for(self, pred, timeout=6.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if pred():
                return True
            time.sleep(0.02)
        return pred()

    def test_wedged_dispatch_requeues_then_quarantine_and_readmit(self):
        """The headline chaos proof: a dispatch wedged by an injected hang
        is re-dispatched on a healthy replica (the request completes), the
        wedged replica is quarantined, and — once its stuck thread returns
        and the probe cooldown passes — re-admitted."""
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.WORKER_DISPATCH_HANG, at=(1,), delay_s=0.5,
                exc=None) as inj:
            with self._server(replicas=2, inflight=2,
                              watchdog_budget_s=0.05) as srv:
                # tight probe schedule so re-admission is fast in the test
                self._supervisor(srv).quarantine_s = 0.05
                t0 = time.perf_counter()
                status, body, _ = _post(srv.address, {"data": [1, 2]})
                took = time.perf_counter() - t0
                assert status == 200 and body["sum"] == 3.0
                # answered by the re-dispatch, not the 0.5s hang clearing
                assert took < 0.45, f"no re-dispatch: took {took:.3f}s"
                assert len(inj.fired(faults.WORKER_DISPATCH_HANG)) == 1
                ex = srv._executor
                assert ex.watchdog.requeues == 1
                sup = self._supervisor(srv)
                assert any(r["state"] != "healthy" or r["ejections"]
                           for r in sup.describe())
                # the stuck thread returns at ~0.5s; after the cooldown the
                # replica is probed and re-admitted
                assert self._wait_for(
                    lambda: sup.summary()["readmissions"] >= 1)
                assert self._wait_for(
                    lambda: sup.summary()["healthy"] == 2)
                # the recovered fleet still serves
                status, body, _ = _post(srv.address, {"data": [5]})
                assert status == 200 and body["sum"] == 5.0

    def test_compile_is_not_a_wedge(self):
        """A dispatch that is compiling a new shape bucket (tens of seconds
        on a TPU) outlasts any compute-derived budget; with no healthy peer
        the watchdog used to double the budget three times and then answer
        504. The budget clock is paused while the dispatching thread is
        inside a CompileCache build."""
        from mmlspark_tpu.core.device_stage import CompileCache

        cache = CompileCache()

        def slow_build():
            time.sleep(0.6)         # 12x the fixed 0.05 s budget
            return lambda *a: a

        def transform(df):
            cache.get(("bucket-8",), slow_build)
            return _echo_transform(df)

        from mmlspark_tpu.serving import ServingServer

        with ServingServer(transform, port=0, max_wait_ms=1.0,
                           async_exec=True, adaptive_batching=False,
                           replicas=1, watchdog_budget_s=0.05) as srv:
            status, body, _ = _post(srv.address, {"data": [1, 2]})
            assert status == 200 and body["sum"] == 3.0
            assert srv._executor.watchdog.trips == 0
            assert srv.stats.shed_summary()["total"] == 0

    def test_hang_under_load_no_request_lost(self):
        """With a mid-load wedge on one replica, every request either
        completes on a healthy replica or sheds with an accounted reason —
        none hang to the slot timeout, none vanish."""
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.WORKER_DISPATCH_HANG, at=(3,), delay_s=0.5,
                exc=None):
            with self._server(replicas=2, inflight=2, max_batch_size=1,
                              watchdog_budget_s=0.05,
                              slot_timeout_s=15.0) as srv:
                self._supervisor(srv).quarantine_s = 0.05
                results = {}
                lock = threading.Lock()

                def client(i):
                    status, body, _ = _post_status(
                        srv.address, {"data": [i]}, timeout=20)
                    with lock:
                        results[i] = (status, body)

                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(10)]
                for t in threads:
                    t.start()
                    time.sleep(0.02)
                for t in threads:
                    t.join(timeout=30)
                shed = srv.stats.shed_summary()
                assert sorted(results) == list(range(10))  # none lost
                answered = sum(1 for s, _ in results.values() if s == 200)
                accounted = shed["total"]
                assert answered + accounted >= 10
                # correct replies for everything answered 200
                for i, (s, body) in results.items():
                    if s == 200:
                        assert body["sum"] == float(i)
                sup = self._supervisor(srv)
                assert sup.summary()["ejections"] >= 1
                assert self._wait_for(
                    lambda: sup.summary()["healthy"] == 2)

    def test_single_replica_wedge_abandons_with_accounted_504(self):
        """No healthy peer: the watchdog extends the budget a bounded
        number of times, then abandons the batch with an accounted 504 —
        faster than the wedge itself, and attributed in the shed stats."""
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.WORKER_DISPATCH_HANG, at=(1,), delay_s=1.2,
                exc=None):
            with self._server(replicas=1, inflight=1,
                              watchdog_budget_s=0.05) as srv:
                self._supervisor(srv).quarantine_s = 0.05
                t0 = time.perf_counter()
                status, body, _ = _post_status(srv.address, {"data": [1]},
                                               timeout=20)
                took = time.perf_counter() - t0
                assert status == 504
                assert took < 1.1, f"abandon beat the wedge: {took:.3f}s"
                shed = srv.stats.shed_summary()
                assert shed["by_reason"].get("watchdog_abandoned", 0) >= 1
                assert srv._executor.watchdog.abandons == 1
                # once the hang clears, probe + readmit restore service
                sup = self._supervisor(srv)
                assert self._wait_for(
                    lambda: sup.summary()["healthy"] == 1, timeout=8.0)
                status, body, _ = _post(srv.address, {"data": [7]})
                assert status == 200 and body["sum"] == 7.0

    def test_replica_crash_scores_out_and_batch_gets_500(self):
        """worker.crash: the dispatch raises like a dying replica process —
        the batch fails 500 (current contract) and repeated crashes eject
        the replica via the consecutive-failure score."""
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.WORKER_CRASH, every=1, times=3) as inj:
            with self._server(replicas=2, inflight=1,
                              max_batch_size=1) as srv:
                codes = []
                for i in range(5):
                    status, _, _ = _post_status(srv.address, {"data": [i]},
                                                timeout=15)
                    codes.append(status)
                assert codes[:3] == [500, 500, 500]
                assert codes[3:] == [200, 200]  # fleet keeps serving
                assert len(inj.fired(faults.WORKER_CRASH)) == 3
                sup = self._supervisor(srv)
                rows = {r["replica"]: r for r in sup.describe()}
                assert sum(r["errors"] for r in rows.values()) == 3

    def test_watchdog_unarmed_until_calibrated(self):
        from mmlspark_tpu.serving.supervisor import DispatchWatchdog

        wd = DispatchWatchdog(k=4.0, min_budget_s=0.5)
        assert wd.budget_s(8) is None  # no estimate yet: never trips
        wd.observe(0.01)
        assert wd.budget_s(8) == 0.5  # floored
        wd.observe(1.0)
        assert wd.budget_s(8) > 0.5
        fixed = DispatchWatchdog(fixed_s=0.25)
        assert fixed.budget_s(1) == 0.25

    def test_watchdog_budget_prefers_cost_model(self):
        from mmlspark_tpu.serving.supervisor import DispatchWatchdog

        wd = DispatchWatchdog(k=2.0, min_budget_s=0.01,
                              predict_ms_fn=lambda rows: 100.0)
        wd.observe(5.0)  # EWMA would give 10s; the model predicts 100ms
        assert wd.budget_s(4) == pytest.approx(0.2)

    def test_supervisor_outlier_and_score_decay(self):
        from mmlspark_tpu.serving.supervisor import ReplicaSupervisor

        sup = ReplicaSupervisor(2, outlier_k=4.0)
        for _ in range(10):
            sup.note_success(0, 0.01)
        sup.note_success(0, 1.0)  # 100x the EWMA: an outlier
        row = sup.describe()[0]
        assert row["outliers"] == 1 and row["state"] == "healthy"
        assert row["score"] < 1.0

    def test_supervisor_consecutive_failures_eject_and_probe_backoff(self):
        from mmlspark_tpu.serving.supervisor import ReplicaSupervisor

        clock = [0.0]
        sup = ReplicaSupervisor(2, max_failures=2, quarantine_s=1.0,
                                clock=lambda: clock[0])
        sup.note_failure(0)
        assert sup.admitted(0)
        sup.note_failure(0)
        assert not sup.admitted(0)
        assert sup.healthy_peers(0) == 1
        assert not sup.probe_due(0)
        clock[0] = 1.5
        assert sup.probe_due(0)
        sup.begin_probe(0)
        sup.note_probe(0, False)  # failed probe: backoff doubles
        clock[0] = 2.5
        assert not sup.probe_due(0)  # needs 2s now
        clock[0] = 3.6
        assert sup.probe_due(0)
        sup.begin_probe(0)
        sup.note_probe(0, True)
        assert sup.admitted(0)
        assert sup.describe()[0]["readmissions"] == 1


# ---------------------------------------------------------------------------
# Hedged requests (RoutingFront + serving/supervisor.py HedgeTracker)
# ---------------------------------------------------------------------------


class _StallWorker:
    """ServingServer wrapper whose transform stalls ``stall_s`` while
    ``stalled`` is set — the deterministic slow replica."""

    def __init__(self, stall_s=0.0):
        from mmlspark_tpu.serving import ServingServer

        self.stalled = stall_s > 0
        self.stall_s = stall_s

        def transform(df):
            if self.stalled:
                time.sleep(self.stall_s)
            return _echo_transform(df)

        self.server = ServingServer(transform, port=0, max_wait_ms=1.0)
        self.server.start()
        self.address = self.server.address

    def stop(self):
        self.server.stop(drain=False)


class TestHedging:
    def _front(self, http_mode="thread", **hedge_kw):
        from mmlspark_tpu.serving import RoutingFront

        hedge_kw.setdefault("init_delay_ms", 40.0)
        hedge_kw.setdefault("min_samples", 1 << 30)  # pin the init delay
        return RoutingFront(port=0, http_mode=http_mode, hedge=hedge_kw)

    def test_hedge_under_stall_first_response_wins(self):
        """A 300ms stall on the primary worker: the hedge fires at ~40ms
        on the healthy peer and the client sees its reply — p99 under the
        injected stall, duplicate work bounded to the stalled requests."""
        fast, slow = _StallWorker(), _StallWorker(stall_s=0.3)
        try:
            with self._front() as front:
                # round-robin alternates; half the primaries stall
                front.register(slow.address)
                front.register(fast.address)
                lat = []
                for i in range(8):
                    t0 = time.perf_counter()
                    status, body, _ = _post_status(front.address,
                                                   {"data": [i]}, timeout=15)
                    lat.append(time.perf_counter() - t0)
                    assert status == 200 and body["sum"] == float(i)
                # every request beat the stall (hedge or fast primary)
                assert max(lat) < 0.28, [round(x, 3) for x in lat]
                s = front._hedge.summary()
                assert s["wins_hedge"] >= 1       # stalled primaries lost
                assert s["wins_primary"] >= 1     # fast primaries won
                assert s["hedged"] <= 5           # only the slow half hedged
        finally:
            fast.stop()
            slow.stop()

    def test_hedge_under_stall_async_front(self):
        fast, slow = _StallWorker(), _StallWorker(stall_s=0.3)
        try:
            with self._front(http_mode="async") as front:
                front.register(slow.address)
                front.register(fast.address)
                lat = []
                for i in range(8):
                    t0 = time.perf_counter()
                    status, body, _ = _post_status(front.address,
                                                   {"data": [i]}, timeout=15)
                    lat.append(time.perf_counter() - t0)
                    assert status == 200 and body["sum"] == float(i)
                assert max(lat) < 0.28, [round(x, 3) for x in lat]
                assert front._hedge.summary()["wins_hedge"] >= 1
        finally:
            fast.stop()
            slow.stop()

    def test_fast_fleet_never_hedges(self):
        """Duplicate-work bound: against healthy sub-delay workers, zero
        hedges launch."""
        a, b = _StallWorker(), _StallWorker()
        try:
            with self._front(init_delay_ms=250.0) as front:
                front.register(a.address)
                front.register(b.address)
                for i in range(10):
                    status, _, _ = _post_status(front.address, {"data": [i]})
                    assert status == 200
                s = front._hedge.summary()
                assert s["hedged"] == 0 and s["requests"] == 10
        finally:
            a.stop()
            b.stop()

    def test_front_hedge_injection_suppresses_deterministically(self):
        """A raising FRONT_HEDGE plan blocks the hedge launch: the stalled
        primary answers after its full stall, and the suppression is
        visible in both the injector log and the tracker."""
        fast, slow = _StallWorker(), _StallWorker(stall_s=0.25)
        try:
            with self._front() as front:
                front.register(slow.address)   # rotation starts here
                front.register(fast.address)
                with FaultInjector(seed=CHAOS_SEED).plan(
                        faults.FRONT_HEDGE, every=1) as inj:
                    t0 = time.perf_counter()
                    status, body, _ = _post_status(front.address,
                                                   {"data": [1]}, timeout=15)
                    took = time.perf_counter() - t0
                    assert status == 200 and body["sum"] == 1.0
                    assert took >= 0.22  # paid the stall: hedge suppressed
                    assert len(inj.fired(faults.FRONT_HEDGE)) == 1
                assert front._hedge.summary()["suppressed"] == 1
        finally:
            fast.stop()
            slow.stop()

    def test_hedge_failed_primary_recovers_via_hedge(self):
        """Primary connection-refused + hedge response: the hedge answer
        wins even when the primary fails outright (not just slowly)."""
        fast = _StallWorker()
        try:
            with self._front(init_delay_ms=20.0) as front:
                front.register("http://127.0.0.1:9/")  # dead primary
                front.register(fast.address)
                status, body, _ = _post_status(front.address, {"data": [2]},
                                               timeout=15)
                assert status == 200 and body["sum"] == 2.0
        finally:
            fast.stop()

    def test_quantile_delay_tracks_observed_latency(self):
        from mmlspark_tpu.serving.supervisor import HedgeConfig, HedgeTracker

        t = HedgeTracker(HedgeConfig(quantile=0.9, min_samples=10,
                                     init_delay_ms=77.0, min_delay_ms=1.0))
        assert t.delay_s() == pytest.approx(0.077)  # under min_samples
        for ms in range(1, 101):  # 1..100ms uniform
            t.observe(ms / 1e3)
        assert t.delay_s() == pytest.approx(0.091, rel=0.02)  # ~p90

    def test_hedge_config_validation(self):
        from mmlspark_tpu.serving.supervisor import HedgeConfig, make_hedge

        with pytest.raises(ValueError):
            HedgeConfig(quantile=1.5)
        with pytest.raises(ValueError):
            HedgeConfig(min_delay_ms=10.0, max_delay_ms=1.0)
        assert make_hedge(None) is None
        assert make_hedge(False) is None
        assert make_hedge(True) is not None
        with pytest.raises(ValueError):
            make_hedge(42)


# ---------------------------------------------------------------------------
# AsyncConnectionPool: stale-socket retry honors the request deadline
# ---------------------------------------------------------------------------


class TestPoolDeadlineGate:
    class _DeadWriter:
        def write(self, b):
            pass

        async def drain(self):
            pass

        def close(self):
            pass

        def is_closing(self):
            return False

    class _ClosedReader:
        async def readline(self):
            return b""  # peer closed before the status line

    def _pool_with_stale_checkout(self):
        import asyncio  # noqa: F401 — exercised via asyncio.run below

        from mmlspark_tpu.serving.aio import AsyncConnectionPool

        pool = AsyncConnectionPool()
        calls = []

        async def checkout(key, force_fresh):
            calls.append(force_fresh)
            return (False, (self._ClosedReader(), self._DeadWriter()))

        pool._checkout = checkout
        return pool, calls

    def test_expired_deadline_blocks_stale_retry(self):
        import asyncio

        pool, calls = self._pool_with_stale_checkout()
        dl = Deadline(time.time() - 1)
        with pytest.raises(OSError, match="deadline expired"):
            asyncio.run(pool._request(("h", 80), "POST", "/", b"", None,
                                      deadline=dl))
        # the single retry NEVER fired: one checkout, no fresh connection
        assert calls == [False]

    def test_live_deadline_allows_stale_retry(self):
        import asyncio

        pool, calls = self._pool_with_stale_checkout()
        dl = Deadline.from_timeout(30)
        with pytest.raises(OSError):
            asyncio.run(pool._request(("h", 80), "POST", "/", b"", None,
                                      deadline=dl))
        assert calls == [False, True]  # retried once on a fresh connection

    def test_no_deadline_keeps_legacy_single_retry(self):
        import asyncio

        pool, calls = self._pool_with_stale_checkout()
        with pytest.raises(OSError):
            asyncio.run(pool._request(("h", 80), "POST", "/", b"", None))
        assert calls == [False, True]


# ---------------------------------------------------------------------------
# ReplicaSet placement: a raising device fails the start
# ---------------------------------------------------------------------------


class TestReplicaPlacementFails:
    def test_failing_device_fails_the_start(self):
        """A server asked for R replicas never serves on fewer: the init
        error of one placement propagates out of the constructor."""
        from mmlspark_tpu.serving import ReplicaSet

        def factory(i, dev):
            if dev == "bad-dev":
                raise RuntimeError(f"device {dev} driver init failed")
            return lambda df: df

        with pytest.raises(RuntimeError, match="driver init failed"):
            ReplicaSet(transform_factory=factory, n=3,
                       devices=["dev0", "bad-dev", "dev2"])


# ---------------------------------------------------------------------------
# Brownout controller (serving/supervisor.py)
# ---------------------------------------------------------------------------


class _FakeSLO:
    def __init__(self):
        self.burn = 0.0

    def burn_rates(self):
        return {60: self.burn}


class TestBrownout:
    def _controller(self, slo, log, clock, **kw):
        from mmlspark_tpu.serving.supervisor import (BrownoutController,
                                                     BrownoutStep)

        steps = [BrownoutStep(f"s{i}",
                              lambda i=i: log.append(("apply", i)),
                              lambda i=i: log.append(("revert", i)))
                 for i in range(2)]
        kw.setdefault("enter_burn", 2.0)
        kw.setdefault("exit_burn", 0.5)
        kw.setdefault("hold_s", 1.0)
        kw.setdefault("check_interval_s", 0.0)
        return BrownoutController(slo, steps, clock=lambda: clock[0], **kw)

    def test_degrades_stepwise_and_restores_with_hysteresis(self):
        slo, log, clock = _FakeSLO(), [], [10.0]
        c = self._controller(slo, log, clock)
        slo.burn = 5.0
        assert c.check() == "degrade" and c.step == 1
        clock[0] += 0.5
        assert c.check() is None  # hold_s not elapsed: one step at a time
        clock[0] += 0.6
        assert c.check() == "degrade" and c.step == 2
        clock[0] += 2.0
        assert c.check() is None  # ladder exhausted, burn still high
        # burn drops: restore needs 2*hold_s BELOW exit continuously
        slo.burn = 0.1
        assert c.check() is None          # starts the below-window
        clock[0] += 1.0
        assert c.check() is None          # 1.0 < 2*hold_s
        clock[0] += 1.1
        assert c.check() == "restore" and c.step == 1
        # mid-band burn (between exit and enter): hold steady
        slo.burn = 1.0
        clock[0] += 5.0
        assert c.check() is None and c.step == 1
        assert log == [("apply", 0), ("apply", 1), ("revert", 1)]
        tr = c.summary()["transitions"]
        assert tr == {"degrade": 2, "restore": 1, "rollback": 0}

    def test_journal_and_one_step_rollback(self):
        slo, log, clock = _FakeSLO(), [], [10.0]
        c = self._controller(slo, log, clock)
        slo.burn = 9.0
        c.check()
        assert [e["action"] for e in c.summary()["journal"]] == ["degrade"]
        assert c.rollback() is True and c.step == 0
        assert log == [("apply", 0), ("revert", 0)]
        assert c.rollback() is False  # nothing left to roll back
        actions = [e["action"] for e in c.summary()["journal"]]
        assert actions == ["degrade", "rollback"]

    def test_a_failing_step_never_kills_the_tick(self):
        from mmlspark_tpu.serving.supervisor import (BrownoutController,
                                                     BrownoutStep)

        slo, clock = _FakeSLO(), [10.0]

        def boom():
            raise RuntimeError("knob exploded")

        c = BrownoutController(slo, [BrownoutStep("bad", boom, boom)],
                               enter_burn=2.0, exit_burn=0.5, hold_s=0.0,
                               check_interval_s=0.0,
                               clock=lambda: clock[0])
        slo.burn = 9.0
        assert c.check() == "degrade"  # transition recorded, error eaten
        assert c.step == 1

    def test_requires_slo_and_hysteresis_band(self):
        from mmlspark_tpu.serving.supervisor import BrownoutController

        with pytest.raises(ValueError, match="requires an SLO"):
            BrownoutController(None, [])
        with pytest.raises(ValueError, match="hysteresis"):
            BrownoutController(_FakeSLO(), [], enter_burn=1.0,
                               exit_burn=1.0)

    def test_server_brownout_engages_under_breach_and_surfaces(self):
        """Integration: a server whose every request breaches a 1ms
        objective degrades within a few batches — the batch window
        collapses and /_mmlspark/stats + metrics expose the step."""
        import urllib.request

        from mmlspark_tpu.serving import ServingServer

        def slowish(df):
            time.sleep(0.02)
            return _echo_transform(df)

        with ServingServer(slowish, port=0, max_wait_ms=5.0,
                           slo={"objective_ms": 1.0, "target": 0.99},
                           brownout={"enter_burn": 1.5, "exit_burn": 0.2,
                                     "hold_s": 0.0,
                                     "check_interval_s": 0.0}) as srv:
            for i in range(6):
                status, _, _ = _post(srv.address, {"data": [i]})
                assert status == 200
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/_mmlspark/stats",
                    timeout=10) as resp:
                stats = json.loads(resp.read())
            bo = stats["brownout"]
            assert bo["active"] and bo["step"] >= 1
            assert srv.max_wait_ms == 0.0  # step 1: window collapsed
            assert bo["journal"][0]["action"] == "degrade"
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/_mmlspark/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
            assert "mmlspark_brownout_step" in text
            assert 'mmlspark_brownout_transitions_total{direction="degrade"}' \
                in text

    def test_brownout_off_by_default_and_tenant_pressure(self):
        from mmlspark_tpu.serving import ServingServer, TenantAdmission

        with ServingServer(_echo_transform, port=0) as srv:
            assert srv._brownout is None
        t = TenantAdmission({"a": 1.0, "b": 1.0})
        base = t.quota("a", 100)
        prev = t.set_pressure(0.5)
        assert prev == 1.0
        assert t.quota("a", 100) == base // 2
        t.set_pressure(prev)
        assert t.quota("a", 100) == base


# ---------------------------------------------------------------------------
# Ingest H2D chaos
# ---------------------------------------------------------------------------


class TestIngestChaos:
    def test_injected_h2d_delay_shows_in_timings(self):
        from mmlspark_tpu.parallel.ingest import TransferRing

        batches = [np.ones((4, 4), dtype=np.float32)] * 3
        with FaultInjector().plan(faults.INGEST_H2D, at=(2,), delay_s=0.1,
                                  exc=None):
            ring = TransferRing(iter(batches), depth=1)
            out = list(ring)
        assert len(out) == 3
        h2d = [t.h2d_s for t in ring.stats.records]
        assert h2d[1] >= 0.09  # the injected slow link is visible
        assert h2d[0] < 0.09

    def test_injected_h2d_failure_surfaces_to_consumer(self):
        from mmlspark_tpu.parallel.ingest import TransferRing

        batches = [np.ones((2, 2), dtype=np.float32)] * 4
        with FaultInjector().plan(faults.INGEST_H2D, at=(2,)):
            ring = TransferRing(iter(batches), depth=1)
            with pytest.raises(InjectedFault):
                list(ring)

    def test_h2d_fault_on_deposit_path_never_corrupts_a_slot(self):
        """INGEST_H2D hitting a slot-staged (deposit) batch: the transform
        fails fast, the lease returns to the pool (no leak, no deadlock),
        and a retry produces bitwise-correct output — the slot content was
        never read half-transferred."""
        import jax

        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.fusion import CompileCache, FusedPipelineModel
        from mmlspark_tpu.core.pipeline import PipelineModel
        from mmlspark_tpu.core.schema import ImageSchema
        from mmlspark_tpu.image.featurizer import ImageFeaturizer
        from mmlspark_tpu.image.stages import ImageTransformer
        from mmlspark_tpu.models.module import (Dense, FunctionModel,
                                                GlobalAvgPool, Sequential)

        size = 12
        mod = Sequential([("pool", GlobalAvgPool()), ("head", Dense(3))],
                         name="tinycnn")
        params, _ = mod.init(jax.random.PRNGKey(0), (size, size, 3))
        backbone = FunctionModel(mod, params, (size, size, 3),
                                 layer_names=["head", "pool"],
                                 name="tinycnn")
        pm = PipelineModel([
            ImageTransformer().resize(size, size).flip(1),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
            .set_model(backbone)])

        rng = np.random.default_rng(int(CHAOS_SEED))
        obj = np.empty(20, dtype=object)
        for i in range(20):
            obj[i] = ImageSchema.make(
                rng.integers(0, 256, (16, 16, 3), dtype=np.uint8),
                f"img{i}")
        df = DataFrame.from_dict({"image": obj}, num_partitions=1)

        def feats(model, frame):
            pdf = model.transform(frame).to_pandas()
            col = next(c for c in pdf.columns if c != "image")
            return np.stack([np.asarray(v) for v in pdf[col].to_list()])

        ref = feats(FusedPipelineModel(pm.stages, cache=CompileCache(),
                                       slot_staging=False), df)
        dep = FusedPipelineModel(pm.stages, cache=CompileCache())
        with FaultInjector().plan(faults.INGEST_H2D, at=(2,)):
            with pytest.raises(InjectedFault):
                dep.transform(df)
        # lease released on the failure path: the pool still hands out
        # every buffer (a leak would starve or deadlock this retry)
        got = feats(dep, df)
        np.testing.assert_array_equal(got, ref)
        s = dep.last_ingest_stats.summary()
        assert s.get("slot_deposits", 0) > 0
        # slow-link variant: an injected DELAY on the deposit path keeps
        # output correctness (the slot is not recycled mid-transfer)
        with FaultInjector().plan(faults.INGEST_H2D, at=(1,),
                                  delay_s=0.05, exc=None):
            np.testing.assert_array_equal(feats(dep, df), ref)


# ---------------------------------------------------------------------------
# GBDT checkpoint/resume
# ---------------------------------------------------------------------------


def _synth_binary(n=300, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    logit = X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 0]
    y = (logit + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
    return X, y


class TestGBDTCheckpointResume:
    def _params(self, **kw):
        from mmlspark_tpu.gbdt import TrainParams

        base = dict(objective="binary", num_iterations=8, num_leaves=7,
                    min_data_in_leaf=5, bagging_fraction=0.8,
                    bagging_freq=1, seed=3)
        base.update(kw)
        return TrainParams(**base)

    def test_interrupted_resume_is_identical(self, tmp_path):
        """Train interrupted at iteration k (injected preemption) then
        resumed must produce the SAME model as an uninterrupted run."""
        from mmlspark_tpu.gbdt import booster as B
        from mmlspark_tpu.gbdt.checkpoint import CheckpointConfig

        X, y = _synth_binary()
        p = self._params()
        full = B.train(p, X, y, checkpoint=CheckpointConfig(
            str(tmp_path / "full.ckpt"), every_k=3))

        ckpt = str(tmp_path / "interrupted.ckpt")
        with FaultInjector(seed=0).plan(faults.TRAIN_STEP, at=(6,)):
            with pytest.raises(InjectedFault):
                B.train(p, X, y,
                        checkpoint=CheckpointConfig(ckpt, every_k=3))
        # the pre-preemption checkpoint is on disk at iteration 3
        from mmlspark_tpu.gbdt.checkpoint import load_checkpoint

        assert load_checkpoint(ckpt)["iteration"] == 3
        resumed = B.train(p, X, y,
                          checkpoint=CheckpointConfig(ckpt, every_k=3))
        assert resumed.to_string() == full.to_string()
        np.testing.assert_array_equal(resumed.raw_predict(X),
                                      full.raw_predict(X))

    def test_checkpoint_cadence_and_final(self, tmp_path):
        from mmlspark_tpu.gbdt import booster as B
        from mmlspark_tpu.gbdt.checkpoint import (CheckpointConfig,
                                                  load_checkpoint)

        X, y = _synth_binary()
        ckpt = str(tmp_path / "m.ckpt")
        B.train(self._params(), X, y,
                checkpoint=CheckpointConfig(ckpt, every_k=3))
        ck = load_checkpoint(ckpt)
        assert ck["iteration"] == 8  # final checkpoint written at the end

    def test_param_mismatch_refuses_resume(self, tmp_path):
        from mmlspark_tpu.gbdt import booster as B
        from mmlspark_tpu.gbdt.checkpoint import CheckpointConfig

        X, y = _synth_binary()
        ckpt = str(tmp_path / "m.ckpt")
        B.train(self._params(), X, y,
                checkpoint=CheckpointConfig(ckpt, every_k=3))
        with pytest.raises(ValueError, match="different train params"):
            B.train(self._params(learning_rate=0.27), X, y,
                    checkpoint=CheckpointConfig(ckpt, every_k=3))

    def test_atomicity_survives_crash_mid_save(self, tmp_path, monkeypatch):
        """A crash inside the checkpoint write leaves the previous complete
        checkpoint (tmp + rename: never a torn file)."""
        from mmlspark_tpu.gbdt.checkpoint import (load_checkpoint,
                                                  save_checkpoint)

        path = str(tmp_path / "c.ckpt")
        args = dict(params_dict={"a": 1}, model_string="tree v1",
                    scores=np.zeros((4, 1)), rng_state={"s": 1},
                    bag_mask=np.ones(4, dtype=bool), best_val=0.5,
                    best_iter=2, rounds_no_improve=0)
        save_checkpoint(path, iteration=3, **args)

        def replace_boom(a, b):
            raise OSError(errno.EIO, "injected crash mid-rename")

        monkeypatch.setattr(os, "replace", replace_boom)
        with pytest.raises(OSError):
            save_checkpoint(path, iteration=4, **args)
        monkeypatch.undo()
        ck = load_checkpoint(path)
        assert ck["iteration"] == 3  # previous complete checkpoint intact


# ---------------------------------------------------------------------------
# DNN train loop: preemption hook + checkpoint/resume
# ---------------------------------------------------------------------------


class TestDNNTrainLoop:
    def _setup(self):
        from mmlspark_tpu.models import training as T
        from mmlspark_tpu.models.module import Dense, Sequential

        module = Sequential([("fc", Dense(2))], name="tiny")
        opt = T.make_optimizer(learning_rate=0.1)
        state = T.init_train_state(module, (4,), opt, seed=0)
        step = T.compile_train_step(module, opt)
        return T, state, step

    @staticmethod
    def _batches(n, seed=0):
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            x = rng.normal(size=(8, 4)).astype(np.float32)
            y = (x[:, 0] > 0).astype(np.int32)
            out.append({"x": x, "y": y})
        return out

    def test_preemption_signal_checkpoints_and_stops(self, tmp_path):
        T, state, step = self._setup()
        ckpt = str(tmp_path / "dnn_ckpt")
        guard = T.PreemptionGuard()
        batches = self._batches(10)

        def preempting(batches):
            for i, b in enumerate(batches):
                if i == 4:
                    guard.request()  # SIGTERM equivalent, delivered manually
                yield b

        res = T.run_train_loop(state, step, preempting(batches),
                               checkpoint_path=ckpt, every_k=100,
                               guard=guard)
        assert res.preempted and res.steps_run == 4
        assert os.path.isdir(ckpt) or os.path.exists(ckpt)

        # resume finishes the remaining steps
        T2, state2, step2 = self._setup()
        res2 = T.run_train_loop(state2, step2, self._batches(10),
                                checkpoint_path=ckpt, guard=None)
        assert not res2.preempted and res2.steps_run == 6
        assert int(np.asarray(res2.state.step)) == 10

    def test_resume_matches_uninterrupted(self, tmp_path):
        T, state, step = self._setup()
        batches = self._batches(8)
        full = T.run_train_loop(state, step, batches)
        assert full.steps_run == 8

        T2, stateA, stepA = self._setup()
        ckpt = str(tmp_path / "halfway")
        half = T.run_train_loop(stateA, stepA, batches[:4],
                                checkpoint_path=ckpt, every_k=4)
        assert half.steps_run == 4
        T3, stateB, stepB = self._setup()
        res = T.run_train_loop(stateB, stepB, batches,
                               checkpoint_path=ckpt, every_k=100)
        assert res.steps_run == 4  # only the un-trained suffix ran
        import jax

        for a, b in zip(jax.tree.leaves(res.state.params),
                        jax.tree.leaves(full.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_train_step_injection_point_fires(self):
        T, state, step = self._setup()
        with FaultInjector(seed=0).plan(faults.TRAIN_STEP, at=(3,)) as inj:
            with pytest.raises(InjectedFault):
                T.run_train_loop(state, step, self._batches(5))
            assert len(inj.fired(faults.TRAIN_STEP)) == 1

    def test_preemption_guard_signal_handler_roundtrip(self):
        import signal as S

        T, _, _ = self._setup()
        prev = S.getsignal(S.SIGUSR1)
        guard = T.PreemptionGuard(signals=(S.SIGUSR1,))
        with guard:
            os.kill(os.getpid(), S.SIGUSR1)
            deadline = time.time() + 2
            while not guard.requested() and time.time() < deadline:
                time.sleep(0.01)
            assert guard.requested()
        # handler restored after exit
        assert S.getsignal(S.SIGUSR1) == prev


class TestCompileCacheChaos:
    """Persistent compile-cache degradation contract (serving/fleet/cache):
    every load/store failure — injected or on-disk — is an accounted
    counter and a recompile, never a crash or a blocked serving path."""

    KEY = ("segF", (("col", (4,), "float32"),))

    def _compiled(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones((4,), jnp.float32)
        return jax.jit(lambda v: v * 3.0).lower(x).compile()

    def _populated(self, tmp_path):
        from mmlspark_tpu.core.device_stage import CompileCache
        from mmlspark_tpu.serving.fleet import PersistentCompileCache

        tier = PersistentCompileCache(str(tmp_path))
        cache = CompileCache()
        cache.attach_persistent(tier)
        cache.get(self.KEY, self._compiled, label="segF", shape="b4")
        assert tier.stats()["stores"] == 1
        return tier

    def test_load_fault_degrades_to_accounted_recompile(self, tmp_path):
        pytest.importorskip("jax")
        import jax.numpy as jnp

        from mmlspark_tpu.core.device_stage import CompileCache
        from mmlspark_tpu.serving.fleet import PersistentCompileCache

        self._populated(tmp_path)
        cache = CompileCache()
        tier = PersistentCompileCache(str(tmp_path))
        cache.attach_persistent(tier)
        built = []

        def builder():
            built.append(1)
            return self._compiled()

        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.COMPILECACHE_LOAD, every=1) as inj:
            fn = cache.get(self.KEY, builder, label="segF", shape="b4")
            assert len(inj.fired(faults.COMPILECACHE_LOAD)) == 1
        # the populated entry was unreachable: serving recompiled and the
        # failure is a counter, not an exception
        assert built == [1]
        assert tier.stats()["load_errors"] == 1
        x = jnp.arange(4, dtype=jnp.float32)
        assert np.allclose(np.asarray(fn(x)), np.asarray(x) * 3.0)
        # honest memory-tier accounting: this WAS a compile
        assert cache.stats()["misses"] == 1

    def test_store_fault_never_blocks_serving(self, tmp_path):
        pytest.importorskip("jax")
        import jax.numpy as jnp

        from mmlspark_tpu.core.device_stage import CompileCache
        from mmlspark_tpu.serving.fleet import PersistentCompileCache

        tier = PersistentCompileCache(str(tmp_path))
        cache = CompileCache()
        cache.attach_persistent(tier)
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.COMPILECACHE_STORE, at=(1,)) as inj:
            fn = cache.get(self.KEY, self._compiled,
                           label="segF", shape="b4")
            assert len(inj.fired(faults.COMPILECACHE_STORE)) == 1
        x = jnp.arange(4, dtype=jnp.float32)
        assert np.allclose(np.asarray(fn(x)), np.asarray(x) * 3.0)
        s = tier.stats()
        assert s["store_errors"] == 1 and s["stores"] == 0
        assert tier.entry_count() == 0  # nothing half-written
        # the in-process cache is intact: the next request is a memory hit
        fn2 = cache.get(self.KEY, lambda: pytest.fail("must be resident"),
                        label="segF", shape="b4")
        assert fn2 is fn

    def test_warm_fault_shrinks_but_never_fails_pod_start(self, tmp_path):
        pytest.importorskip("jax")
        from mmlspark_tpu.core.device_stage import CompileCache
        from mmlspark_tpu.serving.fleet import PersistentCompileCache

        self._populated(tmp_path)
        tier = PersistentCompileCache(str(tmp_path))
        cache = CompileCache()
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.COMPILECACHE_LOAD, every=1):
            out = tier.warm(cache)
        assert out["warmed"] == 0 and out["errors"] == 1
        assert cache.stats()["entries"] == 0
        # without injection the same directory warms fine
        out2 = PersistentCompileCache(str(tmp_path)).warm(cache)
        assert out2["warmed"] == 1

    def test_on_disk_corruption_matrix(self, tmp_path):
        """Truncated tail, foreign magic, garbage payload: each load
        degrades to an accounted miss; the chaos seed picks the byte
        ranges so the matrix varies across CI lanes."""
        pytest.importorskip("jax")
        from mmlspark_tpu.serving.fleet import PersistentCompileCache
        from mmlspark_tpu.serving.fleet.cache import SUFFIX

        rng = np.random.default_rng(CHAOS_SEED)
        for mode in ("truncate", "magic", "garbage"):
            sub = tmp_path / mode
            sub.mkdir()
            self._populated(sub)
            (name,) = [n for n in os.listdir(sub) if n.endswith(SUFFIX)]
            path = os.path.join(str(sub), name)
            blob = open(path, "rb").read()
            if mode == "truncate":
                cut = int(rng.integers(1, len(blob)))
                blob = blob[:cut]
            elif mode == "magic":
                blob = b"XXXXXX" + blob[6:]
            else:
                lo = int(rng.integers(0, max(1, len(blob) - 64)))
                blob = blob[:lo] + bytes(rng.integers(
                    0, 256, 64, dtype=np.uint8)) + blob[lo + 64:]
            with open(path, "wb") as fh:
                fh.write(blob)
            tier = PersistentCompileCache(str(sub))
            assert tier.load(self.KEY, label="segF", shape="b4") is None, \
                mode
            st = tier.stats()
            # every outcome is accounted: either a parse failure or (for
            # a garbage run that shredded the header length) a miss
            assert st["load_errors"] + st["misses"] >= 1, mode


# ---------------------------------------------------------------------------
# Model lifecycle: crash mid-swap / mid-checkpoint (serving/lifecycle)
# ---------------------------------------------------------------------------


def _lc_sparse_rows(n, seed=0, nnz=3):
    rng = np.random.default_rng(seed)
    rows, labels = [], []
    for _ in range(n):
        idx = rng.choice(64, size=nnz, replace=False)
        rows.append({"indices": [int(i) for i in idx],
                     "values": [float(v) for v in
                                rng.normal(size=nnz).round(3)]})
        labels.append(float(rng.integers(0, 2)))
    return rows, labels


class TestLifecycleChaos:
    """The two lifecycle chaos seams: ``lifecycle.swap`` fires BEFORE any
    registry/executor state mutates (a crash mid-swap must leave the
    incumbent serving), ``lifecycle.checkpoint`` fires before the atomic
    checkpoint write (resume + journal replay must be bitwise)."""

    def _plane(self, candidate, steps=(0.0,)):
        pytest.importorskip("jax")
        from mmlspark_tpu.serving.lifecycle import (CanaryConfig,
                                                    LifecyclePlane)

        clock = [1_000.0]
        plane = LifecyclePlane(
            CanaryConfig(shadow_fraction=0.0, steps=steps, hold_s=0.0,
                         min_step_requests=0, check_interval_s=0.0,
                         objective_ms=60_000.0),
            clock=lambda: clock[0])
        plane.registry.adopt_live(
            lambda df: df.with_column("reply", lambda p: p["value"]),
            version="base")
        plane.deploy(candidate, version="cand")
        return plane, clock

    def test_crash_mid_swap_keeps_registry_intact(self):
        """An injected crash inside swap_live (fired before any mutation)
        leaves the incumbent live and the candidate retriable; the next
        tick completes the promotion."""
        from mmlspark_tpu.serving.lifecycle import CANARY

        plane, clock = self._plane(
            lambda df: df.with_column("reply", lambda p: p["value"]))
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.LIFECYCLE_SWAP, at=(1,)):
            clock[0] += 1.0
            plane.tick(0.01)  # promotion attempt 1: seam raises mid-swap
            assert any(e["action"] == "swap_failed"
                       for e in plane.controller.journal)
            assert plane.registry.live.version == "base"
            assert plane.registry.get("cand").state == CANARY
            # traffic still resolves through the incumbent
            out = plane(_lc_df([b"hello"]))
            assert list(out.collect()["reply"]) == [b"hello"]
            clock[0] += 1.0
            plane.tick(0.01)  # seam passes -> promotion completes
        assert plane.registry.live.version == "cand"

    def test_crash_mid_swap_e2e_incumbent_replies_bitwise(self):
        """Through a live server with a DIVERGING candidate and the swap
        seam raising on every attempt: clients only ever see the
        incumbent's bytes (the candidate never takes traffic at share 0,
        and the repeated failed promotions never half-install it)."""
        pytest.importorskip("jax")
        from mmlspark_tpu.serving.server import ServingServer

        def echo(df):
            return df.with_column("reply", lambda p: p["value"])

        def diverging(df):
            return df.with_column("reply",
                                  lambda p: [b"WRONG" for _ in p["id"]])

        srv = ServingServer(echo, port=0, max_wait_ms=1.0,
                            lifecycle={"shadow_fraction": 0.0,
                                       "steps": (0.0,), "hold_s": 0.0,
                                       "min_step_requests": 0,
                                       "check_interval_s": 0.0,
                                       "objective_ms": 60_000.0})
        with FaultInjector(seed=CHAOS_SEED).plan(
                faults.LIFECYCLE_SWAP, every=1, times=-1):
            with srv:
                plane = srv._lifecycle
                plane.deploy(diverging, version="bad")
                deadline = time.monotonic() + 20.0
                failed = 0
                i = 0
                while time.monotonic() < deadline:
                    body = json.dumps({"i": i}).encode()
                    req = urllib.request.Request(srv.address, data=body,
                                                 method="POST")
                    with urllib.request.urlopen(req, timeout=15) as resp:
                        assert resp.read() == body  # incumbent, bitwise
                    i += 1
                    failed = sum(1 for e in plane.controller.journal
                                 if e["action"] == "swap_failed")
                    if failed >= 2:
                        break
                assert failed >= 2
                assert plane.registry.live.version != "bad"
                assert plane.controller.promotions == 0

    def test_checkpoint_crash_resume_is_bitwise(self):
        """Crash before checkpoint k's write: the on-disk checkpoint stays
        at k-1, and a fresh trainer's resume + journal replay reproduces
        the uninterrupted run's state bitwise. The chaos seed picks k."""
        pytest.importorskip("jax")
        import tempfile

        from mmlspark_tpu.serving.lifecycle import (OnlineTrainer,
                                                    VWOnlineAdapter)
        from mmlspark_tpu.vw.learner import LearnerConfig

        cfg = LearnerConfig(num_bits=8)
        rows, labels = _lc_sparse_rows(24, seed=CHAOS_SEED)
        crash_at = 2 + CHAOS_SEED % 3

        with tempfile.TemporaryDirectory() as td:
            ref = OnlineTrainer(VWOnlineAdapter(cfg),
                                os.path.join(td, "ref.jsonl"),
                                os.path.join(td, "ref.ck"), batch_rows=4)
            ref.feed(rows, labels)
            ref.train_pending()
            ref_state = ref.adapter.to_json(ref.state)
            ref.stop()

            t1 = OnlineTrainer(VWOnlineAdapter(cfg),
                               os.path.join(td, "fb.jsonl"),
                               os.path.join(td, "ck.json"), batch_rows=4)
            t1.feed(rows, labels)
            with FaultInjector(seed=CHAOS_SEED).plan(
                    faults.LIFECYCLE_CHECKPOINT, at=(crash_at,)):
                with pytest.raises(InjectedFault):
                    t1.train_pending()
            t1.journal.close()  # crash: no stop(), no further writes
            with open(os.path.join(td, "ck.json"),
                      encoding="utf-8") as fh:
                assert json.load(fh)["step"] == crash_at - 1

            t2 = OnlineTrainer(VWOnlineAdapter(cfg),
                               os.path.join(td, "fb.jsonl"),
                               os.path.join(td, "ck.json"), batch_rows=4)
            assert t2.resume() is True
            assert t2.step == crash_at - 1
            t2.train_pending()
            assert t2.consumed == 24
            assert t2.adapter.to_json(t2.state) == ref_state
            t2.stop()


def _lc_df(values):
    from mmlspark_tpu.core.dataframe import DataFrame

    h = np.empty(len(values), dtype=object)
    for i in range(len(values)):
        h[i] = {}
    return DataFrame.from_dict({
        "id": np.arange(len(values), dtype=np.int64),
        "value": np.asarray(values, dtype=object),
        "headers": h,
    })
