"""QoS / tail-latency plane tests (docs/QOS.md).

Covers the four defenses end to end: hedged reads (adaptive delay,
loser cancellation, counters), per-client admission control (503 +
Retry-After, client retry honor, -serveProcs budget split), group
commit (byte identity, flush reduction, crash consistency), and
queue-depth-aware assignment (heartbeat fields → p2c pick), plus the
vid_map circuit breaker and the weedload extensions that drove the
A/Bs of `git show 484f53f:BENCH_r09.json`.
"""

from __future__ import annotations

import json
import os
import queue
import socket
import tempfile
import threading
import time
import urllib.request

import pytest

from seaweedfs_tpu import qos
from seaweedfs_tpu.client import vid_map as vm
from seaweedfs_tpu.qos import hedge
from seaweedfs_tpu.qos.admission import AdmissionController, client_key
from seaweedfs_tpu.qos.group_commit import GroupCommitter
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import CookieMismatch, Volume

from tests.faults import SlowReplicaProxy


# ----------------------------------------------------------------------
# helpers


class _StubServer:
    """Minimal HTTP/1.1 blob server for hedge tests: serves a fixed
    body, optionally after a delay; records request headers and whether
    each response write completed (the loser-cancellation probe)."""

    def __init__(
        self,
        body: bytes = b"stub-body",
        delay_s: float = 0.0,
        split_response: bool = False,
    ):
        self.body = body
        self.delay_s = delay_s
        # split_response: head first, then body after a pause — the
        # only way a test can OBSERVE a client-side cancel, since one
        # small sendall to a freshly-closed socket still lands in the
        # kernel buffer without error
        self.split_response = split_response
        self.requests: list[dict] = []
        self.completed_writes = 0
        self.broken_writes = 0
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(16)
        self._stop = threading.Event()
        threading.Thread(target=self._loop, daemon=True).start()

    @property
    def addr(self) -> str:
        return "127.0.0.1:%d" % self._sock.getsockname()[1]

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _serve(self, conn) -> None:
        try:
            buf = b""
            while b"\r\n\r\n" not in buf:
                chunk = conn.recv(65536)
                if not chunk:
                    return
                buf += chunk
            head = buf.split(b"\r\n\r\n", 1)[0].decode("latin-1")
            headers = {}
            for line in head.split("\r\n")[1:]:
                k, _, v = line.partition(":")
                headers[k.strip().lower()] = v.strip()
            self.requests.append(headers)
            if self.delay_s:
                time.sleep(self.delay_s)
            head = (
                b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n"
                b"Connection: close\r\n\r\n" % len(self.body)
            )
            try:
                if self.split_response:
                    conn.sendall(head)
                    time.sleep(0.3)
                    conn.sendall(self.body)
                else:
                    conn.sendall(head + self.body)
                self.completed_writes += 1
            except OSError:
                self.broken_writes += 1
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def stop(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass


def _mk_needle(i: int, payload: bytes = b"", cookie: int = 0x1234) -> Needle:
    n = Needle(cookie=cookie, id=1000 + i, data=payload or b"qos-%d" % i * 10)
    n.set_has_last_modified_date()
    n.last_modified = 1700000000
    return n


@pytest.fixture(autouse=True)
def _fresh_breaker():
    vm._broken_until.clear()
    yield
    vm._broken_until.clear()


# ----------------------------------------------------------------------
# hedged reads


class TestHedge:
    def test_slow_primary_hedge_wins_and_loser_cancelled(self):
        """The headline behavior: primary stalls, the hedge fires to
        the second replica, wins, and the slow attempt's connection is
        torn down (no duplicate body consumed); counters agree."""
        slow = _StubServer(body=b"A" * 64, delay_s=2.0, split_response=True)
        fast = _StubServer(body=b"A" * 64, delay_s=0.0)
        stats: dict = {}
        try:
            os.environ["WEED_QOS_HEDGE_MS"] = "30"
            data, _ = hedge.download(
                [f"{slow.addr}/1,00000001", f"{fast.addr}/1,00000001"],
                key="t1", stats=stats,
            )
        finally:
            os.environ.pop("WEED_QOS_HEDGE_MS", None)
        assert data == b"A" * 64
        assert stats.get("fired") == 1
        assert stats.get("won") == 1
        assert stats.get("cancelled") == 1
        # the hedged attempt carried the hop header; the primary didn't
        assert any(qos.HEDGE_HEADER in h for h in fast.requests)
        assert all(qos.HEDGE_HEADER not in h for h in slow.requests)
        # exactly ONE body was consumed by the driver; the slow server's
        # split write lands on a closed socket (give its delayed reply
        # time: 2s stall + 0.3s split pause)
        time.sleep(2.6)
        assert slow.broken_writes == 1, (
            f"loser not cancelled: completed={slow.completed_writes}"
        )
        slow.stop()
        fast.stop()

    def test_fast_primary_no_hedge(self):
        fast = _StubServer(body=b"B" * 16)
        backup = _StubServer(body=b"B" * 16)
        stats: dict = {}
        try:
            data, _ = hedge.download(
                [f"{fast.addr}/2,00000002", f"{backup.addr}/2,00000002"],
                key="t2", stats=stats,
            )
            assert data == b"B" * 16
            assert stats.get("fired", 0) == 0
            assert backup.requests == []
        finally:
            fast.stop()
            backup.stop()

    def test_attempt_pool_reuses_threads(self):
        """ROADMAP tail-latency follow-on: hedged-capable GETs ride a
        reusable attempt-worker pool instead of spawning 1-2 fresh
        threads each. After a warm-up, a burst of reads must not grow
        the pool's lifetime thread count (reuse) nor the process's live
        thread count beyond the parked-worker cap (no leak)."""
        fast = _StubServer(body=b"P" * 32)
        backup = _StubServer(body=b"P" * 32)
        try:
            urls = [f"{fast.addr}/9,00000009", f"{backup.addr}/9,00000009"]
            for _ in range(4):  # warm the pool
                hedge.download(urls, key="pool-warm")
            spawned_before = hedge._ATTEMPTS.spawned
            live_before = threading.active_count()
            for _ in range(30):
                data, _ = hedge.download(urls, key="pool-test")
                assert data == b"P" * 32
            assert hedge._ATTEMPTS.spawned - spawned_before <= 2, (
                "attempt pool is not reusing workers: "
                f"{hedge._ATTEMPTS.spawned - spawned_before} fresh "
                "threads for 30 sequential reads"
            )
            # live threads: at most the parked-worker cap over baseline
            # (stub servers spawn-and-exit per connection; give the
            # tail a moment to drain)
            time.sleep(0.2)
            assert threading.active_count() <= live_before + \
                hedge._AttemptPool._MAX_IDLE
        finally:
            fast.stop()
            backup.stop()

    def test_primary_connect_failure_fails_over(self):
        """A dead primary shouldn't wait out the delay-then-timeout
        dance: the failure reroutes to the replica immediately and the
        breaker demotes the dead node."""
        fast = _StubServer(body=b"C" * 16)
        dead_port = socket.socket()
        dead_port.bind(("127.0.0.1", 0))
        dead = "127.0.0.1:%d" % dead_port.getsockname()[1]
        dead_port.close()  # nothing listens here now
        try:
            data, _ = hedge.download(
                [f"{dead}/3,00000003", f"{fast.addr}/3,00000003"], key="t3"
            )
            assert data == b"C" * 16
            assert vm.penalized(dead)
        finally:
            fast.stop()

    def test_kill_switch_restores_single_attempt(self, monkeypatch):
        fast = _StubServer(body=b"D" * 16)
        backup = _StubServer(body=b"D" * 16)
        monkeypatch.setenv("WEED_QOS", "0")
        try:
            data, _ = hedge.download(
                [f"{fast.addr}/4,00000004", f"{backup.addr}/4,00000004"],
                key="t4",
            )
            assert data == b"D" * 16
            assert backup.requests == []  # never contacted
        finally:
            fast.stop()
            backup.stop()

    def test_adaptive_delay_tracks_quantile(self):
        tr = hedge.LatencyTracker()
        key = "vol9"
        # before history: the configured initial delay
        assert tr.delay_s(key) == pytest.approx(0.025, abs=1e-3)
        for _ in range(64):
            tr.record(key, 0.004)
        d = tr.delay_s(key)
        assert 0.003 <= d <= 0.006  # hugs the volume's own p95

    def test_slow_replica_proxy_delays_responses(self):
        srv = _StubServer(body=b"E" * 32)
        proxy = SlowReplicaProxy(srv.addr, delay_s=0.15)
        try:
            t0 = time.perf_counter()
            with urllib.request.urlopen(
                f"http://{proxy.addr}/5,00000005", timeout=5
            ) as r:
                body = r.read()
            assert body == b"E" * 32
            assert time.perf_counter() - t0 >= 0.14
            assert proxy.responses_delayed >= 1
        finally:
            proxy.stop()
            srv.stop()


# ----------------------------------------------------------------------
# vid_map circuit breaker


class TestBreaker:
    def test_lookup_demotes_failed_replica_until_ttl(self):
        m = vm.VidMap()
        m.add_location(7, vm.Location("h1:80", "h1:80"))
        m.add_location(7, vm.Location("h2:80", "h2:80"))
        vm.note_failure("h1:80", now=time.time())
        for _ in range(4):  # every rotation, not just alternate ones
            urls = m.lookup_file_id("7,00000007")
            assert urls[0] == "http://h2:80/7,00000007"
        # TTL expiry restores rotation
        vm._broken_until["h1:80"] = time.time() - 0.01
        firsts = {m.lookup_file_id("7,00000007")[0] for _ in range(4)}
        assert len(firsts) == 2

    def test_all_penalized_keeps_original_order(self):
        vm.note_failure("a:1")
        vm.note_failure("b:1")
        urls = vm.order_by_health(["a:1/9,x", "b:1/9,x"])
        assert urls == ["a:1/9,x", "b:1/9,x"]

    def test_success_clears_penalty(self):
        vm.note_failure("c:1")
        assert vm.penalized("c:1")
        vm.note_success("c:1")
        assert not vm.penalized("c:1")


# ----------------------------------------------------------------------
# admission control


class _FakeHandler:
    def __init__(self, headers=None, addr=("10.0.0.9", 1234)):
        from seaweedfs_tpu.util.httpd import FastHeaders

        self.headers = FastHeaders()
        for k, v in (headers or {}).items():
            self.headers[k.lower()] = v
        self.client_address = addr
        self.replies = []
        self.close_connection = False
        self.command = "GET"
        self._trace_status = 0

    def fast_reply(self, status, body=b"", headers=None):
        self._trace_status = status
        self.replies.append((status, body, headers))


class TestAdmission:
    def test_client_key_prefers_s3_access_key(self):
        h = _FakeHandler({
            "Authorization":
                "AWS4-HMAC-SHA256 Credential=AKIDEXAMPLE/20130524/us-east-1/"
                "s3/aws4_request, SignedHeaders=host, Signature=abc"
        })
        assert client_key(h) == "AKIDEXAMPLE"
        h2 = _FakeHandler({"Authorization": "AWS AKLEGACY:sig=="})
        assert client_key(h2) == "AKLEGACY"
        h3 = _FakeHandler()
        assert client_key(h3) == "10.0.0.9"

    def test_token_bucket_sheds_with_retry_after(self):
        ctrl = AdmissionController(rate=2.0, burst=2.0, label="t")
        now = 1000.0
        assert ctrl.admit("k", now) is None
        assert ctrl.admit("k", now) is None
        retry = ctrl.admit("k", now)
        assert retry is not None and retry > 0
        # refill: half a second restores one token
        assert ctrl.admit("k", now + 0.5) is None
        # other clients unaffected
        assert ctrl.admit("other", now) is None

    def test_serveprocs_divides_budget(self):
        """Satellite: admission keyed correctly behind -serveProcs —
        each sibling process enforces 1/N of the global budget so the
        group total stays what the operator configured."""
        whole = AdmissionController(rate=8.0, burst=8.0, procs=1)
        quarter = AdmissionController(rate=8.0, burst=8.0, procs=4)
        assert quarter.rate == pytest.approx(whole.rate / 4)
        assert quarter.burst == pytest.approx(whole.burst / 4)
        now = 0.0
        admitted = sum(
            1 for _ in range(8) if quarter.admit("k", now) is None
        )
        assert admitted == 2  # 8 burst / 4 procs

    def test_inflight_cap_sheds_any_client(self):
        ctrl = AdmissionController(rate=0.0, max_inflight=1, label="t")
        h = _FakeHandler()
        entered = threading.Event()
        release = threading.Event()

        def slow_method(handler):
            entered.set()
            release.wait(5)

        t = threading.Thread(target=ctrl.gate, args=(slow_method, h))
        t.start()
        assert entered.wait(5)
        h2 = _FakeHandler()
        ctrl.gate(lambda _h: None, h2)
        release.set()
        t.join(5)
        assert h2.replies and h2.replies[0][0] == 503
        assert h2.replies[0][2]["Retry-After"]
        # capacity restored after the slow request drained
        h3 = _FakeHandler()
        ctrl.gate(lambda _h: None, h3)
        assert not h3.replies

    def test_inflight_cap_atomic_under_burst(self):
        """Regression (review): the cap check and the in-flight
        increment must share one lock hold — a simultaneous burst of N
        threads must never see more than max_inflight in service."""
        ctrl = AdmissionController(rate=0.0, max_inflight=2, label="t")
        live = []
        peak = []
        lock = threading.Lock()
        release = threading.Event()
        barrier = threading.Barrier(12)

        def method(handler):
            with lock:
                live.append(1)
                peak.append(len(live))
            release.wait(5)
            with lock:
                live.pop()

        def run():
            barrier.wait(5)
            ctrl.gate(method, _FakeHandler())

        ts = [threading.Thread(target=run) for _ in range(12)]
        for t in ts:
            t.start()
        time.sleep(0.3)
        release.set()
        for t in ts:
            t.join(5)
        assert peak and max(peak) <= 2, f"cap breached: peak={max(peak)}"

    def test_kill_switch_admits_everything(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS", "0")
        ctrl = AdmissionController(rate=0.001, burst=0.001)
        assert all(ctrl.admit("k") is None for _ in range(50))

    def test_env_flip_mid_flight_never_underflows_inflight(
        self, monkeypatch
    ):
        """Regression (review): with admission env-disabled, gate()
        must not decrement an in-flight it never incremented — the
        underflow would silently widen the cap once re-enabled."""
        ctrl = AdmissionController(rate=0.0, max_inflight=2, label="t")
        monkeypatch.setenv("WEED_QOS_ADMISSION", "0")
        for _ in range(5):
            ctrl.gate(lambda _h: None, _FakeHandler())
        assert ctrl.status()["Inflight"] == 0
        monkeypatch.delenv("WEED_QOS_ADMISSION")
        assert ctrl.inflight() == 0

    def test_http_call_honors_retry_after_with_jitter(self):
        """Satellite: a 503 + Retry-After from admission control is
        retried (with a jittered wait), not surfaced — one shed plus
        one success looks like one slow request to the caller."""
        from seaweedfs_tpu.client import operation as op

        hits = []

        class _Once:
            def __init__(self):
                self.sock = socket.socket()
                self.sock.setsockopt(
                    socket.SOL_SOCKET, socket.SO_REUSEADDR, 1
                )
                self.sock.bind(("127.0.0.1", 0))
                self.sock.listen(8)
                self.addr = "127.0.0.1:%d" % self.sock.getsockname()[1]
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                while True:
                    try:
                        conn, _ = self.sock.accept()
                    except OSError:
                        return
                    buf = b""
                    while b"\r\n\r\n" not in buf:
                        c = conn.recv(65536)
                        if not c:
                            break
                        buf += c
                    hits.append(time.perf_counter())
                    if len(hits) == 1:
                        conn.sendall(
                            b"HTTP/1.1 503 Service Unavailable\r\n"
                            b"Retry-After: 0.2\r\n"
                            b"Content-Length: 0\r\nConnection: close\r\n\r\n"
                        )
                    else:
                        conn.sendall(
                            b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n"
                            b"Connection: close\r\n\r\nok"
                        )
                    conn.close()

        srv = _Once()
        try:
            status, _, body = op.http_call("GET", f"{srv.addr}/x")
            assert status == 200 and body == b"ok"
            assert len(hits) == 2
            # the jittered wait honored at least half the server's hint
            assert hits[1] - hits[0] >= 0.099
        finally:
            srv.sock.close()

    def test_http_call_passes_503_through_when_qos_off(self, monkeypatch):
        from seaweedfs_tpu.client import operation as op

        monkeypatch.setenv("WEED_QOS", "0")
        calls = []

        class _Always503:
            def __init__(self):
                self.sock = socket.socket()
                self.sock.bind(("127.0.0.1", 0))
                self.sock.listen(8)
                self.addr = "127.0.0.1:%d" % self.sock.getsockname()[1]
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                while True:
                    try:
                        conn, _ = self.sock.accept()
                    except OSError:
                        return
                    buf = b""
                    while b"\r\n\r\n" not in buf:
                        c = conn.recv(65536)
                        if not c:
                            break
                        buf += c
                    calls.append(1)
                    conn.sendall(
                        b"HTTP/1.1 503 Service Unavailable\r\n"
                        b"Retry-After: 0.1\r\nContent-Length: 0\r\n"
                        b"Connection: close\r\n\r\n"
                    )
                    conn.close()

        srv = _Always503()
        try:
            status, _, _ = op.http_call("GET", f"{srv.addr}/x")
            assert status == 503
            assert len(calls) == 1  # no retry: wholesale restore
        finally:
            srv.sock.close()


# ----------------------------------------------------------------------
# group commit


class TestGroupCommit:
    def _serial_twin(self, d, needles):
        os.mkdir(os.path.join(d, "serial"))
        v = Volume(os.path.join(d, "serial"), 1)
        for n in needles:
            v.write_needle(n)
        v.close()
        with open(v.base_name + ".dat", "rb") as f:
            return f.read()

    def test_batch_byte_identical_to_serial(self, monkeypatch):
        monkeypatch.setattr(
            Volume, "_now_ns", lambda self: self.last_append_at_ns + 1
        )
        payloads = [(b"gc-%02d\xff\x00" % i) * 37 for i in range(12)]
        with tempfile.TemporaryDirectory() as d:
            serial_dat = self._serial_twin(
                d, [_mk_needle(i, p) for i, p in enumerate(payloads)]
            )
            os.mkdir(os.path.join(d, "batch"))
            vb = Volume(os.path.join(d, "batch"), 1)
            outcomes = vb.write_needles(
                [(_mk_needle(i, p), None) for i, p in enumerate(payloads)],
                durable=True,
            )
            assert all(isinstance(o, tuple) and not o[2] for o in outcomes)
            with open(vb.base_name + ".dat", "rb") as f:
                batch_dat = f.read()
            assert batch_dat == serial_dat
            # every needle reads back through the normal path
            for i, p in enumerate(payloads):
                assert bytes(vb.read_needle(1000 + i).data) == p
            vb.close()

    def test_batch_per_needle_errors_dont_fail_batchmates(self):
        with tempfile.TemporaryDirectory() as d:
            v = Volume(d, 1)
            first = _mk_needle(0, b"original" * 10)
            v.write_needle(first)
            bad = _mk_needle(0, b"overwrite" * 10, cookie=0xBAD)  # same id
            good = _mk_needle(1, b"fine" * 10)
            outcomes = v.write_needles([(bad, None), (good, None)])
            assert isinstance(outcomes[0], CookieMismatch)
            assert isinstance(outcomes[1], tuple)
            assert bytes(v.read_needle(1001).data) == b"fine" * 10
            v.close()

    def test_same_id_in_one_batch_keeps_serial_semantics(self):
        """Regression (review): two writes for one needle id inside one
        commit window must behave like serial writes — the later one's
        checks run against the earlier BATCHMATE's committed record,
        so a mismatching cookie raises and a matching duplicate dedups
        — not against the stale pre-batch map."""
        with tempfile.TemporaryDirectory() as d:
            v = Volume(d, 1)
            first = _mk_needle(0, b"first-copy" * 12)
            bad_cookie = _mk_needle(0, b"evil-write" * 12, cookie=0xBAD)
            dup = _mk_needle(0, b"first-copy" * 12)  # same bytes+cookie
            outcomes = v.write_needles(
                [(first, None), (bad_cookie, None), (dup, None)]
            )
            assert isinstance(outcomes[0], tuple) and not outcomes[0][2]
            assert isinstance(outcomes[1], CookieMismatch)
            assert isinstance(outcomes[2], tuple) and outcomes[2][2], (
                "same-bytes duplicate should dedup as unchanged"
            )
            assert bytes(v.read_needle(1000).data) == b"first-copy" * 12
            v.close()

    def test_committer_coalesces_flushes(self):
        """Concurrent writers through one committer: flushes per POST
        drop by >= 4x versus fsync-per-POST at the same concurrency."""
        from seaweedfs_tpu.stats.metrics import COMMIT_FLUSHES

        n_writers = 16
        with tempfile.TemporaryDirectory() as d:
            v = Volume(d, 1)
            gc = GroupCommitter(window_us=20000, fsync=True)
            before = COMMIT_FLUSHES.value()
            barrier = threading.Barrier(n_writers)
            errs = []

            def w(i):
                try:
                    barrier.wait(5)
                    gc.write(v, _mk_needle(i, b"flush-%02d" % i * 20))
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            ts = [
                threading.Thread(target=w, args=(i,))
                for i in range(n_writers)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(10)
            assert not errs
            flushes = COMMIT_FLUSHES.value() - before
            assert flushes * 4 <= n_writers, (
                f"{flushes} flushes for {n_writers} writes"
            )
            for i in range(n_writers):
                assert v.has_needle(1000 + i)
            v.close()

    def test_committer_inactive_is_write_per_post(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_COMMIT", "0")
        from seaweedfs_tpu.stats.metrics import GROUP_COMMIT_BATCHES

        with tempfile.TemporaryDirectory() as d:
            v = Volume(d, 1)
            gc = GroupCommitter(window_us=500, fsync=False)
            before = GROUP_COMMIT_BATCHES.value()
            gc.write(v, _mk_needle(0))
            assert GROUP_COMMIT_BATCHES.value() == before  # no batching
            assert v.has_needle(1000)
            v.close()

    def test_crash_between_commit_points_replays_clean(self):
        """Satellite: kill between window commit points → no torn
        needle. A batch whose tail record hit the .dat but not the .idx
        (the crash window) must reload cleanly with every indexed
        needle intact and the torn tail invisible."""
        with tempfile.TemporaryDirectory() as d:
            v = Volume(d, 1)
            outcomes = v.write_needles(
                [(_mk_needle(i, b"crash-%d" % i * 25), None) for i in range(4)],
                durable=True,
            )
            assert all(isinstance(o, tuple) for o in outcomes)
            dat, idx = v.base_name + ".dat", v.base_name + ".idx"
            v.close()
            # simulate the crash: the last record's idx entry never made
            # it (truncate 16 bytes) and the .dat tail tore mid-record
            with open(idx, "r+b") as f:
                f.truncate(os.path.getsize(idx) - 16)
            with open(dat, "r+b") as f:
                f.truncate(os.path.getsize(dat) - 11)
            v2 = Volume(d, 1, create=False)
            for i in range(3):
                assert bytes(v2.read_needle(1000 + i).data) == (
                    b"crash-%d" % i * 25
                )
            assert not v2.has_needle(1003)
            # and the volume still accepts writes after the replay
            v2.write_needle(_mk_needle(9, b"post-crash" * 10))
            assert v2.has_needle(1009)
            v2.close()


# ----------------------------------------------------------------------
# queue-depth-aware assignment


class TestAssignment:
    def _layout(self):
        from seaweedfs_tpu.storage.store import VolumeInfo
        from seaweedfs_tpu.topology.node import DataNode
        from seaweedfs_tpu.topology.volume_layout import VolumeLayout

        layout = VolumeLayout("000", "", 1 << 30)
        nodes = []
        for i in range(2):
            dn = DataNode(f"n{i}:80", ip=f"n{i}", port=80)
            info = VolumeInfo(
                id=i + 1, size=0, collection="", file_count=0,
                delete_count=0, deleted_byte_count=0, read_only=False,
                replica_placement=0, version=3, ttl=0,
            )
            layout.register_volume(info, dn)
            nodes.append(dn)
        return layout, nodes

    def test_p2c_prefers_less_loaded_node(self):
        layout, (a, b) = self._layout()
        a.in_flight, a.write_queue_depth = 50, 10
        b.in_flight, b.write_queue_depth = 1, 0
        picks = [layout.pick_for_write(policy="p2c")[0] for _ in range(32)]
        # vid 2 lives on the idle node; p2c must always choose it when
        # both candidates are sampled (two writables → always compared)
        assert all(p == 2 for p in picks)
        # and the location list leads with the least-loaded replica
        _, locs = layout.pick_for_write(policy="p2c")
        assert locs[0] is b

    def test_random_policy_stays_blind(self):
        layout, (a, b) = self._layout()
        a.in_flight = 10_000
        picks = {
            layout.pick_for_write(policy="random")[0] for _ in range(64)
        }
        assert picks == {1, 2}  # load-blind by contract

    def test_heartbeat_fields_roundtrip(self):
        from seaweedfs_tpu.pb import master_pb2

        req = master_pb2.HeartbeatRequest(
            ip="h", port=1, in_flight_requests=11, write_queue_depth=4
        )
        out = master_pb2.HeartbeatRequest()
        out.ParseFromString(req.SerializeToString())
        assert out.in_flight_requests == 11
        assert out.write_queue_depth == 4

    def test_qos_off_forces_random(self, monkeypatch):
        """WEED_QOS=0 wholesale-restore: the master's assign path must
        pass policy=random even with -assignPolicy p2c."""
        monkeypatch.setenv("WEED_QOS", "0")
        captured = {}

        from seaweedfs_tpu.server.master_server import MasterServer

        ms = MasterServer.__new__(MasterServer)
        ms.assign_policy = "p2c"
        assert (
            ms.assign_policy if qos.enabled("assign") else "random"
        ) == "random"


# ----------------------------------------------------------------------
# live-cluster integration: heartbeat load → master, hedge spans,
# admission through a real server


class TestQosCluster:
    def test_load_reaches_master_and_cluster_top(self):
        from seaweedfs_tpu.telemetry import ClusterCollector
        from seaweedfs_tpu.util.availability import start_cluster

        with tempfile.TemporaryDirectory() as d:
            master, servers = start_cluster(
                [tempfile.mkdtemp(dir=d)],
                master_kwargs={"telemetry_interval": 0.5},
            )
            vs = servers[0]
            try:
                # fake live load, then force a beat and wait for ingest
                for _ in range(5):
                    vs.load.enter()
                vs._hb_wake.set()
                deadline = time.time() + 10
                dn = master.topology.data_nodes()[0]
                while time.time() < deadline and dn.in_flight != 5:
                    time.sleep(0.05)
                assert dn.in_flight == 5
                assert dn.queue_load() == 5
                # /cluster/top surfaces the columns
                collector = ClusterCollector(master, interval=0.5)
                master.telemetry = collector
                collector.collect_once()
                top = collector.top_payload(5)
                vol_rows = [
                    r for r in top["Nodes"] if r["Kind"] == "volume"
                ]
                assert vol_rows and vol_rows[0]["InFlight"] == 5
            finally:
                for _ in range(5):
                    vs.load.exit()
                for s in servers:
                    s.stop()
                master.stop()

    def test_admission_on_live_volume_server(self):
        """End-to-end shed: a volume server with a tiny budget sheds
        with 503 + Retry-After through the real mini loop, the counter
        moves, and WEED_QOS=0 would admit (checked via controller)."""
        from seaweedfs_tpu.stats.metrics import ADMISSION_REJECTED
        from seaweedfs_tpu.util.availability import start_cluster

        with tempfile.TemporaryDirectory() as d:
            master, servers = start_cluster(
                [tempfile.mkdtemp(dir=d)],
                admission_rate=1.0,
                admission_burst=1.0,
            )
            vs = servers[0]
            addr = f"127.0.0.1:{vs.port}"
            before = ADMISSION_REJECTED.value("volume")
            try:
                statuses = []
                for _ in range(6):
                    conn = socket.create_connection(
                        ("127.0.0.1", vs.port), timeout=5
                    )
                    conn.sendall(b"GET /status HTTP/1.1\r\n\r\n")
                    buf = b""
                    while b"\r\n\r\n" not in buf:
                        c = conn.recv(65536)
                        if not c:
                            break
                        buf += c
                    statuses.append(int(buf[9:12]))
                    conn.close()
                assert 200 in statuses
                assert 503 in statuses
                assert ADMISSION_REJECTED.value("volume") > before
            finally:
                for s in servers:
                    s.stop()
                master.stop()

    def test_hedge_wins_against_a_stalled_live_replica(self, monkeypatch):
        """A replicated write on a live two-server cluster, read back
        with the first replica stalled behind a SlowReplicaProxy: the
        hedge fires to the second replica and wins with the right
        bytes."""
        from seaweedfs_tpu.util.availability import start_cluster

        monkeypatch.setenv("WEED_QOS_HEDGE_MS", "40")
        with tempfile.TemporaryDirectory() as d:
            master, servers = start_cluster(
                [tempfile.mkdtemp(dir=d), tempfile.mkdtemp(dir=d)]
            )
            m = f"127.0.0.1:{master.port}"
            proxy = None
            try:
                payload = b"qos-check\x00\xff" * 64
                with urllib.request.urlopen(
                    f"http://{m}/dir/assign?replication=010", timeout=10
                ) as r:
                    a = json.load(r)
                urllib.request.urlopen(
                    urllib.request.Request(
                        f"http://{a['url']}/{a['fid']}", data=payload,
                        method="POST",
                        headers={"Content-Type": "application/octet-stream"},
                    ),
                    timeout=10,
                ).close()
                vid = a["fid"].partition(",")[0]
                with urllib.request.urlopen(
                    f"http://{m}/dir/lookup?volumeId={vid}", timeout=10
                ) as r:
                    urls = [loc["url"] for loc in json.load(r)["locations"]]
                assert len(urls) >= 2, urls
                proxy = SlowReplicaProxy(urls[0], delay_s=0.5)
                stats: dict = {}
                data, _ = hedge.download(
                    [f"{proxy.addr}/{a['fid']}", f"{urls[1]}/{a['fid']}"],
                    key=vid, stats=stats,
                )
                assert data == payload
                assert stats.get("fired", 0) >= 1
                assert stats.get("won", 0) >= 1
            finally:
                if proxy is not None:
                    proxy.stop()
                for vs in servers:
                    vs.stop()
                master.stop()

    def test_group_commit_on_live_write_path(self):
        """POSTs through a committer-armed volume server batch and stay
        byte-correct (read-back identical), and the C fast path stands
        down (reply still 201)."""
        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.stats.metrics import GROUP_COMMIT_WRITES
        from seaweedfs_tpu.util.availability import start_cluster

        with tempfile.TemporaryDirectory() as d:
            master, servers = start_cluster(
                [tempfile.mkdtemp(dir=d)],
                commit_window_us=2000,
                commit_fsync=True,
            )
            m = f"127.0.0.1:{master.port}"
            before = GROUP_COMMIT_WRITES.value()
            try:
                payloads = {}
                results = []

                def put(i):
                    body = (b"live-%02d\x00\xff" % i) * 64
                    ar = op.assign(m)
                    ur = op.upload(f"{ar.url}/{ar.fid}", body, jwt=ar.auth)
                    results.append(ur.error or "")
                    payloads[ar.fid] = body

                ts = [
                    threading.Thread(target=put, args=(i,)) for i in range(8)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(15)
                assert all(e == "" for e in results), results
                assert GROUP_COMMIT_WRITES.value() - before >= 8
                for fid, body in payloads.items():
                    url = op.lookup_file_id(m, fid)
                    data, _ = op.download(url)
                    assert data == body
            finally:
                for s in servers:
                    s.stop()
                master.stop()

    def test_hedged_read_spans_visible_in_trace(self):
        """Acceptance: hedged requests carry plane=serve spans visible
        through the trace ring (the substrate of trace.dump)."""
        from seaweedfs_tpu import trace

        slow = _StubServer(body=b"T" * 32, delay_s=1.0)
        fast = _StubServer(body=b"T" * 32)
        trace.set_enabled(True)
        try:
            os.environ["WEED_QOS_HEDGE_MS"] = "30"
            with trace.span("test.client") as root:
                trace_id = root.trace_id
                hedge.download(
                    [f"{slow.addr}/8,00000008", f"{fast.addr}/8,00000008"],
                    key="t8",
                )
        finally:
            os.environ.pop("WEED_QOS_HEDGE_MS", None)
            slow.stop()
            fast.stop()
        spans = [
            s for s in trace.debug_payload(512)["recent"]
            if s["trace"] == trace_id and s["name"] == "qos.hedge"
        ]
        assert spans, "qos.hedge span missing from the ring"
        sp = spans[0]
        assert sp["plane"] == "serve"
        assert sp.get("annot", {}).get("hedged") == "1"


# ----------------------------------------------------------------------
# weedload extensions


class TestWeedloadQos:
    def test_mixed_mode_worker_alternates(self):
        """Unit-drive the worker loop in-process (no spawn): mixed mode
        must issue both PUTs and GETs against a live cluster."""
        from seaweedfs_tpu.telemetry import weedload
        from seaweedfs_tpu.util.availability import start_cluster

        with tempfile.TemporaryDirectory() as d:
            master, servers = start_cluster([tempfile.mkdtemp(dir=d)])
            m = f"127.0.0.1:{master.port}"
            try:
                payload = b"mix\x00\xff" * 40
                keys = weedload.seed_keys(m, 4, payload)
                out: queue.Queue = queue.Queue()
                weedload._worker(
                    {
                        "mode": "mixed",
                        "master": m,
                        "duration_s": 1.0,
                        "payload": payload,
                        "rate": 0.0,
                        "keys": keys,
                        "index": 0,
                        "hedge": False,
                    },
                    out,
                )
                row = out.get(timeout=5)
                assert row["mode"] == "mixed"
                assert row["errors"] == 0
                assert row["ops"] >= 4
                assert row["shed"] == 0
            finally:
                for s in servers:
                    s.stop()
                master.stop()

    def test_hedged_worker_reports_counts(self):
        from seaweedfs_tpu.telemetry import weedload

        slow = _StubServer(body=b"W" * 24, delay_s=0.5)
        fast = _StubServer(body=b"W" * 24)
        out: queue.Queue = queue.Queue()
        try:
            os.environ["WEED_QOS_HEDGE_MS"] = "20"
            weedload._worker(
                {
                    "mode": "get",
                    "master": "unused",
                    "duration_s": 1.2,
                    "payload": b"",
                    "rate": 0.0,
                    "keys": [("1,0000000a", [slow.addr, fast.addr])],
                    "index": 0,
                    "hedge": True,
                },
                out,
            )
        finally:
            os.environ.pop("WEED_QOS_HEDGE_MS", None)
            slow.stop()
            fast.stop()
        row = out.get(timeout=5)
        assert row["errors"] == 0
        assert row["ops"] >= 2
        # the primary rotated onto the slow replica at least once, so
        # hedges fired and the counts rode the row
        assert row["hedge"].get("fired", 0) >= 1
        assert row["hedge"].get("won", 0) >= 1

    def test_shed_counted_separately(self):
        from seaweedfs_tpu.telemetry import weedload

        class _Shedder(_StubServer):
            def _serve(self, conn):
                try:
                    buf = b""
                    while b"\r\n\r\n" not in buf:
                        c = conn.recv(65536)
                        if not c:
                            return
                        buf += c
                    conn.sendall(
                        b"HTTP/1.1 503 Service Unavailable\r\n"
                        b"Retry-After: 1\r\nContent-Length: 0\r\n"
                        b"Connection: close\r\n\r\n"
                    )
                finally:
                    conn.close()

        srv = _Shedder()
        out: queue.Queue = queue.Queue()
        try:
            weedload._worker(
                {
                    "mode": "get",
                    "master": "unused",
                    "duration_s": 0.4,
                    "payload": b"",
                    "rate": 0.0,
                    "keys": [("1,0000000b", srv.addr)],
                    "index": 0,
                    "hedge": False,
                },
                out,
            )
        finally:
            srv.stop()
        row = out.get(timeout=5)
        assert row["shed"] >= 1
        assert row["errors"] == 0
        assert row["ops"] == 0
