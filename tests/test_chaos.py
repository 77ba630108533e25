"""weedchaos: fault library units + the cluster scenario suite
(docs/CHAOS.md).

The scenario quartet the chaos plane ships with — leader kill during a
write fan, partition during ec.rebuild, EIO on the read path, lossy EC
gathers — each executed against REAL servers over real sockets with
the invariant checkers auditing: no acked write lost, no double-apply,
re-convergence within a bound. Plus the deadline plane's acceptance
tests: expired `X-Weed-Deadline` is 504-fast-rejected at every daemon
before any work, and `http_call`'s whole-request wall bound defeats a
trickling server.
"""

from __future__ import annotations

import errno
import json
import os
import socket
import threading
import time
import urllib.request

import pytest

from seaweedfs_tpu.analysis import chaos as chaos_mod
from seaweedfs_tpu.analysis.chaos import (
    ChaosProxy,
    DiskChaos,
    DiskFault,
    Fault,
    ProcChaos,
    Scenario,
    bounded_amplification,
    converges,
    no_acked_write_lost,
    no_double_apply,
    parse_disk_spec,
    run_scenario,
)
from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.client import retry as retry_mod
from seaweedfs_tpu.util import deadline as dl_mod
from tests import chaos as wiring
from tests.chaos import free_port, wait_for
from tests.faults import DeadShard


# ---------------------------------------------------------------------------
# deadline plane units


class TestDeadlineUnit:
    def test_cap_derives_remaining(self):
        d = dl_mod.Deadline.after(10.0)
        assert 9.0 < d.cap(30.0) <= 10.0  # remaining wins
        assert d.cap(0.5) == 0.5  # explicit per-op cap wins when smaller

    def test_cap_raises_when_spent(self):
        d = dl_mod.Deadline.after(-0.1)
        assert d.expired
        with pytest.raises(dl_mod.DeadlineExceeded):
            d.cap(5.0)

    def test_deadline_exceeded_is_a_timeout(self):
        # transport handlers classify TimeoutError as "do not blindly
        # replay"; an exhausted budget must ride the same arm
        assert issubclass(dl_mod.DeadlineExceeded, TimeoutError)
        assert issubclass(dl_mod.DeadlineExceeded, OSError)

    def test_header_roundtrip(self):
        d = dl_mod.Deadline.after(2.0)
        back = dl_mod.from_header(d.header_value())
        assert abs(back.remaining() - d.remaining()) < 0.05

    def test_negative_header_parses_expired(self):
        d = dl_mod.from_header("-120.0")
        assert d is not None and d.expired

    def test_garbage_header_is_none(self):
        assert dl_mod.from_header("soon") is None
        assert dl_mod.from_header("") is None

    def test_scope_nests_and_restores(self):
        outer = dl_mod.Deadline.after(5.0)
        inner = dl_mod.Deadline.after(1.0)
        assert dl_mod.current() is None
        with dl_mod.scope(outer):
            assert dl_mod.current() is outer
            with dl_mod.scope(inner):
                assert dl_mod.current() is inner
            assert dl_mod.current() is outer
        assert dl_mod.current() is None

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv("WEED_DEADLINE", "0")
        with dl_mod.scope(dl_mod.Deadline.after(1.0)):
            assert dl_mod.effective() is None
            h: dict = {}
            dl_mod.stamp(h)
            assert dl_mod.DEADLINE_HEADER not in h


# ---------------------------------------------------------------------------
# unified retry policy units


class TestRetryUnit:
    def _policy(self, **kw):
        kw.setdefault("budget", None)
        kw.setdefault("backoff_ms", 1)
        kw.setdefault("backoff_max_ms", 2)
        return retry_mod.RetryPolicy(**kw)

    def test_attempt_cap(self):
        calls = []
        p = self._policy(attempts=3)
        with pytest.raises(OSError):
            p.run(lambda a: calls.append(a) or (_ for _ in ()).throw(OSError("x")))
        assert calls == [0, 1, 2]

    def test_success_after_retry(self):
        state = {"n": 0}

        def fn(attempt):
            state["n"] += 1
            if state["n"] < 3:
                raise OSError("flaky")
            return "ok"

        assert self._policy(attempts=4).run(fn) == "ok"

    def test_non_retryable_type_raises_immediately(self):
        calls = []
        p = self._policy(attempts=5, retry_on=(ConnectionError,))
        with pytest.raises(ValueError):
            p.run(lambda a: calls.append(a) or (_ for _ in ()).throw(ValueError()))
        assert calls == [0]

    def test_non_idempotent_applied_never_replays(self):
        calls = []
        p = self._policy(attempts=5)
        with pytest.raises(OSError):
            p.run(
                lambda a: calls.append(a) or (_ for _ in ()).throw(OSError()),
                idempotent=False,
                applied=lambda e: True,  # the request may have landed
            )
        assert calls == [0]

    def test_deadline_gates_retries(self):
        import random as _random

        calls = []
        # seeded jitter: draws ~6.7 ms then ~42 ms against a 20 ms
        # budget, so exactly ONE retry fits and the next is gated —
        # deterministic (unseeded, the uniform[0,50] chain fit a third
        # call ~8% of runs and flaked the suite)
        p = self._policy(
            attempts=10, backoff_ms=50, backoff_max_ms=50,
            rng=_random.Random(1),
        )
        with pytest.raises(OSError):
            p.run(
                lambda a: calls.append(a) or (_ for _ in ()).throw(OSError()),
                deadline=dl_mod.Deadline.after(0.02),
            )
        assert len(calls) == 2  # retry 1 fit the budget, retry 2 was gated

    def test_budget_dries_up_then_probes(self):
        budget = retry_mod.RetryBudget(ratio=0.0001, min_reserve=1.0)
        assert budget.try_spend(now=100.0)  # the reserve token
        assert budget.try_spend(now=100.1)  # dry → first probe granted
        assert not budget.try_spend(now=100.2)  # probe not due yet
        assert budget.denied == 1
        # the probe trickle resumes one interval later
        assert budget.try_spend(now=100.1 + budget.probe_interval_s)
        assert not budget.try_spend(now=100.2 + budget.probe_interval_s)

    def test_budget_credits_from_requests(self):
        budget = retry_mod.RetryBudget(ratio=0.5, min_reserve=0.0)
        assert budget.try_spend(now=9.0)  # empty bucket → the 1/s probe
        budget.note_request(4)  # 2 tokens
        assert budget.try_spend(now=9.5)
        assert budget.try_spend(now=9.5)
        assert not budget.try_spend(now=9.5)  # dry again, probe not due

    def test_full_jitter_bounded_by_ceiling(self):
        p = self._policy(attempts=5, backoff_ms=100, backoff_max_ms=150)
        for attempt, ceiling in ((1, 0.1), (2, 0.15), (3, 0.15)):
            for _ in range(20):
                w = p.backoff_for(attempt)
                assert 0.0 <= w <= ceiling

    def test_master_failover_retries_across_rounds(self):
        """Satellite regression: a leaderless window spanning one full
        rotation used to surface the raw connection error; the policy
        now retries rounds (bounded, jittered) until the new leader
        answers."""
        state = {"rounds": 0}

        def fn(master):
            state["rounds"] += 1
            if state["rounds"] <= 4:  # 2 full rotations of 2 masters
                raise ConnectionRefusedError("leader died")
            return f"ok-{master}"

        policy = retry_mod.RetryPolicy(
            attempts=4, backoff_ms=1, backoff_max_ms=2,
            retry_on=(op.AllMastersFailed,), budget=None,
        )
        result, idx = op.with_master_failover(["m1", "m2"], fn, policy=policy)
        assert result == "ok-m1" and idx == 0
        assert state["rounds"] == 5


# ---------------------------------------------------------------------------
# ChaosProxy units


def _echo_server():
    """A tiny server echoing each received chunk back, for proxy tests."""
    lst = socket.socket()
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    lst.listen(8)

    def serve():
        while True:
            try:
                c, _ = lst.accept()
            except OSError:
                return
            def pump(conn):
                try:
                    while True:
                        d = conn.recv(65536)
                        if not d:
                            return
                        conn.sendall(d)
                except OSError:
                    pass
                finally:
                    conn.close()
            threading.Thread(target=pump, args=(c,), daemon=True).start()

    threading.Thread(target=serve, daemon=True).start()
    return lst, "127.0.0.1:%d" % lst.getsockname()[1]


class TestChaosProxyUnit:
    def test_latency_and_runtime_mutation(self):
        lst, target = _echo_server()
        proxy = ChaosProxy(target)
        try:
            proxy.response.latency_s = 0.15
            s = socket.create_connection(("127.0.0.1", proxy.port), timeout=5)
            t0 = time.monotonic()
            s.sendall(b"ping")
            assert s.recv(16) == b"ping"
            assert time.monotonic() - t0 >= 0.14
            proxy.response.latency_s = 0.0  # live retune
            t0 = time.monotonic()
            s.sendall(b"fast")
            assert s.recv(16) == b"fast"
            assert time.monotonic() - t0 < 0.1
            assert proxy.chunks_delayed >= 1
            s.close()
        finally:
            proxy.stop()
            lst.close()

    def test_partition_parks_then_heals(self):
        lst, target = _echo_server()
        proxy = ChaosProxy(target)
        try:
            s = socket.create_connection(("127.0.0.1", proxy.port), timeout=5)
            s.sendall(b"before")
            assert s.recv(16) == b"before"
            proxy.partition()
            assert proxy.partitioned
            s.sendall(b"during")
            s.settimeout(0.3)
            with pytest.raises(TimeoutError):
                s.recv(16)  # parked, not dropped
            proxy.heal()
            s.settimeout(5)
            assert s.recv(16) == b"during"  # delivered after heal
            s.close()
        finally:
            proxy.stop()
            lst.close()

    def test_drop_kills_connection(self):
        lst, target = _echo_server()
        proxy = ChaosProxy(target, seed=7)
        try:
            proxy.request.drop_p = 1.0
            s = socket.create_connection(("127.0.0.1", proxy.port), timeout=5)
            s.sendall(b"doomed")
            s.settimeout(2)
            # dropped → RST/EOF, never an echo
            try:
                got = s.recv(16)
            except OSError:
                got = b""
            assert got == b""
            assert proxy.conns_dropped >= 1
            s.close()
        finally:
            proxy.stop()
            lst.close()

    def test_rst_mid_stream(self):
        lst, target = _echo_server()
        proxy = ChaosProxy(target)
        try:
            proxy.response.rst_after_bytes = 4
            s = socket.create_connection(("127.0.0.1", proxy.port), timeout=5)
            s.sendall(b"12345678")
            s.settimeout(2)
            got = b""
            try:
                while True:
                    d = s.recv(16)
                    if not d:
                        break
                    got += d
            except OSError:
                pass  # the RST
            assert len(got) <= 4
            assert proxy.conns_rst >= 1
            s.close()
        finally:
            proxy.stop()
            lst.close()


class TestPartitionOnALiveMaster:
    def test_deadlined_call_fails_fast_then_heals(self):
        """A planted partition in front of a live master is DETECTED (a
        deadlined call through it fails within the budget, it never
        parks) and HEALED (the same call succeeds after heal())."""
        from seaweedfs_tpu.server.master_server import MasterServer

        master = MasterServer(
            port=free_port(), volume_size_limit_mb=64, vacuum_interval=0
        )
        master.start()
        proxy = ChaosProxy(f"127.0.0.1:{master.port}")
        try:
            status, _, _ = op.http_call(
                "GET", f"{proxy.addr}/dir/status", timeout=5
            )
            assert status == 200
            proxy.partition()
            t0 = time.perf_counter()
            with pytest.raises((TimeoutError, OSError)):
                op.http_call(
                    "GET", f"{proxy.addr}/dir/status", timeout=5,
                    deadline=dl_mod.Deadline.after(0.5),
                )
            # the budget, not a parked socket, ended the call
            assert time.perf_counter() - t0 < 3.0
            proxy.heal()
            status, _, _ = op.http_call(
                "GET", f"{proxy.addr}/dir/status", timeout=5
            )
            assert status == 200
        finally:
            proxy.stop()
            master.stop()


# ---------------------------------------------------------------------------
# DiskChaos units


class TestDiskChaosUnit:
    def test_eio_on_matching_read(self, tmp_path):
        victim = tmp_path / "data.bin"
        victim.write_bytes(b"x" * 1024)
        with DiskChaos([DiskFault("eio", str(tmp_path))]):
            f = open(victim, "rb")
            with pytest.raises(OSError) as ei:
                os.pread(f.fileno(), 16, 0)
            assert ei.value.errno == errno.EIO
            f.close()
        # uninstalled: reads work again
        f = open(victim, "rb")
        assert os.pread(f.fileno(), 4, 0) == b"xxxx"
        f.close()

    def test_non_matching_paths_untouched(self, tmp_path):
        victim = tmp_path / "a" / "data.bin"
        victim.parent.mkdir()
        victim.write_bytes(b"y" * 64)
        with DiskChaos([DiskFault("eio", str(tmp_path / "other"))]):
            f = open(victim, "rb")
            assert os.pread(f.fileno(), 2, 0) == b"yy"
            f.close()

    def test_enospc_on_write(self, tmp_path):
        victim = tmp_path / "w.bin"
        with DiskChaos(
            [DiskFault("enospc", str(tmp_path), ops=("write",))]
        ):
            fd = os.open(victim, os.O_CREAT | os.O_WRONLY)
            with pytest.raises(OSError) as ei:
                os.pwrite(fd, b"data", 0)
            assert ei.value.errno == errno.ENOSPC
            os.close(fd)

    def test_short_read(self, tmp_path):
        victim = tmp_path / "s.bin"
        victim.write_bytes(b"z" * 100)
        with DiskChaos(
            [DiskFault("short", str(tmp_path), short_by=3)]
        ):
            fd = os.open(victim, os.O_RDONLY)
            assert len(os.pread(fd, 10, 0)) == 7
            os.close(fd)

    def test_max_hits_and_counter(self, tmp_path):
        victim = tmp_path / "h.bin"
        victim.write_bytes(b"q" * 16)
        fault = DiskFault("eio", str(tmp_path), max_hits=1)
        with DiskChaos([fault]):
            fd = os.open(victim, os.O_RDONLY)
            with pytest.raises(OSError):
                os.pread(fd, 4, 0)
            assert os.pread(fd, 4, 0) == b"qqqq"  # budget spent
            os.close(fd)
        assert fault.hits == 1

    def test_parse_env_spec(self):
        faults = parse_disk_spec(
            "eio:/data/v1;slow:/data/v2:read,write;garbage;short:"
        )
        assert len(faults) == 2
        assert faults[0].mode == "eio" and faults[0].ops == ("read",)
        assert faults[1].ops == ("read", "write")

    def test_uninstall_restores_os(self, tmp_path):
        import builtins

        real_pread, real_open = os.pread, builtins.open
        dc = DiskChaos([DiskFault("eio", str(tmp_path))]).install()
        assert os.pread is not real_pread
        dc.uninstall()
        assert os.pread is real_pread and builtins.open is real_open


# ---------------------------------------------------------------------------
# scenario runner units


class TestScenarioRunner:
    def test_faults_fire_in_order_and_report(self):
        fired = []
        sc = Scenario(
            "unit",
            faults=[
                Fault(0.05, lambda: fired.append("b"), name="second"),
                Fault(0.0, lambda: fired.append("a"), name="first"),
            ],
            duration_s=2.0,
        )
        report = run_scenario(sc, lambda: {"acked": {}})
        assert fired == ["a", "b"]
        assert [name for _, name in report["events"]] == ["first", "second"]
        assert report["ok"] is True

    def test_invariant_failure_raises_named(self):
        sc = Scenario("bad", faults=[], duration_s=1.0)

        def workload():
            return {"acked": {"f1": b"expect"}}

        inv = no_acked_write_lost(lambda fid: b"CORRUPTED")
        with pytest.raises(chaos_mod.InvariantFailed) as ei:
            run_scenario(sc, workload, [inv])
        assert "no_acked_write_lost" in str(ei.value)

    def test_amplification_math(self):
        inv = bounded_amplification(factor=1.15)
        report = {"requests_sent": 120, "acked": {f"f{i}": b"" for i in range(100)}, "failed": 0}
        r = inv(report)
        assert not r.ok and report["amplification"] == 1.2
        report2 = {"requests_sent": 110, "acked": {f"f{i}": b"" for i in range(100)}, "failed": 0}
        assert inv(report2).ok


# ---------------------------------------------------------------------------
# deadline plane e2e: 504 fast-reject at every daemon, wall bound


@pytest.fixture(scope="module")
def mini_cluster(tmp_path_factory):
    """1 master + 2 volume servers, in-process, for the deadline and
    lossy-gather suites."""
    from seaweedfs_tpu.server.master_server import MasterServer

    master = MasterServer(
        port=free_port(), volume_size_limit_mb=64, vacuum_interval=0
    )
    master.start()
    servers = [
        wiring.start_volume_server(
            tmp_path_factory, f"127.0.0.1:{master.port}", f"mini{i}"
        )
        for i in range(2)
    ]
    assert wait_for(lambda: len(master.topology.data_nodes()) == 2)
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


def _get_status(url: str, headers: dict) -> tuple[int, bytes]:
    req = urllib.request.Request(url, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class TestDeadline504E2E:
    def test_expired_deadline_rejected_at_every_daemon(self, mini_cluster):
        """Acceptance: a request entering ANY daemon with an expired
        X-Weed-Deadline is 504-fast-rejected before touching disk —
        evidenced by the span (status 504, expired-at-entry annotation,
        no work stages) and the rejection counter."""
        from seaweedfs_tpu.stats.metrics import DEADLINE_REJECTED

        master, servers = mini_cluster
        masters = [f"127.0.0.1:{master.port}"]
        fid = wiring.put_blob(masters, b"deadline payload " * 100)
        url, _ = op.with_master_failover(
            masters, lambda m: op.lookup_file_id(m, fid)
        )

        before = DEADLINE_REJECTED.value("volume")
        # healthy read first: the blob IS servable
        status, body = _get_status(f"http://{url}", {})
        assert status == 200 and body == b"deadline payload " * 100

        # expired budget → 504 at the volume server, blob untouched,
        # span evidence captured via the forced trace header
        status, body = _get_status(
            f"http://{url}",
            {
                "X-Weed-Deadline": "-250.0",
                "X-Weed-Trace": "deadbeefdeadbeef:cafecafecafecafe:serve",
            },
        )
        assert status == 504
        assert b"deadline" in body
        assert DEADLINE_REJECTED.value("volume") > before

        # ...and at the master
        status, body = _get_status(
            f"http://127.0.0.1:{master.port}/dir/assign",
            {"X-Weed-Deadline": "-5.0"},
        )
        assert status == 504

        # span evidence: a 504 span with the annotation and no stages
        vol = next(v for v in servers if f"127.0.0.1:{v.port}" == url.split("/")[0])
        with urllib.request.urlopen(
            f"http://127.0.0.1:{vol.port}/debug/traces?n=64", timeout=10
        ) as r:
            doc = json.loads(r.read())
        reject_spans = [
            s
            for s in doc.get("recent", [])
            if s.get("status") == 504
            and s.get("annot", {}).get("deadline") == "expired-at-entry"
        ]
        assert reject_spans, doc.get("recent", [])[:5]
        assert not reject_spans[-1].get("stages_ms")

    def test_expired_deadline_rejected_on_grpc(self, mini_cluster):
        import grpc

        from seaweedfs_tpu.pb import rpc as rpc_mod
        from seaweedfs_tpu.pb import volume_pb2

        master, servers = mini_cluster
        vs = servers[0]
        with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
            stub = rpc_mod.volume_stub(ch)
            with pytest.raises(grpc.RpcError) as ei:
                stub.VolumeSyncStatus(
                    volume_pb2.VolumeSyncStatusRequest(volume_id=1),
                    metadata=((dl_mod.DEADLINE_HEADER, "-100.0"),),
                )
            assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED

    def test_deadline_propagates_client_to_handler(self, mini_cluster):
        """A client deadline rides the hop header into the serving
        funnel, which installs it as the handler's ambient deadline —
        the seam every internal hop inherits from."""
        master, _ = mini_cluster
        with dl_mod.scope(dl_mod.Deadline.after(5.0)):
            status, _, body = op.http_call(
                "GET", f"127.0.0.1:{master.port}/dir/status", timeout=5
            )
        assert status == 200

    def test_stub_caps_timeout_from_ambient_deadline(self, mini_cluster):
        """An expired ambient deadline stops a gRPC hop before dialing."""
        master, _ = mini_cluster
        from seaweedfs_tpu.pb import master_pb2, rpc as rpc_mod

        ch = rpc_mod.cached_channel(f"127.0.0.1:{master.grpc_port}")
        with dl_mod.scope(dl_mod.Deadline(time.monotonic() - 1.0)):
            with pytest.raises(dl_mod.DeadlineExceeded):
                rpc_mod.master_stub(ch).LookupVolume(
                    master_pb2.LookupVolumeRequest(vids=["1"])
                )


class TestHttpCallWallBound:
    """Satellite: the per-socket-op timeout must not let a trickling
    server hold a caller forever."""

    def _trickle_server(self, byte_interval_s=0.15, total=64):
        lst = socket.socket()
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lst.bind(("127.0.0.1", 0))
        lst.listen(4)

        def serve():
            while True:
                try:
                    c, _ = lst.accept()
                except OSError:
                    return
                def drip(conn):
                    try:
                        conn.recv(65536)
                        conn.sendall(
                            b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n"
                            % total
                        )
                        for _ in range(total):
                            conn.sendall(b"x")
                            time.sleep(byte_interval_s)
                    except OSError:
                        pass
                    finally:
                        conn.close()
                threading.Thread(target=drip, args=(c,), daemon=True).start()

        threading.Thread(target=serve, daemon=True).start()
        return lst, "127.0.0.1:%d" % lst.getsockname()[1]

    def test_wall_bound_beats_trickle(self):
        # 64 bytes at 1 byte / 150 ms = 9.6 s of trickle; each recv
        # returns within the 0.3 s op timeout so per-op timeouts never
        # fire — only the whole-request wall (0.3 × 4 = 1.2 s) stops it
        lst, addr = self._trickle_server()
        try:
            t0 = time.monotonic()
            with pytest.raises((TimeoutError, OSError)):
                op.http_call("GET", f"{addr}/trickle", timeout=0.3)
            elapsed = time.monotonic() - t0
            assert elapsed < 4.0, f"wall bound did not fire ({elapsed:.1f}s)"
        finally:
            lst.close()

    def test_explicit_deadline_bounds_whole_call(self):
        lst, addr = self._trickle_server()
        try:
            t0 = time.monotonic()
            with pytest.raises((dl_mod.DeadlineExceeded, OSError)):
                op.http_call(
                    "GET",
                    f"{addr}/trickle",
                    timeout=5,
                    deadline=dl_mod.Deadline.after(0.5),
                )
            assert time.monotonic() - t0 < 2.5
        finally:
            lst.close()


# ---------------------------------------------------------------------------
# scenario: leader kill during a concurrent write fan


class TestLeaderKillScenario:
    def test_leader_kill_write_fan(self, tmp_path_factory, monkeypatch):
        """Kill the raft leader mid-write-fan: writers re-resolve via
        the retry policy, zero acked writes lost, no double-apply, and
        the survivors re-converge on a single leader within bound."""
        # determinism under the election storm: let the in-test retry
        # budget refill freely (the amplification bound is audited by
        # the bench chaos config against a blackholed replica instead)
        monkeypatch.setenv("WEED_RETRY_BUDGET_RATIO", "1.0")
        masters = wiring.start_ha_masters(tmp_path_factory, 3)
        addrs = wiring.master_addrs(masters)
        vs = wiring.start_volume_server(
            tmp_path_factory, ",".join(addrs), "lk"
        )
        killed: list = []
        try:
            leader = next(m for m in masters if m.is_leader)
            assert wait_for(
                lambda: len(leader.topology.data_nodes()) == 1
            ), "volume server never registered"

            policy = retry_mod.RetryPolicy(
                attempts=8,
                backoff_ms=100,
                backoff_max_ms=800,
                retry_on=(op.AllMastersFailed,),
                label="chaos-leader-kill",
            )

            def kill_leader():
                killed.append(chaos_mod.kill_raft_leader(masters))

            survivors = lambda: [m for m in masters if m not in killed]  # noqa: E731

            def probe():
                live = survivors()
                if sum(1 for m in live if m.is_leader) != 1:
                    return False
                new_leader = next(m for m in live if m.is_leader)
                return len(new_leader.topology.data_nodes()) == 1

            report = run_scenario(
                Scenario(
                    "leader-kill-write-fan",
                    faults=[Fault(0.4, kill_leader, name="SIGKILL leader")],
                    duration_s=45.0,
                ),
                workload=lambda: wiring.write_fan(
                    addrs, n_writers=3, n_writes=25, policy=policy
                ),
                invariants=[
                    # convergence FIRST: the read-back audit must run
                    # against the re-elected cluster, not the election
                    converges(probe, bound_s=20.0, name="reconverged"),
                    no_acked_write_lost(
                        lambda fid: wiring.read_blob(
                            [f"127.0.0.1:{m.port}" for m in survivors()], fid
                        )
                    ),
                    no_double_apply(),
                ],
            )
            assert report["ok"], report["invariants"]
            assert killed and killed[0] is not None, "no leader was killed"
            # the kill landed mid-fan and writers still completed: the
            # re-resolve satellite's regression bar
            assert len(report["acked"]) == 75, (
                f"failed={report['failed']} — writers did not survive "
                f"the election window"
            )
            assert report["reconverged_s"] <= 20.0
        finally:
            vs.stop()
            for m in masters:
                if m not in killed:
                    try:
                        m.stop()
                    except Exception:
                        pass


# ---------------------------------------------------------------------------
# scenario: partition a survivor holder during ec.rebuild


class TestPartitionDuringRebuild:
    def test_rebuild_backs_off_then_completes_after_heal(
        self, tmp_path_factory
    ):
        """Quarantine a shard while the node holding the other half of
        the survivors is partitioned: the repair scheduler's attempt
        fails WITHIN its deadline budget (not a parked slot), backs
        off exponentially, and completes after heal — with every key
        byte-identical and the repair queue drained."""
        from seaweedfs_tpu.server.master_server import MasterServer

        master = MasterServer(
            port=free_port(),
            volume_size_limit_mb=64,
            vacuum_interval=0,
            repair_interval=0.4,
            repair_grace=0.3,
        )
        # bounded budgets for the fault window: one rebuild attempt may
        # spend 3 s (the deadline caps its parked gathers), retries
        # back off from 1 s
        master.repair.backoff_base = 1.0
        master.repair.backoff_max = 4.0
        master.repair.cooldown = 2.0
        master.repair.repair_deadline_s = 3.0
        master.start()
        maddr = f"127.0.0.1:{master.port}"
        vs_a = wiring.start_volume_server(tmp_path_factory, maddr, "pa")
        vs_b, pair = wiring.proxied_volume_server(tmp_path_factory, maddr, "pb")
        try:
            assert wait_for(lambda: len(master.topology.data_nodes()) == 2)
            vid, keys = wiring.seed_ec_volume(master, "pchaos")
            assert wait_for(
                lambda: wiring.registered_shards(master, vid) == 14, 30
            ), "EC spread never registered"
            wait_for(lambda: not master.repair.tasks, 30)

            ev_a = vs_a.store.find_ec_volume(vid)
            assert ev_a is not None and ev_a.shard_ids(), "A holds no shards"
            # A alone must not be able to rebuild (k=10): with the
            # spread balancing 2 nodes this holds structurally
            assert len(ev_a.shard_ids()) <= 10

            # partition B, then kill a shard on A → repair needs B
            pair.partition()
            dead = DeadShard(vid, volume_servers=[vs_a], collection="pchaos")
            sid = dead.kill()

            def task_attempted():
                t = master.repair.tasks.get(("ec_rebuild", vid))
                return t is not None and t.attempts >= 1 and t.last_error

            assert wait_for(task_attempted, 30), (
                "no bounded failed rebuild attempt under partition: "
                f"{master.repair.queue_snapshot()}"
            )

            # heal → backoff lapses → rebuild completes
            pair.heal()
            assert wait_for(
                lambda: any(
                    h["Kind"] == "ec_rebuild" and h["VolumeId"] == vid
                    for h in master.repair.history
                ),
                45,
            ), f"rebuild never completed after heal: {master.repair.queue_snapshot()}"
            assert wait_for(
                lambda: wiring.registered_shards(master, vid) == 14, 30
            ), "cluster never reconverged to 14 shards"
            assert wait_for(lambda: not master.repair.tasks, 30), (
                "repair queue did not drain"
            )

            # no acked write lost through the whole episode
            for fid, want in keys.items():
                got = wiring.read_blob([maddr], fid, collection="pchaos")
                assert got == want, f"{fid} corrupt after heal"
            assert sid in (
                set(range(14))
            )
        finally:
            pair.stop()
            vs_b.stop()
            vs_a.stop()
            master.stop()


# ---------------------------------------------------------------------------
# scenario: EIO on the EC read path → quarantine, never a crash


class TestEIOOnRead:
    def test_eio_shard_quarantined_reads_survive(self, tmp_path):
        """A failing medium (full-size shard, EIO on every pread) must
        degrade reads to reconstruction AND quarantine the shard after
        the strike budget — the serving path never crashes and every
        byte stays correct."""
        from tests.test_ec_degraded import _local_ec_store

        vid, sid = 9, 0  # _local_ec_store default vid; shard 0 dies
        victim_path = os.path.join(str(tmp_path), f"{vid}.ec{sid:02d}")
        # the shim tracks fds opened WHILE installed (the Recorder
        # model), so the store — which opens every shard at mount —
        # is created inside the fault context
        with DiskChaos([DiskFault("eio", victim_path)]) as dc:
            store, needles = _local_ec_store(tmp_path, n_needles=40)
            try:
                ev = store.find_ec_volume(vid)
                assert sid in ev.shard_ids()
                results = []
                # two passes: ~1/10 of interval reads land on the dying
                # shard, and each one strikes it once — the second pass
                # pushes it past the 3-strike quarantine threshold
                for _pass in range(2):
                    for nid, data in needles.items():
                        n = store.read_needle(vid, nid)
                        results.append((nid, bytes(n.data) == data))
                assert all(ok for _, ok in results), [
                    nid for nid, ok in results if not ok
                ]
                assert dc.faults[0].hits > 0, "the EIO fault never fired"
                # the strikes quarantined the dying shard → the repair
                # plane will regenerate it (no crash, no silent decay)
                assert sid in ev.quarantined, ev.quarantined
                assert "read errors" in ev.quarantined[sid]
            finally:
                store.close()

    def test_eio_via_env_knob_spec(self, tmp_path, monkeypatch):
        """The WEED_CHAOS_DISK env path used for subprocess clusters
        installs the same shim (idempotent)."""
        monkeypatch.setenv("WEED_CHAOS_DISK", f"eio:{tmp_path}")
        monkeypatch.setattr(chaos_mod, "_ENV_DISK", None)
        shim = chaos_mod.install_disk_chaos_from_env()
        try:
            assert shim is not None
            assert chaos_mod.install_disk_chaos_from_env() is shim  # idempotent
            victim = tmp_path / "v.bin"
            victim.write_bytes(b"abc")
            fd = os.open(victim, os.O_RDONLY)
            with pytest.raises(OSError):
                os.pread(fd, 3, 0)
            os.close(fd)
        finally:
            shim.uninstall()
            monkeypatch.setattr(chaos_mod, "_ENV_DISK", None)


# ---------------------------------------------------------------------------
# scenario: SIGSTOP gray failure (weedguard, docs/HEALTH.md)


class TestSigstopGrayFailure:
    """A SIGSTOP'd volume server keeps its TCP sessions open and its
    heartbeat STREAM alive — the binary liveness model can't see it
    until node_timeout. The phi-accrual detector must mark it suspect
    within ≤3 heartbeat intervals, write assignment must route around
    it at once, no acked write may be lost, and after SIGCONT the node
    must rejoin healthy. Runs on both serving paths."""

    HB = 0.5  # subprocess heartbeat interval (s)

    @pytest.mark.parametrize("native", ["1", "0"])
    def test_pause_suspect_exclude_recover(self, tmp_path, native):
        def http_json_url(url, timeout=3):
            with urllib.request.urlopen(url, timeout=timeout) as r:
                return json.loads(r.read())

        def try_json(url):
            try:
                return http_json_url(url)
            except (OSError, ValueError):
                return None

        mport = free_port()
        va_port, vb_port = free_port(), free_port()
        dirs = [tmp_path / "va", tmp_path / "vb"]
        for d in dirs:
            d.mkdir()
        env_extra = {"WEED_NATIVE_SERVE": native}
        procs = [
            wiring.spawn_cli(
                "master", "-port", str(mport), "-nodeTimeout", "60",
                env_extra=env_extra,
            )
        ]
        maddr = f"127.0.0.1:{mport}"
        try:
            assert wait_for(
                lambda: try_json(f"http://{maddr}/cluster/status")
                is not None,
                45,
            )
            for port, d in ((va_port, dirs[0]), (vb_port, dirs[1])):
                procs.append(
                    wiring.spawn_cli(
                        "volume", "-port", str(port), "-dir", str(d),
                        "-mserver", maddr, "-heartbeat", str(self.HB),
                        env_extra=env_extra,
                    )
                )
            vb_url = f"127.0.0.1:{vb_port}"

            def assign():
                a = try_json(f"http://{maddr}/dir/assign")
                return None if a is None or a.get("error") else a

            def nodes_registered():
                h = try_json(f"http://{maddr}/cluster/health")
                return h is not None and len(h["NodeHealth"]["Nodes"]) == 2

            assert wait_for(nodes_registered, 60), "nodes never registered"
            assert wait_for(assign, 30)

            # seed writes so BOTH nodes hold writable volumes (the
            # exclusion assertion is vacuous otherwise) — and give the
            # phi detector a beat history to learn the cadence from
            acked = {}
            t0 = time.time()
            while time.time() - t0 < 30:
                a = assign()
                if a is None:
                    continue
                payload = f"gray {len(acked)} ".encode() * 20
                req = urllib.request.Request(
                    f"http://{a['url']}/{a['fid']}", data=payload,
                    method="POST",
                )
                urllib.request.urlopen(req, timeout=10).read()
                acked[a["fid"]] = (payload, a["url"])
                seen = {u for _, u in acked.values()}
                if len(seen) == 2 and len(acked) >= 8:
                    break
            assert {u for _, u in acked.values()} == {
                f"127.0.0.1:{va_port}", vb_url
            }, "writes never spread over both nodes"
            def node_row(url):
                h = http_json_url(f"http://{maddr}/cluster/health")
                return h["NodeHealth"]["Nodes"].get(url, {})

            def state_of(url):
                return node_row(url).get("State")

            # cadence warm-up: barrier on the detector's own Warmed bit
            # rather than sleeping a fixed 6 beats. The sleep assumed
            # wall time == beat count; under rig load the subprocess
            # beat threads run late and a fixed sleep can end with
            # fewer than the detector's minimum samples in its ring —
            # phi then stays pinned at 0 and the SIGSTOP below is
            # undetectable inside any timeout (the PR-18 flake)
            assert wait_for(
                lambda: node_row(vb_url).get("Warmed")
                and node_row(f"127.0.0.1:{va_port}").get("Warmed"),
                30,
            ), "detector never accumulated its minimum cadence samples"
            assert wait_for(lambda: state_of(vb_url) == "healthy", 10)

            # --- the gray failure: freeze B, sessions stay open
            paused = procs[2]
            # the promptness bound must track the LEARNED cadence, not
            # the configured one: the detector's gate opens at 2x the
            # worst observed inter-arrival gap, and on a loaded rig
            # that gap legitimately stretches past the configured tick
            # — a bound stated in configured beats flakes exactly then
            gate_s = float(node_row(vb_url).get("GateS") or 0.0)
            assert gate_s > 0.0, "warmed detector reported no gate"
            paused.send_signal(__import__("signal").SIGSTOP)
            t_pause = time.monotonic()
            assert wait_for(
                lambda: state_of(vb_url) == "suspect",
                max(10.0, gate_s + 10.0), interval=0.03,
            ), "paused node never went suspect"
            detect_s = time.monotonic() - t_pause
            # earliest detectable silence: the gate past the LAST beat
            # (which landed up to one full beat before the pause), then
            # ~a beat of margin for the phi threshold crossing and the
            # master-side evaluation, then poll slop
            assert detect_s <= gate_s + 2 * self.HB + 0.5, (
                f"suspect detection took {detect_s:.2f}s "
                f"(measured gate {gate_s:.2f}s + 2 beats + poll slop)"
            )

            # excluded from assignment while suspect — and writes keep
            # succeeding (routed at the healthy node), zero loss
            for i in range(8):
                a = assign()
                assert a is not None
                assert a["url"] != vb_url, (
                    f"assign targeted the SIGSTOP'd node: {a}"
                )
                payload = f"during-pause {i} ".encode() * 20
                req = urllib.request.Request(
                    f"http://{a['url']}/{a['fid']}", data=payload,
                    method="POST",
                )
                urllib.request.urlopen(req, timeout=10).read()
                acked[a["fid"]] = (payload, a["url"])

            # --- SIGCONT: the node must rejoin HEALTHY (hysteresis
            # holds it suspect briefly, then clean beats clear it)
            paused.send_signal(__import__("signal").SIGCONT)
            assert wait_for(
                lambda: state_of(vb_url) == "healthy", 30
            ), "node never recovered to healthy after SIGCONT"

            # zero acked-write loss across the whole episode
            for fid, (payload, url) in acked.items():
                with urllib.request.urlopen(
                    f"http://{url}/{fid}", timeout=10
                ) as r:
                    assert r.read() == payload, fid
        finally:
            wiring.reap_procs(procs)


# ---------------------------------------------------------------------------
# scenario: filer/S3-tier partition under the deadline plane


class TestFilerPartitionS3:
    """The chaos quartet faults master+volume; this covers the gateway
    tier (ROADMAP weedchaos follow-on): the S3 gateway reaches its
    filer only through a ChaosProxy pair. Under a blackhole partition,
    S3 GET/PUT carrying an X-Weed-Deadline budget must fail WITHIN the
    budget's order (bounded, never a 60 s park), and after heal the
    tier serves acked objects byte-identical."""

    def test_s3_bounded_failure_and_heal(self, tmp_path_factory):
        from seaweedfs_tpu.server.filer_server import FilerServer
        from seaweedfs_tpu.server.master_server import MasterServer
        from seaweedfs_tpu.s3api.s3api_server import S3ApiServer

        master = MasterServer(
            port=free_port(), volume_size_limit_mb=64, vacuum_interval=0
        )
        master.start()
        maddr = f"127.0.0.1:{master.port}"
        vs = wiring.start_volume_server(tmp_path_factory, maddr, "fp")
        fport = free_port()
        pair = chaos_mod.ProxyPair(f"127.0.0.1:{fport}")
        filer = FilerServer([maddr], port=fport, store="memory")
        filer.start()
        # the gateway reaches the filer ONLY through the faulted pair
        s3 = S3ApiServer(filer=pair.addr, port=free_port())
        s3.start()
        base = f"http://127.0.0.1:{s3.port}"
        try:
            assert wait_for(lambda: len(master.topology.data_nodes()) == 1)

            def s3req(url, data=None, method="GET", headers=None, timeout=30):
                req = urllib.request.Request(url, data=data, method=method)
                for k, v in (headers or {}).items():
                    req.add_header(k, v)
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return r.status, r.read()

            # healthy tier: bucket + object round-trip
            assert s3req(f"{base}/chaosbkt", method="PUT")[0] == 200
            body = b"filer partition payload " * 40
            assert s3req(
                f"{base}/chaosbkt/obj1", data=body, method="PUT"
            )[0] == 200
            status, got = s3req(f"{base}/chaosbkt/obj1")
            assert status == 200 and got == body

            # --- partition the filer: S3 requests with a deadline
            # budget fail BOUNDED (the gateway hop inherits the budget
            # → capped socket timeouts), never a full-timeout park
            pair.partition()
            budget_ms = 1500.0
            for method, data in (("GET", None), ("PUT", b"never lands")):
                t0 = time.monotonic()
                with pytest.raises((urllib.error.HTTPError, OSError)):
                    s3req(
                        f"{base}/chaosbkt/obj1",
                        data=data,
                        method=method,
                        headers={"X-Weed-Deadline": str(budget_ms)},
                        timeout=30,
                    )
                elapsed = time.monotonic() - t0
                assert elapsed < 10.0, (
                    f"{method} under partition took {elapsed:.1f}s — the "
                    f"deadline plane did not bound the filer hop"
                )

            # --- heal: the acked object reads back byte-identical and
            # PUTs flow again
            pair.heal()

            def healed():
                try:
                    s, g = s3req(f"{base}/chaosbkt/obj1", timeout=10)
                    return s == 200 and g == body
                except (OSError, urllib.error.HTTPError):
                    return False

            assert wait_for(healed, 30), "tier never healed"
            assert s3req(
                f"{base}/chaosbkt/obj2", data=b"after heal", method="PUT"
            )[0] == 200
            status, got = s3req(f"{base}/chaosbkt/obj2")
            assert status == 200 and got == b"after heal"
        finally:
            pair.stop()
            s3.stop()
            filer.stop()
            vs.stop()
            master.stop()


# ---------------------------------------------------------------------------
# scenario: 30% loss on the EC gather path


class TestLossyEcGather:
    def test_degraded_reads_survive_30pct_loss(self, tmp_path_factory):
        """Kill a shard on node A while node B (holding half the
        survivors) drops 30% of transfers mid-flight: degraded reads
        must stay byte-identical through the retry/hedge planes, with
        the fault verifiably firing."""
        from seaweedfs_tpu.server.master_server import MasterServer

        master = MasterServer(
            port=free_port(), volume_size_limit_mb=64, vacuum_interval=0
        )
        master.start()
        maddr = f"127.0.0.1:{master.port}"
        vs_a = wiring.start_volume_server(tmp_path_factory, maddr, "la")
        vs_b, pair = wiring.proxied_volume_server(tmp_path_factory, maddr, "lb")
        try:
            assert wait_for(lambda: len(master.topology.data_nodes()) == 2)
            vid, keys = wiring.seed_ec_volume(master, "lchaos")
            assert wait_for(
                lambda: wiring.registered_shards(master, vid) == 14, 30
            )
            dead = DeadShard(vid, volume_servers=[vs_a], collection="lchaos")
            dead.kill()

            # 30% of B's gRPC transfers (the shard gather wire) die
            # mid-flight — connection-granularity loss, the only kind
            # TCP can express
            pair.grpc.response.drop_conn_p = 0.30

            # generous attempt cap with real backoff: a dropped gRPC
            # stream leaves the channel in TRANSIENT_FAILURE for a
            # beat, so immediate retries fail in a burst — the jittered
            # waits are what let the link recover between attempts
            policy = retry_mod.RetryPolicy(
                attempts=12, backoff_ms=100, backoff_max_ms=600,
                retry_on=(OSError, urllib.error.HTTPError), budget=None,
                label="chaos-lossy-read",
            )
            url_a = f"127.0.0.1:{vs_a.port}"
            bad = []
            for fid, want in keys.items():
                def read_once(attempt, _fid=fid):
                    data, _ = op.download(
                        f"{url_a}/{_fid}?collection=lchaos", timeout=10
                    )
                    return data
                got = policy.run(read_once)
                if got != want:
                    bad.append(fid)
            assert not bad, f"corrupt degraded reads under loss: {bad}"
            assert (
                pair.grpc.conns_dropped + pair.grpc.conns_rst > 0
                or pair.grpc.bytes_forwarded > 0
            ), "the lossy link never carried/dropped gather traffic"
        finally:
            pair.stop()
            vs_b.stop()
            vs_a.stop()
            master.stop()
