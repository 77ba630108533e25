"""The transport of a remote survivor span (ISSUE 37): the holder's
`GET /ec/shard/read` on its HTTP data plane, and the rebuilder's readers
(`VolumeServer._remote_rebuild_readers`) that fetch into the ring slot
over it and fall back to `VolumeEcShardRead` by what they can see.

Three volume servers in one process under one master, the 14 shards of
one seeded volume placed as the rack configuration places them (A
{0,4,8,12}, C {2,6,10}, D {3,7,11}, B's four lost), host codecs
everywhere: what is asserted is bytes, statuses, counts and spans."""

import json
import os
import shutil
import socket
import threading
import time
import types
import urllib.error
import urllib.request

import grpc
import numpy as np
import pytest

from seaweedfs_tpu import trace
from seaweedfs_tpu.ec import ec_files, ec_stream
from seaweedfs_tpu.ec.ec_volume import RemoteEcAttachment
from seaweedfs_tpu.pb import master_pb2, rpc, volume_pb2
from seaweedfs_tpu.qos.admission import AdmissionController
from seaweedfs_tpu.scrub.arbiter import BandwidthArbiter, set_arbiter
from seaweedfs_tpu.security.guard import Guard
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.stats.metrics import EC_REMOTE_FETCH, EC_REPAIR_BYTES_READ
from seaweedfs_tpu.util.availability import free_port
from tests.test_ec_rack_rebuild_cell import _wait_for
from tests.test_ec_rebuild_tile import _write_shard_set

KIB, MIB = 1 << 10, 1 << 20
VID = 37
TILE = 256 * KIB
# four whole tiles and a tail of twelve bytes: five spans a survivor
SHARD_BYTES = MIB + 12
SPANS = 5
PLACEMENT = {"A": [0, 4, 8, 12], "C": [2, 6, 10], "D": [3, 7, 11]}
LOST = [1, 5, 9, 13]
ROUTE = "/ec/shard/read"


def _lookup(master, vid: int) -> dict[int, list[str]]:
    """Shard id -> holders' urls, in the order the master gives them."""
    with rpc.dial(f"127.0.0.1:{master.grpc_port}") as ch:
        resp = rpc.master_stub(ch).LookupEcVolume(
            master_pb2.LookupEcVolumeRequest(volume_id=vid), timeout=5)
    return {e.shard_id: [loc.url for loc in e.locations]
            for e in resp.shard_id_locations if e.locations}


@pytest.fixture(scope="module")
def rack(tmp_path_factory):
    """The cluster after the loss: every survivor mounted where the
    placement puts it, the master's lookup naming C and D for their six."""
    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    origin = str(tmp_path_factory.mktemp("origin") / str(VID))
    _write_shard_set(origin, SHARD_BYTES, seed=3700)
    servers = {}
    previous = set_arbiter(BandwidthArbiter(total_bytes_s=64e9))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ec_stream, "REBUILD_TILE_BYTES", TILE)
        try:
            for name, ids in PLACEMENT.items():
                vs = servers[name] = VolumeServer(
                    [str(tmp_path_factory.mktemp("span" + name))], port=free_port(),
                    master=f"127.0.0.1:{master.port}", heartbeat_interval=0.2,
                    max_volume_counts=[100], rack=name, ec_codec="native",
                )
                vs.start()
                for sid in ids:
                    shutil.copy(origin + ec_files.to_ext(sid), vs.store.locations[0].directory)
                vs.store.mount_ec_shards(VID, "", ids)
            want = {sid: [f"127.0.0.1:{servers[name].port}"]
                    for name, ids in PLACEMENT.items() for sid in ids}
            _wait_for("the master's lookup to name every holder",
                      lambda: _lookup(master, VID) == want)
            yield types.SimpleNamespace(
                master=master, origin=origin, **{k.lower(): v for k, v in servers.items()})
        finally:
            set_arbiter(previous)
            trace.reset()
            for vs in servers.values():
                vs.stop()
            master.stop()


def _get(vs, query: str, headers: dict | None = None) -> tuple[int, bytes]:
    req = urllib.request.Request(f"http://127.0.0.1:{vs.port}{ROUTE}?{query}",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _over_grpc(vs, sid: int, offset: int, size: int) -> bytes:
    with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
        return b"".join(r.data for r in rpc.volume_stub(ch).VolumeEcShardRead(
            volume_pb2.VolumeEcShardReadRequest(
                volume_id=VID, shard_id=sid, offset=offset, size=size), timeout=10))


# --- the holder's route ---------------------------------------------------------------


@pytest.mark.parametrize("offset,size,want", [
    (300 * KIB + 7, 200 * KIB, 200 * KIB),       # inside the shard
    (SHARD_BYTES - 4 * KIB, 4 * KIB, 4 * KIB),   # its tail, to the last byte
    (SHARD_BYTES - 100, 4 * KIB, 100),           # past its end: clamped
    (SHARD_BYTES + 5, 4 * KIB, 0),               # wholly past it: nothing
], ids=["inside", "tail", "clamped", "beyond"])
def test_route_returns_the_bytes_of_the_rpc(rack, offset, size, want):
    status, body = _get(rack.c, f"volumeId={VID}&shard=6&offset={offset}&size={size}")
    assert status == 200 and len(body) == want
    assert body == _over_grpc(rack.c, 6, offset, size)
    with open(rack.origin + ec_files.to_ext(6), "rb") as f:
        f.seek(offset)
        assert body == f.read(size)


def test_route_keeps_the_connection(rack):
    """Two spans down one connection, each whole (the sendfile leaves
    the connection aligned for the next request)."""
    with socket.create_connection(("127.0.0.1", rack.d.port), timeout=10) as s:
        for offset in (0, 512 * KIB):
            s.sendall(f"GET {ROUTE}?volumeId={VID}&shard=3&offset={offset}&size={TILE} "
                      f"HTTP/1.1\r\nHost: x\r\n\r\n".encode())
            buf = b""
            while b"\r\n\r\n" not in buf:
                buf += s.recv(65536)
            head, _, body = buf.partition(b"\r\n\r\n")
            assert head.startswith(b"HTTP/1.1 200 OK") and b"Connection: close" not in head
            assert f"Content-Length: {TILE}".encode() in head
            while len(body) < TILE:
                body += s.recv(TILE - len(body))
            with open(rack.origin + ec_files.to_ext(3), "rb") as f:
                f.seek(offset)
                assert body == f.read(TILE)


def test_route_404s_what_is_not_a_mounted_shard_file(rack, monkeypatch):
    span = "offset=0&size=4096"
    assert _get(rack.c, f"volumeId={VID + 1}&shard=2&{span}")[0] == 404   # unknown volume
    assert _get(rack.c, f"volumeId={VID}&shard=3&{span}")[0] == 404       # mounted on D, not here
    # a tiered-away shard goes through the RPC, which streams it from the backend
    ev = rack.c.store.find_ec_volume(VID)
    monkeypatch.setattr(ev, "remote", RemoteEcAttachment(
        "dir.cold", SHARD_BYTES, {9: {"key": "k", "size": SHARD_BYTES}}))
    assert _get(rack.c, f"volumeId={VID}&shard=9&{span}")[0] == 404
    # the tombstone check is the needle path's
    assert _get(rack.c, f"volumeId={VID}&shard=2&{span}&fileKey=5")[0] == 404
    assert _get(rack.c, f"volumeId={VID}&shard=2&{span}")[0] == 200
    assert _get(rack.c, f"volumeId={VID}&shard=two&{span}")[0] == 400
    assert _get(rack.c, f"volumeId={VID}&shard=2&offset=-1&size=8")[0] == 400


def test_route_answers_a_spent_deadline_with_504_and_no_bytes(rack):
    status, body = _get(rack.c, f"volumeId={VID}&shard=2&offset=0&size=4096",
                        {"X-Weed-Deadline": "-5.0"})
    assert status == 504 and b"deadline" in body
    assert _get(rack.c, f"volumeId={VID}&shard=2&offset=0&size=4096",
                {"X-Weed-Deadline": "5000.0"})[0] == 200


def test_route_is_not_served_beside_mtls(rack, monkeypatch):
    monkeypatch.setattr(rpc, "_TLS", types.SimpleNamespace(is_enabled=True))
    assert _get(rack.c, f"volumeId={VID}&shard=2&offset=0&size=4096")[0] == 404


def test_route_honours_the_white_list(rack, monkeypatch):
    monkeypatch.setattr(rack.c, "guard", Guard(white_list=["10.9.8.7"]))
    assert _get(rack.c, f"volumeId={VID}&shard=2&offset=0&size=4096")[0] == 401
    monkeypatch.setattr(rack.c, "guard", Guard(white_list=["127.0.0.1"]))
    assert _get(rack.c, f"volumeId={VID}&shard=2&offset=0&size=4096")[0] == 200


def test_route_is_not_charged_to_the_admission_bucket(rack, monkeypatch):
    """One token, no refill: the second foreground request of a client
    is shed, a repair's spans pass."""
    monkeypatch.setattr(rack.d._http_server, "admission",
                        AdmissionController(rate=1e-6, burst=1.0))

    def status_of(path: str) -> int:
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{rack.d.port}{path}", timeout=10) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    assert [status_of("/status") for _ in range(2)] == [200, 503]
    span = f"{ROUTE}?volumeId={VID}&shard=7&offset=0&size=4096"
    assert [status_of(span) for _ in range(4)] == [200] * 4
    assert status_of("/status") == 503


# --- the rebuilder's readers ------------------------------------------------------------


class Served:
    """Wraps one holder's route: counts the requests, and answers them
    as `answer(handler)` says where it is given (else as the holder does)."""

    def __init__(self, mp, vs, answer=None):
        self.calls: list[str] = []
        cls = vs._http_server.RequestHandlerClass
        real = cls._serve_ec_shard_span

        def serve(handler):
            self.calls.append(handler.path)
            return real(handler) if answer is None else answer(handler)

        mp.setattr(cls, "_serve_ec_shard_span", serve)


def _rebuild(rack, metadata=None) -> dict:
    """One repair on A: the response's shard ids, the verb's report line
    and the spans it left; the rebuilt files are held to the lost ones
    and removed again."""
    import logging

    handler = logging.Handler()
    lines = []
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("seaweedfs_tpu")
    logger.addHandler(handler)
    trace.reset()
    base = os.path.join(rack.a.store.locations[0].directory, str(VID))
    try:
        with grpc.insecure_channel(f"127.0.0.1:{rack.a.grpc_port}") as ch:
            resp = rpc.volume_stub(ch).VolumeEcShardsRebuild(
                volume_pb2.VolumeEcShardsRebuildRequest(volume_id=VID),
                timeout=60, metadata=metadata)
        same = []
        for sid in LOST:
            with open(base + ec_files.to_ext(sid), "rb") as got, \
                    open(rack.origin + ec_files.to_ext(sid), "rb") as want:
                same.append(got.read() == want.read())
    finally:
        logger.removeHandler(handler)
        for sid in LOST:
            if os.path.exists(base + ec_files.to_ext(sid)):
                os.remove(base + ec_files.to_ext(sid))
    reports = [json.loads(line.partition("report=")[2]) for line in lines
               if "ec.rebuild vid=" in line and "report=" in line]
    assert len(reports) == 1, lines
    return {"rebuilt": list(resp.rebuilt_shard_ids), "same": same, "report": reports[0],
            "spans": trace.debug_payload(n=512)["recent"]}


def test_a_repair_whose_holders_know_the_route_takes_the_data_plane(rack):
    http0, grpc0 = EC_REMOTE_FETCH.value("http"), EC_REMOTE_FETCH.value("grpc")
    remote0 = EC_REPAIR_BYTES_READ.value("remote")
    with pytest.MonkeyPatch.context() as mp:
        served = [Served(mp, rack.c), Served(mp, rack.d)]
        got = _rebuild(rack, metadata=((trace.TRACE_HEADER, "00000000000000ab:000000cd:serve"),))
    assert got["rebuilt"] == LOST and got["same"] == [True] * 4
    report = got["report"]
    assert report["remote_fetches"] == report["remote_fetches_dataplane"] == 6 * SPANS
    assert [len(s.calls) for s in served] == [3 * SPANS] * 2
    assert (report["remote_survivors"], report["survivor_bytes_remote"]) == (6, 6 * SHARD_BYTES)
    assert EC_REMOTE_FETCH.value("http") - http0 == 6 * SPANS
    assert EC_REMOTE_FETCH.value("grpc") == grpc0
    assert EC_REPAIR_BYTES_READ.value("remote") - remote0 == 6 * SHARD_BYTES
    # the root span carries the two counts, as the report line does
    root = [s for s in got["spans"] if s["name"] == "ec_stream.rebuild"]
    assert len(root) == 1
    for key in ("remote_fetches", "remote_fetches_dataplane"):
        assert root[0]["annot"][key] == str(report[key]), key
    # the holders' spans of the reads they served: one a fetch, under
    # the rebuild verb's span, in its trace, named as the RPC's are
    verb = [s for s in got["spans"] if s["name"] == "volume.ec_rebuild"]
    reads = [s for s in got["spans"] if s["name"] == "volume.ec_shard_read"]
    assert len(verb) == 1 and len(reads) == 6 * SPANS
    for s in reads:
        assert s["parent"] == verb[0]["span"] and s["trace"] == "00000000000000ab"
        assert s["annot"]["transport"] == "http" and s["annot"]["vid"] == str(VID)
        assert int(s["annot"]["shard"]) in PLACEMENT["C"] + PLACEMENT["D"]
        assert s["status"] == 200 and s["bytes"] in (TILE, 12)
    assert not [s for s in got["spans"] if s["name"] == "volume.get"]


def test_metrics_page_carries_the_fetch_counter(rack):
    with urllib.request.urlopen(f"http://127.0.0.1:{rack.a.port}/metrics", timeout=10) as r:
        text = r.read().decode()
    assert 'weed_ec_remote_fetch_total{transport="http"}' in text


def test_a_holder_that_answers_404_is_asked_once_and_fetched_over_grpc(rack):
    def not_found(handler):
        handler._json({"error": "not found"}, 404)

    grpc0 = EC_REMOTE_FETCH.value("grpc")
    with pytest.MonkeyPatch.context() as mp:
        old, new = Served(mp, rack.c, not_found), Served(mp, rack.d)
        got = _rebuild(rack)
    assert got["rebuilt"] == LOST and got["same"] == [True] * 4
    assert len(old.calls) == 1 and len(new.calls) == 3 * SPANS
    report = got["report"]
    assert report["remote_fetches"] == 6 * SPANS
    assert report["remote_fetches_dataplane"] == 3 * SPANS
    assert EC_REMOTE_FETCH.value("grpc") - grpc0 == 3 * SPANS
    wires = {s["annot"]["shard"]: s["annot"]["transport"]
             for s in got["spans"] if s["name"] == "volume.ec_shard_read"}
    assert {int(k) for k, v in wires.items() if v == "grpc"} == set(PLACEMENT["C"])
    assert {int(k) for k, v in wires.items() if v == "http"} == set(PLACEMENT["D"])


def test_a_holder_with_no_listener_on_the_route_is_fetched_over_grpc(rack, monkeypatch):
    """A refused connection is a holder that does not know the route."""
    from seaweedfs_tpu.client import operation

    dialed = []
    real = operation._RawHTTPConnection.__init__

    def refuse_c(self, host, port, timeout):
        dialed.append(port)
        if port == rack.c.port:
            raise ConnectionRefusedError(111, "refused")
        real(self, host, port, timeout)

    monkeypatch.setattr(operation._RawHTTPConnection, "__init__", refuse_c)
    got = _rebuild(rack)
    assert got["rebuilt"] == LOST and got["same"] == [True] * 4
    assert dialed.count(rack.c.port) == 1
    assert got["report"]["remote_fetches"] == 6 * SPANS
    assert got["report"]["remote_fetches_dataplane"] == 3 * SPANS


def test_beside_mtls_no_http_fetch_is_made(rack, monkeypatch):
    monkeypatch.setattr(rpc, "_TLS", types.SimpleNamespace(is_enabled=True))
    # the servers' ports were bound in the clear: dial them so
    monkeypatch.setattr(rpc, "dial", grpc.insecure_channel)
    served = [Served(monkeypatch, rack.c), Served(monkeypatch, rack.d)]
    got = _rebuild(rack)
    assert got["rebuilt"] == LOST and got["same"] == [True] * 4
    assert [s.calls for s in served] == [[], []]
    assert got["report"]["remote_fetches"] == 6 * SPANS
    assert got["report"]["remote_fetches_dataplane"] == 0


def test_a_body_cut_short_fails_the_fetch_and_the_next_url_is_tried(rack, monkeypatch):
    """Shard 2 on two holders; the first the master names sends half of
    every span and hangs up, and its RPC fails too: the fetch is made
    on the RPC, fails there, and the shard's next url serves it."""
    sid = 2
    shutil.copy(rack.origin + ec_files.to_ext(sid), rack.d.store.locations[0].directory)
    rack.d.store.mount_ec_shards(VID, "", [sid])
    try:
        urls = _wait_for("two holders of shard 2",
                         lambda: (lambda u: u if len(u) == 2 else None)(
                             _lookup(rack.master, VID).get(sid, [])))
        first, second = (
            (rack.c, rack.d) if urls[0].endswith(f":{rack.c.port}") else (rack.d, rack.c))

        def half(handler):
            if f"shard={sid}&" not in handler.path:
                return real(handler)
            size = int(handler.path.rpartition("size=")[2])
            handler._trace_status = 200
            handler.wfile.write(b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n" % size
                                + b"\0" * (size // 2))
            handler.close_connection = True

        real = first._http_server.RequestHandlerClass._serve_ec_shard_span
        cut, whole = Served(monkeypatch, first, half), Served(monkeypatch, second)
        rpc_calls = []
        shards = first.store.find_ec_volume(VID).shards

        def medium_gone(offset, size):
            rpc_calls.append(offset)
            raise OSError("medium gone")

        monkeypatch.setitem(shards, sid, types.SimpleNamespace(
            size=shards[sid].size, read_at=medium_gone))
        got = _rebuild(rack)
    finally:
        rack.d.store.unmount_ec_shards(VID, [sid])
        os.remove(os.path.join(rack.d.store.locations[0].directory,
                               str(VID) + ec_files.to_ext(sid)))
        _wait_for("one holder of shard 2 again",
                  lambda: len(_lookup(rack.master, VID).get(sid, [])) == 1)
    assert got["rebuilt"] == LOST and got["same"] == [True] * 4
    assert sum(f"shard={sid}&" in p for p in cut.calls) == SPANS
    assert len(rpc_calls) == SPANS
    assert sum(f"shard={sid}&" in p for p in whole.calls) == SPANS
    assert got["report"]["remote_fetches"] == got["report"]["remote_fetches_dataplane"] == 6 * SPANS


def test_a_kept_connection_the_holder_let_go_is_dialed_again(rack, monkeypatch):
    """The holder reaps idle connections (-serveIdleMs); the next fetch
    of that thread finds its connection gone and makes a fresh one."""
    from seaweedfs_tpu.client import operation

    monkeypatch.setattr(rack.d._http_server, "serve_idle_ms", 50)
    dialed = []
    real = operation._RawHTTPConnection.__init__

    def dial(self, host, port, timeout):
        dialed.append(port)
        real(self, host, port, timeout)

    monkeypatch.setattr(operation._RawHTTPConnection, "__init__", dial)
    readers, closer, report = rack.a._remote_rebuild_readers(VID, set(PLACEMENT["A"]))
    try:
        row = np.zeros(TILE, dtype=np.uint8)
        with open(rack.origin + ec_files.to_ext(7), "rb") as f:
            for offset in (0, TILE):
                assert readers[7](offset, row) == TILE
                assert row.tobytes() == f.read(TILE)
                time.sleep(0.3)
        assert dialed == [rack.d.port] * 2
        assert report()["remote_fetches_dataplane"] == report()["remote_fetches"] == 2
    finally:
        closer()


def test_a_holder_that_does_not_answer_in_time_fails_the_fetch_as_a_timeout(rack, monkeypatch):
    """The readers' sockets block under the kernel's timeouts (the
    caller's budget, 30 s at most): a holder that sits on a request
    costs the budget once, not once more over the RPC."""
    from seaweedfs_tpu.util import deadline

    def late(handler):
        time.sleep(1.5)
        return real(handler)

    real = rack.c._http_server.RequestHandlerClass._serve_ec_shard_span
    Served(monkeypatch, rack.c, late)
    rpcs = []
    monkeypatch.setitem(rack.c.store.find_ec_volume(VID).shards, 2, types.SimpleNamespace(
        size=SHARD_BYTES, read_at=lambda offset, size: rpcs.append(offset) or b""))
    with deadline.scope(deadline.Deadline.after(0.5)):
        readers, closer, _ = rack.a._remote_rebuild_readers(VID, set(PLACEMENT["A"]))
    try:
        row = np.zeros(TILE, dtype=np.uint8)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            readers[2](0, row)
        assert time.monotonic() - t0 < 1.4 and rpcs == []
    finally:
        closer()


def test_readers_under_more_threads_than_cores_lose_no_fetch(rack):
    """The readers' shared books (connections, verdicts, counts) under
    24 threads and a short switch interval: every row right, every
    fetch counted once, each holder asked first by one thread alone."""
    import sys

    remote = PLACEMENT["C"] + PLACEMENT["D"]
    files = {sid: np.fromfile(rack.origin + ec_files.to_ext(sid), dtype=np.uint8)
             for sid in remote}
    readers, closer, report = rack.a._remote_rebuild_readers(VID, set(PLACEMENT["A"]))
    span, each, wrong = 64 * KIB, 12, []

    def work(n: int) -> None:
        row = np.zeros(span, dtype=np.uint8)
        for k in range(each):
            sid, offset = remote[(n + k) % 6], ((n * each + k) % 16) * span
            if readers[sid](offset, row) != span or not np.array_equal(
                    row, files[sid][offset:offset + span]):
                wrong.append((n, k))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(n,)) for n in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        closer()
    assert wrong == []
    assert report()["remote_fetches"] == report()["remote_fetches_dataplane"] == 24 * each


def test_readers_fill_the_row_they_are_given(rack):
    """The contract itself, off the driver: read_into(offset, dest)
    returns the bytes it placed in `dest`, a span that runs past the
    shard is the ValueError it always was, the closer shuts the wires."""
    readers, closer, report = rack.a._remote_rebuild_readers(VID, set(PLACEMENT["A"]))
    try:
        assert sorted(readers) == sorted(PLACEMENT["C"] + PLACEMENT["D"])
        row = np.zeros(TILE, dtype=np.uint8)
        assert readers[10](TILE, row) == TILE
        with open(rack.origin + ec_files.to_ext(10), "rb") as f:
            f.seek(TILE)
            assert row.tobytes() == f.read(TILE)
        with pytest.raises(ValueError, match="returned 12 of"):
            readers[10](4 * TILE, row)
        assert report() == {"arbiter_wait_s": 0.0, "remote_fetches": 1,
                            "remote_fetches_dataplane": 1}
    finally:
        closer()
    leftover = [t for t in threading.enumerate() if t.name.startswith("ec-stream-")]
    assert not leftover
