"""Small-gap sweep: gRPC TLS, persistent needle map, query engine,
Query RPC, delta heartbeats, 5-byte offsets.

Reference roles: security/tls.go, needle_map_leveldb.go:24,
query/json/query_json.go:18 + volume_grpc_query.go:12,
master.proto:43-44 delta beats, types/offset_5bytes.go."""

import os
import socket
import subprocess
import sys
import time

import pytest


from seaweedfs_tpu.util.availability import free_port  # noqa: E402 — collision-hardened allocator


# ---------------------------------------------------------------------------
# TLS


def _make_certs(tmp_path):
    """Self-signed CA + a server/client cert signed by it. Skips (not
    errors) on images without the cryptography package — the mTLS code
    under test only ever runs where certs exist."""
    import datetime

    pytest.importorskip("cryptography", reason="no cryptography package")
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    def key():
        return rsa.generate_private_key(public_exponent=65537, key_size=2048)

    def name(cn):
        return x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, cn)])

    now = datetime.datetime.now(datetime.timezone.utc)

    ca_key = key()
    ca_cert = (
        x509.CertificateBuilder()
        .subject_name(name("weed-ca"))
        .issuer_name(name("weed-ca"))
        .public_key(ca_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now)
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(x509.BasicConstraints(ca=True, path_length=None), True)
        .sign(ca_key, hashes.SHA256())
    )

    leaf_key = key()
    leaf_cert = (
        x509.CertificateBuilder()
        .subject_name(name("seaweedfs"))
        .issuer_name(name("weed-ca"))
        .public_key(leaf_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now)
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(
            x509.SubjectAlternativeName([x509.DNSName("seaweedfs")]), False
        )
        .sign(ca_key, hashes.SHA256())
    )

    paths = {}
    for nm, data in [
        ("ca.crt", ca_cert.public_bytes(serialization.Encoding.PEM)),
        ("node.crt", leaf_cert.public_bytes(serialization.Encoding.PEM)),
        (
            "node.key",
            leaf_key.private_bytes(
                serialization.Encoding.PEM,
                serialization.PrivateFormat.TraditionalOpenSSL,
                serialization.NoEncryption(),
            ),
        ),
    ]:
        p = tmp_path / nm
        p.write_bytes(data)
        paths[nm] = str(p)
    return paths


class TestGrpcTls:
    def test_mtls_handshake_and_plaintext_rejection(self, tmp_path):
        import grpc

        from seaweedfs_tpu.pb import master_pb2, rpc
        from seaweedfs_tpu.security.tls import (
            TlsConfig,
            client_credentials,
            server_credentials,
        )

        certs = _make_certs(tmp_path)
        tls = TlsConfig(
            ca_pem=open(certs["ca.crt"], "rb").read(),
            cert_pem=open(certs["node.crt"], "rb").read(),
            key_pem=open(certs["node.key"], "rb").read(),
        )

        # a bare gRPC server with the master service behind mTLS
        from concurrent import futures

        class Impl:
            def __getattr__(self, name):
                def h(req, ctx):
                    return master_pb2.StatisticsResponse(total_size=42)

                return h

        server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        server.add_generic_rpc_handlers(
            (rpc.servicer_handler(rpc.MASTER_SERVICE, rpc.MASTER_METHODS, Impl()),)
        )
        port = free_port()
        server.add_secure_port(
            f"127.0.0.1:{port}", server_credentials(tls)
        )
        server.start()
        try:
            # mTLS client succeeds (cert CN "seaweedfs" needs override)
            ch = grpc.secure_channel(
                f"127.0.0.1:{port}",
                client_credentials(tls),
                (("grpc.ssl_target_name_override", "seaweedfs"),),
            )
            resp = rpc.master_stub(ch).Statistics(
                master_pb2.StatisticsRequest(), timeout=5
            )
            assert resp.total_size == 42
            ch.close()

            # plaintext client is refused
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            with pytest.raises(grpc.RpcError):
                rpc.master_stub(ch).Statistics(
                    master_pb2.StatisticsRequest(), timeout=5
                )
            ch.close()
        finally:
            server.stop(grace=0)

    def test_dial_seam_honors_set_tls(self, tmp_path):
        from seaweedfs_tpu.pb import rpc
        from seaweedfs_tpu.security.tls import TlsConfig

        certs = _make_certs(tmp_path)
        tls = TlsConfig(
            ca_pem=open(certs["ca.crt"], "rb").read(),
            cert_pem=open(certs["node.crt"], "rb").read(),
            key_pem=open(certs["node.key"], "rb").read(),
        )
        try:
            rpc.set_tls(tls, "seaweedfs")
            ch = rpc.dial("127.0.0.1:1")  # no connect yet; type check only
            assert ch is not None
            ch.close()
        finally:
            rpc.set_tls(None)


# ---------------------------------------------------------------------------
# persistent needle map


class TestDbNeedleMap:
    def test_roundtrip_and_resume(self, tmp_path):
        from seaweedfs_tpu.storage.needle_map import CompactNeedleMap, DbNeedleMap

        idx = str(tmp_path / "1.idx")
        nm = DbNeedleMap.load(idx)
        nm.put(5, 10, 100)
        nm.put(9, 30, 200)
        nm.put(5, 50, 120)  # overwrite
        nm.delete(9, 70)
        assert nm.get(5).offset == 50 and nm.get(5).size == 120
        assert nm.get(9).size == 0xFFFFFFFF
        assert nm.file_count == 3 and nm.deletion_count == 2
        assert nm.max_file_key == 9
        nm.close()

        # resume: no .idx replay needed (watermark), state intact
        nm2 = DbNeedleMap.load(idx)
        assert nm2.get(5).offset == 50
        assert nm2.max_file_key == 9
        assert sorted(v.key for v in nm2.items()) == [5, 9]
        nm2.close()

        # the .idx bytes are identical to what the in-memory map writes
        cm = CompactNeedleMap.load(str(tmp_path / "2.idx"))
        cm.put(5, 10, 100)
        cm.put(9, 30, 200)
        cm.put(5, 50, 120)
        cm.delete(9, 70)
        cm.close()
        assert (
            open(idx, "rb").read() == open(str(tmp_path / "2.idx"), "rb").read()
        )

    def test_tail_replay_after_external_append(self, tmp_path):
        from seaweedfs_tpu.storage import idx as idx_codec
        from seaweedfs_tpu.storage.needle_map import DbNeedleMap

        idx = str(tmp_path / "3.idx")
        nm = DbNeedleMap.load(idx)
        nm.put(1, 8, 64)
        nm.close()
        # an external writer (e.g. replication) appends to the .idx
        with open(idx, "ab") as f:
            f.write(idx_codec.pack_entry(2, 16, 128))
        nm2 = DbNeedleMap.load(idx)
        assert nm2.get(2).offset == 16
        nm2.close()

    def test_volume_with_db_map(self, tmp_path):
        from seaweedfs_tpu.storage.needle import Needle
        from seaweedfs_tpu.storage.volume import Volume

        v = Volume(str(tmp_path), 7, needle_map_kind="db")
        n = Needle(cookie=0x1234, id=42, data=b"persistent map payload")
        v.write_needle(n)
        got = v.read_needle(42, cookie=0x1234)
        assert bytes(got.data) == b"persistent map payload"
        v.close()
        v2 = Volume(str(tmp_path), 7, create=False, needle_map_kind="db")
        got = v2.read_needle(42, cookie=0x1234)
        assert bytes(got.data) == b"persistent map payload"
        v2.close()


# ---------------------------------------------------------------------------
# query engine


class TestJsonQuery:
    def test_ops(self):
        from seaweedfs_tpu.query import Query, query_json

        line = '{"name": "alice", "age": 30, "vip": true, "addr": {"city": "sf"}}'
        cases = [
            (Query("name", "=", "alice"), True),
            (Query("name", "!=", "alice"), False),
            (Query("name", "%", "al*"), True),
            (Query("name", "!%", "al*"), False),
            (Query("age", ">", "29"), True),
            (Query("age", "<=", "29"), False),
            (Query("vip", "=", "true"), True),
            (Query("addr.city", "=", "sf"), True),
            (Query("missing", "=", "x"), False),
            (Query("addr.city", "", ""), True),  # existence
        ]
        for q, expect in cases:
            passed, _ = query_json(line, [], q)
            assert passed is expect, q

    def test_projections(self):
        from seaweedfs_tpu.query import Query, query_json

        line = '{"a": 1, "b": {"c": [10, 20]}}'
        passed, values = query_json(line, ["a", "b.c.1", "nope"], Query("a", "=", "1"))
        assert passed
        assert values == [1, 20, None]

    def test_gjson_path_table(self):
        """gjson.Get path semantics table (query_json.go:18 →
        tidwall/gjson): wildcards match keys first-wins, `#` is array
        length / per-element collection, no negative indices."""
        from seaweedfs_tpu.query.json_query import _MISSING, get_path

        doc = {
            "name": {"first": "Tom", "last": "Anderson"},
            "age": 37,
            "children": ["Sara", "Alex", "Jack"],
            "friends": [
                {"first": "Dale", "last": "Murphy", "age": 44},
                {"first": "Roger", "last": "Craig", "age": 68},
                {"first": "Jane", "last": "Murphy"},
            ],
            "fav.movie": "Deer Hunter",
        }
        cases = [
            # (path, expected) — mirrors the gjson README examples
            ("name.last", "Anderson"),
            ("age", 37),
            ("children", ["Sara", "Alex", "Jack"]),
            ("children.#", 3),
            ("children.1", "Alex"),
            ("child*.2", "Jack"),
            ("c?ildren.0", "Sara"),
            ("friends.#.first", ["Dale", "Roger", "Jane"]),
            ("friends.#.age", [44, 68]),  # missing elements skipped
            ("friends.1.last", "Craig"),
            ("friends.#", 3),
            ("name.*", "Tom"),  # wildcard: first matching key wins
            ("x*", _MISSING),
            ("children.-1", _MISSING),  # gjson has no negative indexing
            ("children.9", _MISSING),
            ("friends.#.nope", []),
            ("age.#", _MISSING),  # `#` only applies to arrays
        ]
        for path, expect in cases:
            got = get_path(doc, path)
            assert got == expect or (got is expect), (path, got, expect)


# ---------------------------------------------------------------------------
# cluster-level: Query RPC + delta heartbeats


@pytest.fixture(scope="module")
def mini_cluster(tmp_path_factory):
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer(
        [str(tmp_path_factory.mktemp("sgvs"))],
        port=free_port(),
        master=f"127.0.0.1:{master.port}",
        heartbeat_interval=0.1,
        max_volume_counts=[100],
    )
    vs.start()
    deadline = time.time() + 45
    while time.time() < deadline and len(master.topology.data_nodes()) < 1:
        time.sleep(0.05)
    yield master, vs
    vs.stop()
    master.stop()


class TestQueryRpc:
    def test_select_from_json_lines(self, mini_cluster):
        import grpc

        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.pb import rpc, volume_pb2

        master, vs = mini_cluster
        rows = b"\n".join(
            [
                b'{"name": "a", "n": 1}',
                b'{"name": "b", "n": 5}',
                b'{"name": "c", "n": 9}',
            ]
        )
        ar = op.assign(f"127.0.0.1:{master.port}")
        assert not op.upload(f"{ar.url}/{ar.fid}", rows, jwt=ar.auth).error

        with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
            stripes = list(
                rpc.volume_stub(ch).Query(
                    volume_pb2.QueryRequest(
                        selections=["name", "n"],
                        from_file_ids=[ar.fid],
                        filter=volume_pb2.QueryRequest.Filter(
                            field="n", operand=">", value="2"
                        ),
                    )
                )
            )
        records = b"".join(s.records for s in stripes).decode().strip().splitlines()
        assert records == ['["b", 5]', '["c", 9]']


class TestDeltaHeartbeats:
    def test_new_volume_registers_via_delta(self, mini_cluster):
        """After the first full beat, a freshly grown volume reaches the
        master through a delta beat (O(changes) chatter)."""
        from seaweedfs_tpu.client import operation as op

        master, vs = mini_cluster
        # force growth in a new collection -> new volumes appear between
        # full beats; the master must learn them from the delta path
        ar = op.assign(f"127.0.0.1:{master.port}", collection="deltac")
        vid = int(ar.fid.split(",")[0])
        deadline = time.time() + 5
        while time.time() < deadline:
            if master.topology.lookup("deltac", vid):
                break
            time.sleep(0.05)
        assert master.topology.lookup("deltac", vid)
        assert not op.upload(f"{ar.url}/{ar.fid}", b"delta beat", jwt=ar.auth).error


# ---------------------------------------------------------------------------
# 5-byte offsets (subprocess: the switch is process-wide)


class TestFiveByteOffsets:
    def test_idx_layout_and_volume_roundtrip(self, tmp_path):
        code = f"""
import os
os.environ["WEED_VOLUME_OFFSET_SIZE"] = "5"
from seaweedfs_tpu.storage import types as t, idx
assert t.OFFSET_SIZE == 5 and idx.ENTRY_SIZE == 17
e = idx.pack_entry(7, 0xFFFFFFFFF, 123)
assert len(e) == 17
assert idx.unpack_entry(e) == (7, 0xFFFFFFFFF, 123)

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
v = Volume({str(tmp_path)!r}, 3)
v.write_needle(Needle(cookie=1, id=11, data=b"five byte offsets"))
assert bytes(v.read_needle(11, cookie=1).data) == b"five byte offsets"
v.close()
v2 = Volume({str(tmp_path)!r}, 3, create=False)
assert bytes(v2.read_needle(11, cookie=1).data) == b"five byte offsets"
print("OK")
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            cwd="/root/repo",
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "OK" in proc.stdout


# ---------------------------------------------------------------------------
# master vacuum loop + tail RPCs + durable sequencer


class TestMasterVacuumLoop:
    def test_vacuum_once_compacts_garbage(self, tmp_path_factory):
        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.server.master_server import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer

        master = MasterServer(
            port=free_port(),
            volume_size_limit_mb=64,
            garbage_threshold=0.3,
            vacuum_interval=0,  # loop off; drive _vacuum_once directly
        )
        master.start()
        vs = VolumeServer(
            [str(tmp_path_factory.mktemp("vacvs"))],
            port=free_port(),
            master=f"127.0.0.1:{master.port}",
            heartbeat_interval=0.1,
            max_volume_counts=[100],
        )
        vs.start()
        try:
            deadline = time.time() + 45
            while time.time() < deadline and len(master.topology.data_nodes()) < 1:
                time.sleep(0.05)
            ar = op.assign(f"127.0.0.1:{master.port}", collection="vacloop")
            vid = int(ar.fid.split(",")[0])
            # create garbage: write then delete a fat needle
            assert not op.upload(
                f"{ar.url}/{ar.fid}", b"x" * 20000, jwt=ar.auth
            ).error
            op.delete(f"{ar.url}/{ar.fid}")
            keeper = op.assign(f"127.0.0.1:{master.port}", collection="vacloop")
            assert not op.upload(
                f"{keeper.url}/{keeper.fid}", b"keep me", jwt=keeper.auth
            ).error

            vol = vs.store.find_volume(vid)
            assert vol.garbage_level() > 0.3
            compacted = master._vacuum_once()
            assert compacted >= 1
            assert vol.garbage_level() < 0.1
            # live needle survives compaction
            if int(keeper.fid.split(",")[0]) == vid:
                data, _ = op.download(f"{keeper.url}/{keeper.fid}")
                assert data == b"keep me"
        finally:
            vs.stop()
            master.stop()


class TestTailRpcs:
    def test_sender_streams_and_receiver_applies(self, mini_cluster, tmp_path_factory):
        import grpc

        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.pb import rpc, volume_pb2
        from seaweedfs_tpu.server.volume_server import VolumeServer

        master, vs = mini_cluster
        ar = op.assign(f"127.0.0.1:{master.port}", collection="tail")
        vid = int(ar.fid.split(",")[0])
        # incompressible payload: a text one would be stored gzipped
        # (the write path's transparent compression), and this test
        # asserts on the RAW tailed record bytes
        payload = bytes(range(256)) * 4
        assert not op.upload(f"{ar.url}/{ar.fid}", payload, jwt=ar.auth).error

        # sender drains after the idle timeout and delivers the needle
        with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
            frames = list(
                rpc.volume_stub(ch).VolumeTailSender(
                    volume_pb2.VolumeTailSenderRequest(
                        volume_id=vid, since_ns=0, idle_timeout_seconds=1
                    ),
                    timeout=30,
                )
            )
        assert frames, "expected at least one tailed needle"
        assert payload in b"".join(f.needle_body for f in frames)

        # a second server replicates the volume through TailReceiver
        vs2 = VolumeServer(
            [str(tmp_path_factory.mktemp("tailvs2"))],
            port=free_port(),
            master="",  # standalone; no heartbeats needed
        )
        vs2.start()
        try:
            with grpc.insecure_channel(f"127.0.0.1:{vs2.grpc_port}") as ch:
                rpc.volume_stub(ch).AllocateVolume(
                    volume_pb2.AllocateVolumeRequest(
                        volume_id=vid, collection="", replication="000"
                    )
                )
                rpc.volume_stub(ch).VolumeTailReceiver(
                    volume_pb2.VolumeTailReceiverRequest(
                        volume_id=vid,
                        since_ns=0,
                        idle_timeout_seconds=1,
                        source_volume_server=f"{vs.host}:{vs.port}",
                    ),
                    timeout=60,
                )
            data, _ = op.download(f"127.0.0.1:{vs2.port}/{ar.fid}")
            assert data == payload
        finally:
            vs2.stop()


class TestFileSequencer:
    def test_no_reuse_across_restart(self, tmp_path):
        from seaweedfs_tpu.sequence import FileSequencer

        path = str(tmp_path / "seq.txt")
        s = FileSequencer(path, batch=10)
        first = s.next_file_id(5)
        assert first == 1
        second = s.next_file_id(1)
        assert second == 6

        # crash (no clean shutdown): a new instance must never re-issue
        s2 = FileSequencer(path, batch=10)
        third = s2.next_file_id(1)
        assert third > second

    def test_set_max_advances(self, tmp_path):
        from seaweedfs_tpu.sequence import FileSequencer

        s = FileSequencer(str(tmp_path / "seq2.txt"), batch=10)
        s.set_max(500)
        assert s.next_file_id(1) == 501


class TestDbNeedleMapCluster:
    """-index db under a live cluster: writes, reads, restart resume,
    and vacuum (whose commit must invalidate the sqlite table)."""

    def test_write_read_vacuum_restart(self, tmp_path_factory):
        import grpc

        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.pb import rpc, volume_pb2
        from seaweedfs_tpu.server.master_server import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer

        data_dir = str(tmp_path_factory.mktemp("dbmapvs"))
        master = MasterServer(port=free_port(), volume_size_limit_mb=64)
        master.start()
        vs = VolumeServer(
            [data_dir],
            port=free_port(),
            master=f"127.0.0.1:{master.port}",
            heartbeat_interval=0.2,
            max_volume_counts=[100],
            needle_map_kind="db",
        )
        vs.start()
        vs2 = None
        try:
            deadline = time.time() + 45
            while time.time() < deadline and len(master.topology.data_nodes()) < 1:
                time.sleep(0.05)

            keep = op.assign(f"127.0.0.1:{master.port}", collection="dbm")
            assert not op.upload(
                f"{keep.url}/{keep.fid}", b"keeper " * 300, jwt=keep.auth
            ).error
            doomed = op.assign(f"127.0.0.1:{master.port}", collection="dbm")
            assert not op.upload(
                f"{doomed.url}/{doomed.fid}", b"x" * 30000, jwt=doomed.auth
            ).error
            op.delete(f"{doomed.url}/{doomed.fid}")

            # vacuum through the gRPC 4-phase (db map rebuilds on commit)
            with grpc.insecure_channel(f"127.0.0.1:{vs.grpc_port}") as ch:
                stub = rpc.volume_stub(ch)
                for v in {int(keep.fid.split(",")[0]), int(doomed.fid.split(",")[0])}:
                    stub.VacuumVolumeCompact(
                        volume_pb2.VacuumVolumeCompactRequest(volume_id=v)
                    )
                    stub.VacuumVolumeCommit(
                        volume_pb2.VacuumVolumeCommitRequest(volume_id=v)
                    )
                    stub.VacuumVolumeCleanup(
                        volume_pb2.VacuumVolumeCleanupRequest(volume_id=v)
                    )
            data, _ = op.download(f"{vs.host}:{vs.port}/{keep.fid}")
            assert data == b"keeper " * 300

            # restart the volume server on the same directory: the db
            # map resumes (or rebuilds) and serves the same bytes
            vs.stop()
            vs2 = VolumeServer(
                [data_dir],
                port=free_port(),
                master=f"127.0.0.1:{master.port}",
                heartbeat_interval=0.2,
                max_volume_counts=[100],
                needle_map_kind="db",
            )
            vs2.start()
            data, _ = op.download(f"{vs2.host}:{vs2.port}/{keep.fid}")
            assert data == b"keeper " * 300
            import urllib.error

            with pytest.raises(urllib.error.HTTPError):
                op.download(f"{vs2.host}:{vs2.port}/{doomed.fid}")
        finally:
            (vs2 or vs).stop()
            master.stop()


class TestEtcdSequencer:
    """External-KV sequencer over the etcd v3 gateway REST protocol
    (sequence/etcd_sequencer.go role) against tests/cloud_fakes.FakeEtcd."""

    @pytest.fixture()
    def etcd(self):
        from tests.cloud_fakes import FakeEtcd

        f = FakeEtcd()
        f.start()
        yield f
        f.stop()

    def test_allocates_monotonic_ranges(self, etcd):
        from seaweedfs_tpu.sequence import EtcdSequencer

        s = EtcdSequencer(etcd.endpoint, step=50)
        a = s.next_file_id(1)
        b = s.next_file_id(10)
        c = s.next_file_id(1)
        assert a >= 1 and b == a + 1 and c == b + 10

    def test_two_sequencers_never_overlap(self, etcd):
        """Two masters against one etcd: CAS range reservation keeps
        their id ranges disjoint (the multi-master coordination the
        external KV exists for)."""
        from seaweedfs_tpu.sequence import EtcdSequencer

        s1 = EtcdSequencer(etcd.endpoint, step=20)
        s2 = EtcdSequencer(etcd.endpoint, step=20)
        got1 = {s1.next_file_id(1) for _ in range(60)}
        got2 = {s2.next_file_id(1) for _ in range(60)}
        assert not got1 & got2

    def test_survives_restart_without_reuse(self, etcd):
        from seaweedfs_tpu.sequence import EtcdSequencer

        s = EtcdSequencer(etcd.endpoint, step=10)
        issued = [s.next_file_id(1) for _ in range(15)]
        s2 = EtcdSequencer(etcd.endpoint, step=10)
        fresh = [s2.next_file_id(1) for _ in range(15)]
        assert not set(issued) & set(fresh)

    def test_key_deleted_externally_does_not_spin(self, etcd):
        """If the sequence key is deleted behind the sequencer's back, a
        VALUE compare can never match the absent key — the reserve loop
        must fall back to create-if-absent instead of spinning."""
        from seaweedfs_tpu.sequence import EtcdSequencer

        s = EtcdSequencer(etcd.endpoint, step=5)
        first = s.next_file_id(1)
        s._kv.call("deleterange", {"key": s._key_b64})
        # exhaust the local reservation to force a fresh CAS round
        ids = [s.next_file_id(1) for _ in range(20)]
        assert len(set(ids)) == 20 and min(ids) > first

    def test_set_max_lifts_stored_value(self, etcd):
        from seaweedfs_tpu.sequence import EtcdSequencer

        s = EtcdSequencer(etcd.endpoint, step=10)
        s.set_max(10_000)
        assert s.next_file_id(1) == 10_001
        # a fresh sequencer sees the lifted max, never reissues below it
        s2 = EtcdSequencer(etcd.endpoint, step=10)
        assert s2.next_file_id(1) > 10_000

    def test_gates_on_connectivity(self):
        from seaweedfs_tpu.sequence import EtcdSequencer

        with pytest.raises(RuntimeError, match="cannot reach"):
            EtcdSequencer("127.0.0.1:1")

    def test_master_assigns_through_etcd_sequencer(self, etcd):
        """A MasterServer wired to the etcd sequencer serves
        /dir/assign with etcd-reserved ids."""
        from seaweedfs_tpu.sequence import EtcdSequencer
        from seaweedfs_tpu.server.master_server import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        import tempfile

        master = MasterServer(
            port=free_port(),
            volume_size_limit_mb=64,
            sequencer=EtcdSequencer(etcd.endpoint),
        )
        master.start()
        vs = VolumeServer(
            [tempfile.mkdtemp()],
            port=free_port(),
            master=f"127.0.0.1:{master.port}",
            heartbeat_interval=0.1,
            max_volume_counts=[100],
        )
        vs.start()
        try:
            deadline = time.time() + 30
            while time.time() < deadline and not master.topology.data_nodes():
                time.sleep(0.05)
            import json as _json
            import urllib.request

            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign", timeout=10
            ) as r:
                assert r.status == 200
                fid = _json.loads(r.read())["fid"]
            assert "," in fid
            # etcd now holds a reserved max covering the issued id
            assert master.sequencer._get() >= 1
        finally:
            vs.stop()
            master.stop()
