"""Degraded-read fast path + repair-bandwidth-frugal rebuild
(docs/SCRUB.md degraded section): the reconstructed-tile cache, the
first-k-wins parallel shard gather through the shared qos.hedge attempt
pool, the rebuild piggyback session, and the fast-path load-tracker
wiring.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
import urllib.request

import pytest

from seaweedfs_tpu.ec import ec_files, repair_session
from seaweedfs_tpu.ec.codec import new_encoder
from seaweedfs_tpu.ec.ec_volume import NotEnoughShards
from seaweedfs_tpu.ec.tile_cache import TileCache
from seaweedfs_tpu.qos import hedge
from seaweedfs_tpu.stats.metrics import (
    EC_DEGRADED_READS,
    EC_REPAIR_BYTES_READ,
    EC_REPAIR_BYTES_WRITTEN,
    EC_REPAIR_DONATED_BYTES,
    EC_TILE_CACHE,
)
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import Volume

from tests.faults import DeadShard


def make_needle(nid, data, cookie=0x12345678):
    return Needle(cookie=cookie, id=nid, data=data)


def _local_ec_store(tmp_path, n_needles=40, vid=9, seed=5):
    d = str(tmp_path)
    v = Volume(d, vid)
    rng = random.Random(seed)
    payload = {}
    for k in range(1, n_needles + 1):
        data = bytes(rng.randbytes(rng.randint(500, 4000)))
        payload[k] = data
        v.write_needle(make_needle(k, data))
    v.close()
    base = os.path.join(d, str(vid))
    ec_files.write_ec_files(base, rs=new_encoder(backend="cpu"))
    ec_files.write_sorted_file_from_idx(base)
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    store = Store([d], ec_backend="cpu")
    assert store.find_ec_volume(vid) is not None
    return store, payload


def _tile_counts():
    return EC_TILE_CACHE.value("hit"), EC_TILE_CACHE.value("miss")


# ---------------------------------------------------------------------------
class TestTileCache:
    def test_lru_eviction_bounds_bytes(self):
        c = TileCache(capacity_bytes=3 * 100, tile_bytes=4096)
        for i in range(10):
            c.put(0, i * 4096, bytes([i]) * 100)
            assert c.total_bytes <= 300
        # the oldest tiles were evicted, the newest survive
        assert c.get(0, 9 * 4096) is not None
        assert c.get(0, 0) is None

    def test_get_touches_lru_order(self):
        c = TileCache(capacity_bytes=2 * 100, tile_bytes=4096)
        c.put(0, 0, b"a" * 100)
        c.put(0, 4096, b"b" * 100)
        assert c.get(0, 0) is not None  # touch: 0 is now most-recent
        c.put(0, 8192, b"c" * 100)  # evicts 4096, not 0
        assert c.get(0, 0) is not None
        assert c.get(0, 4096) is None

    def test_covers_spans_and_partial_tail(self):
        c = TileCache(capacity_bytes=1 << 20, tile_bytes=4096)
        c.put(3, 0, b"x" * 4096)
        c.put(3, 4096, b"y" * 1000)  # short tail tile
        assert c.covers(3, 100, 200)
        assert c.covers(3, 4000, 200)  # crosses into the tail tile
        assert c.covers(3, 4096, 1000)
        assert not c.covers(3, 4096, 2000)  # beyond the cached tail
        assert not c.covers(3, 8192, 1)
        assert not c.covers(4, 0, 1)  # other shard

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("WEED_EC_TILE_CACHE", "0")
        c = TileCache()
        assert not c.enabled
        c.put(0, 0, b"z" * 10)
        assert c.get(0, 0) is None

    def test_invalidate_drops_everything(self):
        c = TileCache(capacity_bytes=1 << 20, tile_bytes=4096)
        c.put(0, 0, b"x" * 50)
        c.invalidate()
        assert c.total_bytes == 0
        assert c.get(0, 0) is None
        assert c.invalidations == 1


# ---------------------------------------------------------------------------
class TestFirstKGather:
    def test_first_k_wins_does_not_wait_for_stragglers(self):
        def fast(tag):
            return lambda done: tag

        def slow(done):
            time.sleep(3.0)
            return "slow"

        t0 = time.perf_counter()
        got = hedge.gather_first_k(
            {"a": fast("a"), "b": fast("b"), "z": slow}, 2, timeout=10.0
        )
        elapsed = time.perf_counter() - t0
        assert set(got) == {"a", "b"}
        assert elapsed < 2.0, "gather blocked on the straggler"

    def test_failures_and_nones_are_misses(self):
        def boom(done):
            raise OSError("down")

        got = hedge.gather_first_k(
            {"x": boom, "y": lambda done: None, "z": lambda done: 7},
            2,
            timeout=5.0,
        )
        assert got == {"z": 7}

    def test_done_event_set_after_k(self):
        saw = {}

        def task(tag):
            def run(done):
                saw[tag] = done
                return tag

            return run

        got = hedge.gather_first_k({1: task(1), 2: task(2)}, 1, timeout=5.0)
        assert len(got) == 1
        deadline = time.time() + 2.0
        while time.time() < deadline and not all(
            d.is_set() for d in saw.values()
        ):
            time.sleep(0.01)
        assert all(d.is_set() for d in saw.values())


# ---------------------------------------------------------------------------
class TestDegradedRead:
    def test_cached_vs_fresh_byte_identity(self, tmp_path):
        store, payload = _local_ec_store(tmp_path)
        ev = store.find_ec_volume(9)
        assert ev.quarantine_shard(0, "test")
        h0, m0 = _tile_counts()
        d0 = EC_DEGRADED_READS.value()
        fresh = {k: bytes(ev.read_needle(k).data) for k in payload}
        h1, m1 = _tile_counts()
        assert m1 > m0, "first pass must decode at least one tile"
        cached = {k: bytes(ev.read_needle(k).data) for k in payload}
        h2, m2 = _tile_counts()
        assert m2 == m1, "second pass must be all cache hits"
        assert h2 > h1
        assert EC_DEGRADED_READS.value() > d0
        for k in payload:
            assert fresh[k] == payload[k] == cached[k]
        store.close()

    def test_remount_invalidates_cache(self, tmp_path):
        store, payload = _local_ec_store(tmp_path)
        ev = store.find_ec_volume(9)
        ev.quarantine_shard(0, "test")
        for k in list(payload)[:5]:
            ev.read_needle(k)
        assert ev.tile_cache.total_bytes > 0
        inv0 = ev.tile_cache.invalidations
        # rebuild regenerates the .bad-renamed shard; remount must drop
        # every cached tile (they were decoded against the old state)
        rebuilt = ec_files.rebuild_ec_files(
            os.path.join(str(tmp_path), "9"), rs=new_encoder(backend="cpu")
        )
        assert rebuilt == [0]
        store.mount_ec_shards(9, "", [0])
        assert ev.tile_cache.total_bytes == 0
        assert ev.tile_cache.invalidations > inv0
        for k, data in payload.items():
            assert bytes(ev.read_needle(k).data) == data
        store.close()

    def test_bounded_memory_under_concurrent_readers(self, tmp_path):
        store, payload = _local_ec_store(tmp_path, n_needles=60)
        ev = store.find_ec_volume(9)
        ev.quarantine_shard(0, "test")
        # tiny tiles + a 3-tile budget: concurrent misses must never
        # blow past the cap even while every thread is inserting
        ev.tile_cache = TileCache(capacity_bytes=3 * 8192, tile_bytes=8192)
        errors: list = []
        peak = [0]

        def reader(seed):
            rng = random.Random(seed)
            try:
                for _ in range(30):
                    k = rng.choice(list(payload))
                    got = bytes(ev.read_needle(k).data)
                    if got != payload[k]:
                        raise AssertionError(f"needle {k} corrupt")
                    peak[0] = max(peak[0], ev.tile_cache.total_bytes)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=reader, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors[:2]
        assert peak[0] <= 3 * 8192
        store.close()

    def test_gather_uses_fetch_for_unmounted_survivors(self, tmp_path):
        store, payload = _local_ec_store(tmp_path)
        ev = store.find_ec_volume(9)
        ev.quarantine_shard(0, "test")
        # unmount four healthy shards: 9 locals remain, the gather must
        # race the "remote" candidates through the attempt pool
        paths = {sid: ev.shards[sid].path for sid in (1, 2, 3, 4)}
        for sid in paths:
            ev.unmount_shard(sid)
        fetched: list[int] = []

        def fetch(sid, offset, size):
            p = paths.get(sid)
            if p is None:
                return None
            fetched.append(sid)
            with open(p, "rb") as f:
                f.seek(offset)
                return f.read(size)

        for k, data in payload.items():
            assert bytes(ev.read_needle(k, fetch=fetch).data) == data
        assert fetched, "remote fetch never used despite missing locals"
        store.close()

    def test_singleflight_one_decode_per_hot_tile(self, tmp_path):
        """8 concurrent degraded GETs of one cold hot key must collapse
        to (about) one k-shard gather + decode, not fan out 8."""
        store, payload = _local_ec_store(tmp_path)
        ev = store.find_ec_volume(9)
        ev.quarantine_shard(0, "test")
        calls: list[int] = []
        orig = ev._reconstruct_range

        def counting(*a, **kw):
            calls.append(1)
            time.sleep(0.05)  # widen the would-be stampede window
            return orig(*a, **kw)

        ev._reconstruct_range = counting
        hot = next(iter(payload))
        errors: list = []

        def read():
            try:
                assert bytes(ev.read_needle(hot).data) == payload[hot]
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=read) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors, errors[:2]
        # one leader per tile the needle spans (2 allows a boundary
        # needle); without singleflight this is 8+
        assert len(calls) <= 2, f"{len(calls)} concurrent decodes"
        store.close()

    def test_not_enough_shards_raises(self, tmp_path):
        store, payload = _local_ec_store(tmp_path)
        ev = store.find_ec_volume(9)
        for sid in range(5):  # 9 survivors < k=10
            ev.quarantine_shard(sid, "test")
        with pytest.raises(NotEnoughShards):
            ev.read_needle(next(iter(payload)))
        store.close()

    def test_serial_fallback_gone_from_hot_path(self):
        # planted-regression guard: the old per-call
        # ThreadPoolExecutor gather must never come back
        import inspect

        from seaweedfs_tpu.ec import ec_volume

        src = inspect.getsource(ec_volume)
        assert "ThreadPoolExecutor" not in src
        assert "as_completed" not in src


# ---------------------------------------------------------------------------
class TestRepairSession:
    def test_consume_coverage_and_gaps(self):
        sess = repair_session.RebuildSession(7, (1,))
        assert sess.donate(1, 0, b"a" * 100)
        assert sess.donate(1, 300, b"b" * 100)
        covered, gaps = sess.consume(0, 500)
        assert [(off, per[1]) for off, per in covered] == [
            (0, b"a" * 100),
            (300, b"b" * 100),
        ]
        assert gaps == [(100, 200), (400, 100)]

    def test_donation_clipped_to_tile_keeps_remainder(self):
        sess = repair_session.RebuildSession(7, (1,))
        sess.donate(1, 50, b"x" * 100)  # spans [50, 150)
        covered, gaps = sess.consume(0, 100)
        assert [(off, len(per[1])) for off, per in covered] == [(50, 50)]
        assert gaps == [(0, 50)]
        # the out-of-window tail [100, 150) survives for the next tile —
        # a serve tile larger than the rebuild tile must not lose its
        # remainder to the first claim
        covered2, gaps2 = sess.consume(100, 100)
        assert [(off, per[1]) for off, per in covered2] == [(100, b"x" * 50)]
        assert gaps2 == [(150, 50)]

    def test_donation_overlapping_claim_is_trimmed_not_rejected(self):
        sess = repair_session.RebuildSession(7, (1,))
        sess.consume(0, 100)  # claim [0, 100)
        assert sess.donate(1, 50, b"y" * 100)  # [50,150): head claimed
        covered, gaps = sess.consume(100, 100)
        assert [(off, per[1]) for off, per in covered] == [(100, b"y" * 50)]
        assert gaps == [(150, 50)]

    def test_late_donations_for_claimed_ranges_rejected(self):
        sess = repair_session.RebuildSession(7, (1,))
        sess.consume(0, 1000)
        assert not sess.donate(1, 0, b"x" * 100)
        assert sess.donate(1, 1000, b"y" * 100)

    def test_multi_target_requires_all_targets(self):
        sess = repair_session.RebuildSession(7, (1, 2))
        sess.donate(1, 0, b"a" * 100)  # target 2 missing for [0,100)
        covered, gaps = sess.consume(0, 100)
        assert covered == []
        assert gaps == [(0, 100)]
        sess2 = repair_session.RebuildSession(7, (1, 2))
        sess2.donate(1, 0, b"a" * 100)
        sess2.donate(2, 0, b"b" * 100)
        covered, gaps = sess2.consume(0, 100)
        assert len(covered) == 1 and gaps == []

    def test_non_target_donation_rejected(self):
        sess = repair_session.RebuildSession(7, (1,))
        assert not sess.donate(5, 0, b"x" * 10)

    def test_yield_to_serving_waits_bounded(self):
        sess = repair_session.RebuildSession(7, (1,))
        sess.serving_enter()
        t0 = time.perf_counter()
        sess.yield_to_serving(max_wait_s=0.2)
        waited = time.perf_counter() - t0
        assert 0.15 <= waited < 2.0
        assert sess.yields > 0
        sess.serving_exit()
        t0 = time.perf_counter()
        sess.yield_to_serving(max_wait_s=0.2)
        assert time.perf_counter() - t0 < 0.1, "idle serving must not block"

    def test_registry_open_find_close(self):
        sess = repair_session.open_session(42, (3,))
        assert repair_session.find(42) is sess
        repair_session.close_session(sess)
        assert repair_session.find(42) is None

    def test_stream_rebuild_consumes_donations_byte_identical(self, tmp_path):
        from seaweedfs_tpu.ec import ec_stream

        d = str(tmp_path)
        base = os.path.join(d, "7")
        rng = random.Random(3)
        with open(base + ".dat", "wb") as f:
            f.write(bytes(rng.randbytes(3_000_000)))
        rs = new_encoder(backend="cpu")
        ec_files.write_ec_files(base, rs=rs)
        shard_bytes = {}
        for i in range(14):
            with open(base + ec_files.to_ext(i), "rb") as f:
                shard_bytes[i] = f.read()
        os.remove(base + ec_files.to_ext(1))
        remote = {}
        for i in (10, 11, 12, 13):
            os.remove(base + ec_files.to_ext(i))
            def read_into(off, dest, data=shard_bytes[i]):
                got = data[off : off + len(dest)]
                memoryview(dest)[: len(got)] = got
                return len(got)

            remote[i] = read_into
        rl0 = EC_REPAIR_BYTES_READ.value("local")
        rr0 = EC_REPAIR_BYTES_READ.value("remote")
        w0 = EC_REPAIR_BYTES_WRITTEN.value()
        sess = repair_session.open_session(7, (1,))
        for off in (0, 262144):  # 512 KiB of 1 MiB donated
            sess.donate(1, off, shard_bytes[1][off : off + 262144])
        rfn, ffn = ec_stream.local_rebuild_fns(rs)
        stats: dict = {}
        rebuilt = ec_stream.stream_rebuild_ec_files(
            base,
            rebuild_fn=rfn,
            fetch_fn=ffn,
            remote_readers=remote,
            session=sess,
            durable=True,
            stats=stats,
        )
        repair_session.close_session(sess)
        assert rebuilt == [1]
        with open(base + ec_files.to_ext(1), "rb") as f:
            assert f.read() == shard_bytes[1], "donated rebuild differs"
        shard_len = len(shard_bytes[1])
        read = (
            EC_REPAIR_BYTES_READ.value("local")
            - rl0
            + EC_REPAIR_BYTES_READ.value("remote")
            - rr0
        )
        written = EC_REPAIR_BYTES_WRITTEN.value() - w0
        assert written == shard_len
        # donations halve the gather: 10 survivors x the uncovered half
        assert read == 10 * (shard_len - 524288)
        assert stats["used_donated_bytes"] == 524288

    def test_donate_cached_tiles_seeds_session(self, tmp_path):
        store, payload = _local_ec_store(tmp_path)
        ev = store.find_ec_volume(9)
        ev.quarantine_shard(0, "test")
        for k in payload:
            ev.read_needle(k)  # warms the tile cache
        assert ev.tile_cache.total_bytes > 0
        sess = repair_session.RebuildSession(9, (0,))
        donated = ev.donate_cached_tiles(sess)
        assert donated > 0
        assert sess.donated_bytes == ev.tile_cache.total_bytes
        store.close()


# ---------------------------------------------------------------------------
class TestFastPathLoadSignal:
    def test_resolve_enters_complete_exits(self):
        from seaweedfs_tpu import qos
        from seaweedfs_tpu.util import native_serve

        class Srv:
            RequestHandlerClass = object
            trace_name = "volume"
            trace_node = "t:1"
            load_tracker = qos.LoadTracker()

            def fast_resolver(self, path, rng, head_only):
                if path == "/miss":
                    return None
                return (200, b"HTTP/1.1 200 OK\r\n\r\n", b"hi", -1, 0, 0)

        srv = Srv()
        srv.fast_resolver = srv.fast_resolver.__get__(srv)
        resolve, _handoff, complete = native_serve._callbacks(srv)
        assert srv.load_tracker.inflight() == 0
        plan = resolve("/1,abc", None, False, "", None)
        assert plan is not None
        assert srv.load_tracker.inflight() == 1, (
            "fast-path GET invisible to the heartbeat load signal"
        )
        ctx = plan[7]
        complete(ctx, 200, 2, 0.0, 0.0, 0.0, 1)
        assert srv.load_tracker.inflight() == 0
        # a declined resolve must not touch the counter
        assert resolve("/miss", None, False, "", None) is None
        assert srv.load_tracker.inflight() == 0
        # a legacy 6-tuple plan cannot validate If-None-Match: a
        # conditional GET must decline to the threaded arm
        assert resolve("/1,abc", None, False, "", '"x"') is None
        assert srv.load_tracker.inflight() == 0


# ---------------------------------------------------------------------------
# live mini-cluster: degraded serving + piggybacked rebuild end to end
@pytest.fixture(scope="module")
def degraded_cluster(tmp_path_factory):
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.util.availability import free_port

    master = MasterServer(
        port=free_port(),
        volume_size_limit_mb=64,
        vacuum_interval=0,
        repair_interval=0,
    )
    master.start()
    servers = []
    for i in range(3):
        vs = VolumeServer(
            [str(tmp_path_factory.mktemp(f"deg{i}"))],
            port=free_port(),
            master=f"127.0.0.1:{master.port}",
            rack=f"rack{i}",
            heartbeat_interval=0.2,
            max_volume_counts=[100],
            ec_codec="cpu",
            scrub_interval=0,
        )
        vs.start()
        servers.append(vs)
    deadline = time.time() + 45
    while time.time() < deadline:
        if len(master.topology.data_nodes()) == 3:
            break
        time.sleep(0.1)
    assert len(master.topology.data_nodes()) == 3
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


class TestDegradedServingEndToEnd:
    def _seed_and_encode(self, master, n=24):
        from seaweedfs_tpu.shell.command_env import CommandEnv
        from seaweedfs_tpu.shell.commands import do_ec_encode
        import io

        rng = random.Random(11)
        keys = {}
        vid = None
        for i in range(n):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign", timeout=10
            ) as r:
                a = json.loads(r.read())
            data = bytes(rng.randbytes(1800 + i))
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://{a['url']}/{a['fid']}",
                    data=data,
                    method="POST",
                    headers={"Content-Type": "application/octet-stream"},
                ),
                timeout=10,
            ).close()
            keys[a["fid"]] = data
            vid = int(a["fid"].partition(",")[0])
        env = CommandEnv([f"127.0.0.1:{master.port}"])
        do_ec_encode(env, vid, "", io.StringIO())
        return vid, keys

    def test_degraded_get_tile_cache_and_piggybacked_rebuild(
        self, degraded_cluster
    ):
        master, servers = degraded_cluster
        vid, keys = self._seed_and_encode(master)
        # all data lives in shard 0 (dat < 1MB => striping block 0);
        # kill it over the operator route on whichever node mounts it
        holder = next(
            vs
            for vs in servers
            if (ev := vs.store.find_ec_volume(vid)) is not None
            and 0 in ev.shards
        )
        fault = DeadShard(vid, sid=0, addr=f"127.0.0.1:{holder.port}")
        assert fault.kill() == 0
        # serve degraded GETs from a surviving holder: byte-identical,
        # second pass all tile-cache hits
        server = next(
            vs
            for vs in servers
            if vs.store.find_ec_volume(vid) is not None
            and vs.store.find_ec_volume(vid).shard_ids()
        )
        d0 = EC_DEGRADED_READS.value()

        def get_all():
            for fid, data in keys.items():
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/{fid}", timeout=30
                ) as r:
                    assert r.read() == data, f"degraded GET {fid} corrupt"

        get_all()
        assert EC_DEGRADED_READS.value() > d0
        h1, m1 = _tile_counts()
        get_all()
        h2, m2 = _tile_counts()
        assert m2 == m1 and h2 > h1, "warm pass must be all cache hits"
        # rebuild ON the warm node: its cached tiles seed the session,
        # so the gather skips the donated ranges entirely
        don0 = EC_REPAIR_DONATED_BYTES.value()
        from seaweedfs_tpu.pb import rpc, volume_pb2

        with rpc.dial(f"127.0.0.1:{server.port + 10000}") as ch:
            resp = rpc.volume_stub(ch).VolumeEcShardsRebuild(
                volume_pb2.VolumeEcShardsRebuildRequest(volume_id=vid),
                timeout=120,
            )
        assert list(resp.rebuilt_shard_ids) == [0]
        assert EC_REPAIR_DONATED_BYTES.value() > don0, (
            "piggyback: cached degraded tiles never reached the rebuild"
        )
        server.store.mount_ec_shards(vid, "", [0])
        # healthy again: reads still byte-identical
        for fid, data in list(keys.items())[:5]:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/{fid}", timeout=30
            ) as r:
                assert r.read() == data
