"""CLI subcommand tests: offline tools against real volume files, and
the benchmark/upload/download tools against a live in-process cluster."""

import json
import os
import socket
import time

import pytest

from seaweedfs_tpu.command import main as cli_main
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume


from seaweedfs_tpu.util.availability import free_port  # noqa: E402 — collision-hardened allocator


def spawn_cli(*args):
    """A real `python -m seaweedfs_tpu ...` subprocess."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", WEED_EC_CODEC="cpu")
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "seaweedfs_tpu",
            *args,
        ],
        env=env,
        cwd="/root/repo",
        stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT,
    )


def wait_until(pred, what, deadline_s=40):
    """Poll pred() (exceptions count as not-ready) until truthy; returns
    the elapsed seconds. Raises RuntimeError on timeout."""
    t0 = time.time()
    while time.time() - t0 < deadline_s:
        try:
            if pred():
                return time.time() - t0
        except Exception:
            pass
        time.sleep(0.2)
    raise RuntimeError(f"timed out waiting for {what}")


def reap(procs):
    """SIGCONT (in case of SIGSTOP tests) then kill+wait each process."""
    import signal

    for p in procs:
        try:
            p.send_signal(signal.SIGCONT)
        except OSError:
            pass
        try:
            p.kill()
            p.wait(timeout=10)
        except OSError:
            pass


class TestOfflineTools:
    def _make_volume(self, tmp_path, vid=7):
        vol = Volume(str(tmp_path), vid)
        for i in range(1, 21):
            n = Needle(cookie=0x1234, id=i, data=f"needle-{i}".encode() * 10)
            n.name = f"file{i}.txt".encode()
            n.set_has_name()
            vol.write_needle(n)
        for i in (3, 7):
            vol.delete_needle(Needle(cookie=0x1234, id=i))
        vol.close()
        return vid

    def test_version(self, capsys):
        assert cli_main(["version"]) == 0
        assert "seaweedfs_tpu" in capsys.readouterr().out

    def test_scaffold(self, capsys):
        assert cli_main(["scaffold", "-config", "filer"]) == 0
        out = capsys.readouterr().out
        assert "[sqlite]" in out

    def test_scaffold_unknown(self, capsys):
        assert cli_main(["scaffold", "-config", "nope"]) == 1

    def test_fix_rebuilds_idx(self, tmp_path, capsys):
        vid = self._make_volume(tmp_path)
        idx = tmp_path / f"{vid}.idx"
        original = idx.read_bytes()
        idx.unlink()
        assert cli_main(["fix", "-dir", str(tmp_path), "-volumeId", str(vid)]) == 0
        rebuilt = idx.read_bytes()
        # 18 live entries (20 written, 2 deleted)
        assert len(rebuilt) == 18 * 16
        # reopening the volume with the rebuilt index serves the data
        vol = Volume(str(tmp_path), vid)
        n = vol.read_needle(5)
        assert n.data == b"needle-5" * 10
        assert not vol.has_needle(3)
        vol.close()

    def test_export_lists_live_needles(self, tmp_path, capsys):
        vid = self._make_volume(tmp_path)
        out_dir = tmp_path / "exported"
        out_dir.mkdir()
        assert (
            cli_main(
                [
                    "export",
                    "-dir",
                    str(tmp_path),
                    "-volumeId",
                    str(vid),
                    "-o",
                    str(out_dir),
                ]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert "file5.txt" in out
        assert (out_dir / "file5.txt").read_bytes() == b"needle-5" * 10
        # deleted needles are not exported
        assert not (out_dir / "file3.txt").exists()
        assert len(list(out_dir.iterdir())) == 18

    def test_export_to_tar_with_name_format(self, tmp_path, capsys):
        """-o name.tar produces a tar whose member names follow
        -fileNameFormat (command/export.go:44,57)."""
        import tarfile

        vid = self._make_volume(tmp_path)
        tar_path = tmp_path / "vol.tar"
        assert (
            cli_main(
                [
                    "export",
                    "-dir", str(tmp_path),
                    "-volumeId", str(vid),
                    "-o", str(tar_path),
                    "-fileNameFormat", "{{.Id}}-{{.Name}}",
                ]
            )
            == 0
        )
        with tarfile.open(tar_path) as t:
            names = t.getnames()
            assert len(names) == 18  # live needles only
            assert "5-file5.txt" in names
            assert not any("file3" in n for n in names)  # deleted
            data = t.extractfile("5-file5.txt").read()
            assert data == b"needle-5" * 10

    def test_export_newer_filter(self, tmp_path, capsys):
        """-newer excludes needles whose last_modified is older
        (command/export.go:59); needles without a timestamp (0) are
        excluded by any cutoff, like the reference's comparison."""
        import time as _time

        from seaweedfs_tpu.storage.needle import Needle

        vol = Volume(str(tmp_path), 42)
        now = int(_time.time())
        for i in range(4):
            n = Needle(cookie=1, id=i + 1, data=b"ts")
            n.last_modified = now if i < 3 else now - 10 * 24 * 3600
            n.set_has_last_modified_date()
            vol.write_needle(n)
        vol.close()

        assert (
            cli_main(
                [
                    "export",
                    "-dir", str(tmp_path),
                    "-volumeId", "42",
                    "-newer", "2099-01-01T00:00:00",
                ]
            )
            == 0
        )
        assert "0 needles" in capsys.readouterr().err
        # a cutoff between the old needle and the fresh ones keeps 3
        import datetime as _dt

        cutoff = _dt.datetime.fromtimestamp(
            now - 3600, _dt.timezone.utc
        ).strftime("%Y-%m-%dT%H:%M:%S")
        assert (
            cli_main(
                [
                    "export",
                    "-dir", str(tmp_path),
                    "-volumeId", "42",
                    "-newer", cutoff,
                ]
            )
            == 0
        )
        assert "3 needles" in capsys.readouterr().err

    def test_compact(self, tmp_path, capsys):
        vid = self._make_volume(tmp_path)
        before = (tmp_path / f"{vid}.dat").stat().st_size
        assert cli_main(["compact", "-dir", str(tmp_path), "-volumeId", str(vid)]) == 0
        after = (tmp_path / f"{vid}.dat").stat().st_size
        assert after < before
        vol = Volume(str(tmp_path), vid)
        assert vol.read_needle(5).data == b"needle-5" * 10
        assert not vol.has_needle(3)
        vol.close()

    def test_help_lists_commands(self, capsys):
        assert cli_main([]) == 2
        out = capsys.readouterr().out
        for cmd in ("master", "volume", "filer", "s3", "benchmark", "shell"):
            assert cmd in out


@pytest.fixture(scope="module")
def mini_cluster(tmp_path_factory):
    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer(
        [str(tmp_path_factory.mktemp("clivol"))],
        port=free_port(),
        master=f"127.0.0.1:{mport}",
        heartbeat_interval=0.2,
        max_volume_counts=[50],
    )
    vs.start()
    deadline = time.time() + 10
    while time.time() < deadline and not master.topology.data_nodes():
        time.sleep(0.05)
    yield f"127.0.0.1:{mport}"
    vs.stop()
    master.stop()


class TestClusterTools:
    def test_upload_download(self, mini_cluster, tmp_path, capsys):
        src = tmp_path / "hello.txt"
        src.write_bytes(b"cli upload payload")
        assert (
            cli_main(["upload", str(src), "-master", mini_cluster]) == 0
        )
        result = json.loads(capsys.readouterr().out)
        fid = result[0]["fid"]
        assert result[0]["error"] == ""
        out_dir = tmp_path / "dl"
        out_dir.mkdir()
        assert (
            cli_main(
                ["download", fid, "-server", mini_cluster, "-dir", str(out_dir)]
            )
            == 0
        )
        files = list(out_dir.iterdir())
        assert len(files) == 1
        assert files[0].read_bytes() == b"cli upload payload"

    def test_benchmark_small(self, mini_cluster, capsys):
        from seaweedfs_tpu.command.benchmark import run_benchmark

        results, fids = run_benchmark(
            mini_cluster, concurrency=4, num=40, size=512
        )
        assert len(fids) == 40
        titles = [t for t, _ in results]
        assert any("Writing" in t for t in titles)
        assert any("Read" in t for t in titles)
        for _, stats in results:
            assert stats.failed == 0
            assert stats.completed == 40
            report = stats.report("x", 4)
            assert "Requests per second" in report
            assert "99%" in report

    def test_shell_script(self, mini_cluster, capsys):
        assert (
            cli_main(["shell", "-master", mini_cluster, "-c", "volume.list"]) == 0
        )
        out = capsys.readouterr().out
        assert "DataCenter" in out or "volume" in out.lower()


class TestServerDaemon:
    """Boot the all-in-one `server` command as a real subprocess and
    drive it over HTTP — the README quickstart, verified."""

    def test_all_in_one_smoke(self, tmp_path):
        import json as _json
        import os
        import signal
        import socket
        import subprocess
        import sys
        import time
        import urllib.request

        from seaweedfs_tpu.util.availability import free_port

        mport, vport, fport = free_port(), free_port(), free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["WEED_EC_CODEC"] = "cpu"
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "seaweedfs_tpu",
                "server",
                "-dir",
                str(tmp_path),
                "-master.port",
                str(mport),
                "-volume.port",
                str(vport),
                "-filer",
                "-filer.port",
                str(fport),
            ],
            env=env,
            cwd="/root/repo",
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.time() + 30
            assign = None
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/dir/assign", timeout=2
                    ) as r:
                        assign = _json.loads(r.read())
                    if "fid" in assign:
                        break
                except OSError:
                    time.sleep(0.2)
            assert assign and "fid" in assign, f"daemon never served: {assign}"

            blob = b"all-in-one daemon smoke"
            req = urllib.request.Request(
                f"http://{assign['url']}/{assign['fid']}",
                data=blob,
                method="POST",
            )
            urllib.request.urlopen(req, timeout=10).close()
            with urllib.request.urlopen(
                f"http://{assign['url']}/{assign['fid']}", timeout=10
            ) as r:
                assert r.read() == blob

            # filer HTTP namespace up too
            req = urllib.request.Request(
                f"http://127.0.0.1:{fport}/smoke/hello.txt",
                data=b"via filer",
                method="POST",
            )
            urllib.request.urlopen(req, timeout=10).close()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{fport}/smoke/hello.txt", timeout=10
            ) as r:
                assert r.read() == b"via filer"
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def _needle_payload(n) -> bytes:
    """A needle's logical payload: the volume auto-gzips compressible
    uploads (util/compression.py, the reference's IsGzippable), so raw
    record comparisons decode the flag first."""
    import gzip

    data = bytes(n.data)
    return gzip.decompress(data) if n.is_gzipped() else data


class TestBackupCommand:
    def test_incremental_backup_roundtrip(self, mini_cluster, tmp_path, capsys):
        """backup pulls a volume's records locally and resumes
        incrementally (command/backup.go runBackup role)."""
        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.storage.file_id import FileId
        from seaweedfs_tpu.storage.volume import Volume

        main = cli_main

        master_addr = mini_cluster
        ar = op.assign(master_addr, collection="bak")
        payload1 = b"first backup payload " * 40
        assert not op.upload(f"{ar.url}/{ar.fid}", payload1, jwt=ar.auth).error
        vid = int(ar.fid.split(",")[0])

        rc = main(
            [
                "backup",
                "-master",
                master_addr,
                "-volumeId",
                str(vid),
                "-collection",
                "bak",
                "-dir",
                str(tmp_path),
            ]
        )
        assert rc == 0

        fid1 = FileId.parse(ar.fid)
        v = Volume(str(tmp_path), vid, "bak", create=False)
        assert _needle_payload(v.read_needle(fid1.key, cookie=fid1.cookie)) == payload1
        first_size = v.data_file_size()
        v.close()

        # write more into the SAME volume, then an incremental run
        # appends only the tail
        payload2 = b"second incremental blob"
        ar2 = op.assign(master_addr, collection="bak")
        for _ in range(300):  # bounded: a hang here must fail, not stall CI
            if int(ar2.fid.split(",")[0]) == vid:
                break
            ar2 = op.assign(master_addr, collection="bak")
        else:
            pytest.skip("assign never landed on the backed-up volume")
        assert not op.upload(f"{ar2.url}/{ar2.fid}", payload2, jwt=ar2.auth).error

        rc = main(
            [
                "backup",
                "-master",
                master_addr,
                "-volumeId",
                str(vid),
                "-collection",
                "bak",
                "-dir",
                str(tmp_path),
            ]
        )
        assert rc == 0
        fid2 = FileId.parse(ar2.fid)
        v = Volume(str(tmp_path), vid, "bak", create=False)
        assert _needle_payload(v.read_needle(fid1.key, cookie=fid1.cookie)) == payload1
        assert _needle_payload(v.read_needle(fid2.key, cookie=fid2.cookie)) == payload2
        assert v.data_file_size() > first_size
        v.close()


class TestFilerCopyCommand:
    def test_copy_tree_into_filer(self, mini_cluster, tmp_path, capsys):
        """filer.copy walks a local tree into the filer namespace
        (command/filer_copy.go role)."""
        import urllib.request

        from seaweedfs_tpu.server.filer_server import FilerServer

        master_addr = mini_cluster
        filer = FilerServer([master_addr], port=free_port(), store="memory")
        filer.start()
        try:
            src = tmp_path / "proj"
            (src / "sub").mkdir(parents=True)
            (src / "a.txt").write_bytes(b"alpha file")
            (src / "sub" / "b.bin").write_bytes(bytes(range(100)))

            rc = cli_main(
                [
                    "filer.copy",
                    str(src),
                    f"http://127.0.0.1:{filer.port}/imported/",
                ]
            )
            assert rc == 0
            assert "copied 2 files" in capsys.readouterr().out

            with urllib.request.urlopen(
                f"http://127.0.0.1:{filer.port}/imported/proj/a.txt", timeout=10
            ) as r:
                assert r.read() == b"alpha file"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{filer.port}/imported/proj/sub/b.bin", timeout=10
            ) as r:
                assert r.read() == bytes(range(100))
        finally:
            filer.stop()


class TestCrashRecovery:
    """Hard-kill (SIGKILL) a volume-server subprocess mid-life and
    restart it on the same directory: every acknowledged write must
    survive (appends flush to the OS per write; .idx tail is validated
    against .dat on load) and the node must rejoin the master."""

    def test_sigkill_volume_server_and_restart(self, tmp_path):
        import signal
        import urllib.request

        def http(url, data=None, method="GET", timeout=5):
            req = urllib.request.Request(url, data=data, method=method)
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.read()

        def assign():
            a = json.loads(http(f"http://127.0.0.1:{mport}/dir/assign"))
            return None if a.get("error") else a

        mport, vport = free_port(), free_port()
        vol_dir = tmp_path / "vol"
        vol_dir.mkdir()
        procs = [spawn_cli("master", "-port", str(mport))]
        try:
            wait_until(
                lambda: http(f"http://127.0.0.1:{mport}/cluster/status"), "master"
            )
            volume = spawn_cli(
                "volume", "-port", str(vport), "-dir", str(vol_dir),
                "-mserver", f"127.0.0.1:{mport}",
            )
            procs.append(volume)
            wait_until(assign, "cluster writable")

            blobs = {}
            for i in range(20):
                wait_until(assign, "assign")
                a = assign()
                payload = f"crash-survivor-{i:03d}".encode() * 10
                http(f"http://{a['url']}/{a['fid']}", data=payload, method="POST")
                blobs[a["fid"]] = payload
            known_fid = next(iter(blobs))

            volume.send_signal(signal.SIGKILL)  # hard crash, no cleanup
            volume.wait(timeout=10)

            procs.append(
                spawn_cli(
                    "volume", "-port", str(vport), "-dir", str(vol_dir),
                    "-mserver", f"127.0.0.1:{mport}",
                )
            )
            # readiness = an actual read succeeds against the restarted
            # server (an assign alone can race the master's stale
            # registration of the killed process)
            wait_until(
                lambda: http(f"http://127.0.0.1:{vport}/{known_fid}"),
                "restarted volume serving reads",
            )

            for fid, payload in blobs.items():
                assert http(f"http://127.0.0.1:{vport}/{fid}") == payload, fid
            # and it still accepts writes
            wait_until(assign, "post-restart assign")
            a = assign()
            http(f"http://{a['url']}/{a['fid']}", data=b"post-crash", method="POST")
            assert http(f"http://127.0.0.1:{vport}/{a['fid']}") == b"post-crash"
        finally:
            reap(procs)


class TestLivenessSweep:
    """End-to-end master liveness: SIGSTOP a volume-server subprocess
    (stream stays open, beats stop) → master sweeps it and drops its
    volume locations; SIGCONT → the woken node re-registers AND its
    volumes reappear promptly (the master requests a full heartbeat
    instead of waiting ~10 delta cycles)."""

    def test_sigstop_sweep_sigcont_recover(self, tmp_path):
        import signal
        import urllib.error
        import urllib.request

        def http_json(url, timeout=2):
            with urllib.request.urlopen(url, timeout=timeout) as r:
                return json.loads(r.read())

        mport, vport = free_port(), free_port()
        vol_dir = tmp_path / "vol"
        vol_dir.mkdir()
        procs = [spawn_cli("master", "-port", str(mport), "-nodeTimeout", "3")]
        try:
            wait_until(
                lambda: http_json(f"http://127.0.0.1:{mport}/cluster/status"),
                "master",
            )
            volume = spawn_cli(
                "volume", "-port", str(vport), "-dir", str(vol_dir),
                "-mserver", f"127.0.0.1:{mport}",
            )
            procs.append(volume)

            def assign():
                a = http_json(f"http://127.0.0.1:{mport}/dir/assign")
                return None if a.get("error") else a

            wait_until(assign, "writable")
            a = assign()
            vid = a["fid"].split(",")[0]
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://{a['url']}/{a['fid']}", data=b"sweep-me", method="POST"
                ),
                timeout=5,
            ).close()

            def located():
                try:
                    out = http_json(
                        f"http://127.0.0.1:{mport}/dir/lookup?volumeId={vid}"
                    )
                except urllib.error.HTTPError:
                    return False  # 404: not located (the swept state)
                return bool(out.get("locations"))

            assert located()
            volume.send_signal(signal.SIGSTOP)  # freeze: stream survives
            wait_until(lambda: not located(), "volume swept", 30)

            volume.send_signal(signal.SIGCONT)
            dt = wait_until(located, "volume re-announced", 30)
            # the requested full beat re-announces within ~2 beat
            # intervals (2s each); without it the delta protocol would
            # wait for the 10-cycle full beat (~20s)
            assert dt < 15, "re-announcement took a full-cycle wait"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{vport}/{a['fid']}", timeout=5
            ) as r:
                assert r.read() == b"sweep-me"
        finally:
            reap(procs)
