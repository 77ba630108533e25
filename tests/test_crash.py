"""Crash-consistency plane tests (docs/ANALYSIS.md v3).

Three layers, mirroring the plane itself:

  * crashlint — planted-bug positive controls for every durability-
    order rule plus negative controls proving the blessed idioms
    (durable.publish, fsync-then-rename-then-dirsync) pass;
  * the enumerator — model unit tests (fsync pins a prefix, renames
    can land before data, torn pwritev at iov cuts, budget truncation
    is flagged) and the planted dynamic bug that must be DETECTED;
  * recovery — Volume repair-mode heals (idx truncate, dat re-index,
    torn-tail truncate, vacuum marker roll-forward/back) and the
    acceptance crash matrices: vacuum crashed at every enumerated
    point and a group-commit torn-final-pwritev, both tier-1 (slow-
    exempt) via small bounded state budgets.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import textwrap

import pytest

from seaweedfs_tpu.analysis import crash, crashlint
from seaweedfs_tpu.storage import idx as idx_codec
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import CorruptNeedle, Needle
from seaweedfs_tpu.storage.volume import NeedleNotFound, Volume


def _mk(nid: int, data: bytes) -> Needle:
    return Needle(cookie=0x5EED, id=nid, data=data)


# ---------------------------------------------------------------------------
# static tier: planted-bug controls per rule


class TestCrashLint:
    def _check(self, tmp_path, source: str, subdir: str = ""):
        root = tmp_path / "fixturepkg" / subdir if subdir else tmp_path / "fixturepkg"
        root.mkdir(parents=True)
        (tmp_path / "fixturepkg" / "__init__.py").write_text("")
        if subdir:
            (root / "__init__.py").write_text("")
        (root / "mod.py").write_text(textwrap.dedent(source))
        findings, _idx = crashlint.check(root=str(tmp_path / "fixturepkg"))
        return findings

    def test_rename_unsynced_src_detected(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def publish(path):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write("x")
                os.replace(tmp, path)
        """)
        rules = {f.rule for f in findings}
        assert "crash-rename-unsynced-src" in rules
        assert "crash-rename-no-dirsync" in rules

    def test_fsync_then_rename_then_dirsync_clean(self, tmp_path):
        findings = self._check(tmp_path, """
            import os
            from seaweedfs_tpu.util.durable import fsync_dir

            def publish(path):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write("x")
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
                fsync_dir(os.path.dirname(path))
        """)
        assert [f.rule for f in findings] == []

    def test_durable_publish_helper_recognized(self, tmp_path):
        findings = self._check(tmp_path, """
            from seaweedfs_tpu.util import durable

            def save(path):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write("x")
                durable.publish(tmp, path)
        """)
        assert [f.rule for f in findings] == []

    def test_fsync_after_close_detected(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def flushed_too_late(path):
                f = open(path, "wb")
                f.write(b"x")
                f.close()
                os.fsync(f.fileno())
        """)
        assert any(f.rule == "crash-fsync-after-close" for f in findings)

    def test_reassigned_handle_not_flagged(self, tmp_path):
        # the FUSE RELEASE/FLUSH shape: close one handle, fetch a
        # DIFFERENT one into the same name, flush that
        findings = self._check(tmp_path, """
            def dispatch(table, fh):
                f = table.pop(fh)
                f.close()
                f = table.get(fh + 1)
                if f is not None:
                    f.flush()
        """)
        assert [f.rule for f in findings] == []

    def test_idx_before_dat_detected(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def backwards_write(self, blob, offset):
                self.nm.put(1, offset, len(blob))
                os.pwrite(self._fd, blob, offset)
        """, subdir="storage")
        assert any(f.rule == "crash-idx-before-dat" for f in findings)

    def test_dat_then_idx_clean(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def forwards_write(self, blob, offset):
                os.pwrite(self._fd, blob, offset)
                self.nm.put(1, offset, len(blob))
        """, subdir="storage")
        assert [f.rule for f in findings] == []

    def test_replace_unflushed_detected(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def leaky_publish(path):
                tmp = path + ".tmp"
                f = open(tmp, "w")
                f.write("x")
                os.replace(tmp, path)
        """)
        assert any(f.rule == "crash-replace-unflushed" for f in findings)

    def test_critical_write_detected(self, tmp_path):
        findings = self._check(tmp_path, """
            def clobber(state_dir):
                with open(state_dir + "/scrub_state.json", "w") as f:
                    f.write("{}")
        """)
        assert any(f.rule == "crash-critical-write" for f in findings)

    def test_critical_write_via_tmp_clean(self, tmp_path):
        findings = self._check(tmp_path, """
            from seaweedfs_tpu.util import durable

            def save(state_dir):
                final = state_dir + "/scrub_state.json"
                tmp = final + ".tmp"
                with open(tmp, "w") as f:
                    f.write("{}")
                durable.publish(tmp, final)
        """)
        assert [f.rule for f in findings] == []


# ---------------------------------------------------------------------------
# the enumerator model


class TestEnumerator:
    def test_fsync_pins_prefix(self):
        """Writes before an fsync survive EVERY legal state at a later
        crash point; writes after it may be lost in some state."""
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "f")
            with open(p, "wb") as f:
                f.write(b"")
            rec = crash.Recorder(d)
            with rec:
                fd = os.open(p, os.O_WRONLY)
                os.pwrite(fd, b"AAAA", 0)
                os.fsync(fd)
                os.pwrite(fd, b"BBBB", 4)
                os.close(fd)
            states, truncated, _n = crash.enumerate_states(
                rec.trace, budget=64
            )
            assert not truncated
            contents = {s.files["f"] for s in states}
            # after the fsync the first write is pinned: no state may
            # hold the second write without the first
            assert not any(
                c[4:8] == b"BBBB" and c[:4] != b"AAAA" for c in contents
            )
            assert b"AAAA" in contents, "no state lost the un-fsynced write"
            assert b"AAAABBBB" in contents
            # states crashing after the barrier never lose the fsynced
            # bytes
            assert all(
                s.files["f"][:4] == b"AAAA"
                for s in states if s.crash_index >= 2
            )

    def test_rename_can_land_before_data(self):
        """The rename-visible-before-data hazard must be in the model:
        some legal state has the destination name with EMPTY bytes."""
        with tempfile.TemporaryDirectory() as d:
            rec = crash.Recorder(d)
            with rec:
                tmp = os.path.join(d, "x.tmp")
                with open(tmp, "wb") as f:
                    f.write(b"NEWDATA")
                os.replace(tmp, os.path.join(d, "x"))
            states, _tr, _n = crash.enumerate_states(rec.trace, budget=64)
            published = [s for s in states if "x" in s.files]
            assert any(s.files["x"] == b"NEWDATA" for s in published)
            assert any(s.files["x"] == b"" for s in published), (
                "model must allow the rename to land without the data"
            )

    def test_torn_pwritev_cuts_at_iov_boundaries(self):
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "f")
            with open(p, "wb") as f:
                f.write(b"")
            rec = crash.Recorder(d)
            with rec:
                fd = os.open(p, os.O_WRONLY)
                os.pwritev(fd, [b"1111", b"2222", b"3333"], 0)
                os.close(fd)
            states, _tr, _n = crash.enumerate_states(rec.trace, budget=64)
            contents = {s.files["f"] for s in states if "f" in s.files}
            # iov-boundary tears of the final write
            assert b"1111" in contents
            assert b"11112222" in contents
            assert b"111122223333" in contents

    def test_budget_truncation_is_flagged(self):
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "f")
            with open(p, "wb") as f:
                f.write(b"")
            rec = crash.Recorder(d)
            with rec:
                fd = os.open(p, os.O_WRONLY)
                for i in range(40):
                    os.pwrite(fd, b"%04d" % i, i * 4)
                os.close(fd)
            states, truncated, candidates = crash.enumerate_states(
                rec.trace, budget=10
            )
            assert truncated and candidates > 10
            assert len(states) <= 10
            # the sampler must be able to reach the END of the
            # candidate space (review finding: a floor-stride spread
            # never picked the torn states of the trace's final writes
            # — generated last — so a recovery bug firing only there
            # would report 0 violations every run)
            full, _tr, _n = crash.enumerate_states(
                rec.trace, budget=10_000
            )
            assert states[-1].digest() == full[-1].digest()

    def test_planted_broken_publish_is_detected(self):
        """The dynamic positive control: an unsynced tmp+rename publish
        MUST yield at least one violating crash state."""
        rep = crash.run_broken_publish(budget=64)
        assert rep.violations, "enumerator went blind: planted bug missed"


# ---------------------------------------------------------------------------
# recovery: Volume repair mode


class TestVolumeRepair:
    def _volume_with(self, d, n=3):
        v = Volume(d, 1)
        data = {}
        for i in range(1, n + 1):
            data[i] = b"rec-%03d\xcd" % i * 30
            v.write_needle(_mk(i, data[i]))
        v.commit()
        return v, data

    def test_idx_entry_past_dat_healed(self, tmp_path):
        d = str(tmp_path)
        v, data = self._volume_with(d)
        v.close()
        # plant an entry referencing bytes the .dat does not have
        with open(v.base_name + ".idx", "ab") as f:
            f.write(idx_codec.pack_entry(99, t.offset_to_units(1 << 20), 640))
        with pytest.raises((CorruptNeedle, ValueError)):
            Volume(d, 1, create=False)  # non-repair open still refuses
        v2 = Volume(d, 1, create=False, repair=True)
        assert not v2.has_needle(99)
        for nid, payload in data.items():
            assert v2.read_needle(nid).data == payload
        v2.close()

    def test_lost_idx_tail_reindexed_from_dat(self, tmp_path):
        d = str(tmp_path)
        v, data = self._volume_with(d)
        v.close()
        idx = v.base_name + ".idx"
        os.truncate(idx, os.path.getsize(idx) - 16)  # lose the last entry
        v2 = Volume(d, 1, create=False, repair=True)
        for nid, payload in data.items():
            assert v2.read_needle(nid).data == payload, f"needle {nid} lost"
        v2.close()

    def test_torn_dat_tail_truncated(self, tmp_path):
        d = str(tmp_path)
        v, data = self._volume_with(d)
        v.close()
        idx = v.base_name + ".idx"
        os.truncate(idx, os.path.getsize(idx) - 16)
        # a torn record: half of a fresh append hit the disk, no idx
        torn = _mk(50, b"torn-needle" * 20).encode_record(3)
        with open(v.base_name + ".dat", "ab") as f:
            f.write(torn[: len(torn) // 2])
        v2 = Volume(d, 1, create=False, repair=True)
        for nid, payload in data.items():
            assert v2.read_needle(nid).data == payload
        assert not v2.has_needle(50)
        # the torn bytes are gone: appends land on a clean tail
        v2.write_needle(_mk(60, b"after-repair" * 10))
        assert v2.read_needle(60).data == b"after-repair" * 10
        v2.close()

    def test_commit_marker_rolls_forward(self, tmp_path):
        d = str(tmp_path)
        v, data = self._volume_with(d)
        v.delete_needle(_mk(2, b""))
        del data[2]
        old_rev = v.super_block.compaction_revision
        v.compact()
        # crash simulation: scratch written + marker durable, renames
        # never ran (commit_compact's window between commit point and
        # the swap)
        with open(v.base_name + ".cpm", "wb") as f:
            f.write(b"commit\n")
        v.close()
        v2 = Volume(d, 1, create=False, repair=True)
        assert v2.super_block.compaction_revision == old_rev + 1
        for nid, payload in data.items():
            assert v2.read_needle(nid).data == payload
        with pytest.raises(NeedleNotFound):
            v2.read_needle(2)
        assert not os.path.exists(v.base_name + ".cpm")
        assert not os.path.exists(v.base_name + ".cpd")
        assert not os.path.exists(v.base_name + ".cpx")
        v2.close()

    def test_db_map_sdb_removed_inside_marker_window(self, tmp_path):
        """Review finding: the db needle map's sqlite table is
        checkpointed CLEAN (old watermark) by nm.close() before the
        swap; if it survives a crash whose marker was already removed,
        a compacted idx of coincidentally equal size would skip the
        rebuild and serve pre-compaction offsets. The unlink order in
        commit_compact is the contract: .idx.sdb strictly before .cpm
        (every crash state then either keeps the marker — recovery
        drops the table — or already lost the table)."""
        d = str(tmp_path)
        v = Volume(d, 1, needle_map_kind="db")
        data = {}
        for i in range(1, 5):
            data[i] = b"db-%03d\xee" % i * 25
            v.write_needle(_mk(i, data[i]))
        v.delete_needle(_mk(3, b""))
        del data[3]
        v.commit()
        v.close()
        rec = crash.Recorder(d)
        with rec:
            v = Volume(d, 1, create=False, needle_map_kind="db")
            v.compact()
            v.commit_compact()
            v.close()
        unlinks = [
            e.path for e in rec.trace.events if e.kind == "unlink"
        ]
        assert "1.idx.sdb" in unlinks and "1.cpm" in unlinks
        assert unlinks.index("1.idx.sdb") < unlinks.index("1.cpm")
        # and marker-present recovery drops a stale table even when
        # the scratch files are already gone (renames done, crash
        # before the sdb/marker unlinks reached disk)
        v = Volume(d, 1, create=False, needle_map_kind="db")
        for nid, payload in data.items():
            v.write_needle(_mk(nid, payload))  # repopulate the sdb
        v.close()
        sdb = os.path.join(d, "1.idx.sdb")
        assert os.path.exists(sdb)
        # poison the checkpointed-clean table the way the crash would
        # leave it: offsets that no longer match the (swapped) .dat.
        # Without marker recovery dropping the table, load() trusts
        # the clean flag + watermark and serves these corrupt offsets.
        import sqlite3

        db = sqlite3.connect(sdb)
        db.execute("UPDATE needles SET offset = offset + 1")
        db.commit()
        db.close()
        with open(os.path.join(d, "1.cpm"), "wb") as f:
            f.write(b"commit\n")
        v = Volume(
            d, 1, create=False, needle_map_kind="db", repair=True
        )
        for nid, payload in data.items():
            assert v.read_needle(nid).data == payload, (
                "stale sqlite table survived marker recovery"
            )
        v.close()

    def test_no_marker_rolls_back(self, tmp_path):
        d = str(tmp_path)
        v, data = self._volume_with(d)
        old_rev = v.super_block.compaction_revision
        v.compact()  # scratch exists, commit point never reached
        v.close()
        v2 = Volume(d, 1, create=False, repair=True)
        assert v2.super_block.compaction_revision == old_rev
        for nid, payload in data.items():
            assert v2.read_needle(nid).data == payload
        assert not os.path.exists(v.base_name + ".cpd")
        assert not os.path.exists(v.base_name + ".cpx")
        v2.close()


# ---------------------------------------------------------------------------
# the acceptance crash matrices (tier-1: small bounded budgets)


class TestCrashMatrix:
    def test_vacuum_recovers_old_or_new_never_hybrid(self):
        """Crash at every enumerated point of compact→commit_compact:
        recovery reaches the old or the new generation, every durably
        acked needle survives, deletes stay deleted."""
        rep = crash.run_vacuum(budget=96)
        assert rep.states_tested >= 48
        assert rep.violations == []

    def test_group_commit_torn_final_pwritev(self):
        """The batch lands via ONE pwritev; tearing it at any iov
        boundary must never surface a torn record as valid or lose an
        acked needle."""
        rep = crash.run_group_commit(budget=96)
        assert rep.states_tested >= 32
        assert rep.violations == []

    def test_group_commit_trace_contains_multi_iov_tears(self):
        """Guard the guard: the sweep above is only meaningful if the
        trace really contains a multi-iov batch write and the
        enumerator really tears it."""
        from seaweedfs_tpu.storage.volume import Volume as V

        with tempfile.TemporaryDirectory() as d:
            v = V(d, 1)
            v.commit()
            v.close()
            rec = crash.Recorder(d)
            with rec:
                v = V(d, 1, create=False)
                outs = v.write_needles(
                    [(_mk(i, b"t%03d" % i * 40), None) for i in range(5)],
                    durable=True,
                )
                assert not any(isinstance(o, BaseException) for o in outs)
                v.close()
            batch_writes = [
                e for e in rec.trace.events
                if e.kind == "write" and len(e.chunks) >= 5
            ]
            assert batch_writes, "no multi-iov pwritev in the trace"
            states, _tr, _n = crash.enumerate_states(rec.trace, budget=256)
            assert any(s.label.startswith("torn@") for s in states)

    def test_quarantine_rename_and_state_publish(self):
        rep = crash.run_quarantine(budget=96)
        assert rep.states_tested >= 32
        assert rep.violations == []

    def test_ec_encode_durable_ordering_clean(self):
        """The EC shard writer-pool flush (ISSUE 12 / PR-11 follow-on):
        with durable ordering — shard fds fsynced, .ecx via
        durable.publish — no crash state shows a complete index over
        missing/torn shard bytes."""
        rep = crash.run_ec_encode(budget=96)
        assert rep.states_tested >= 24
        assert rep.violations == []

    def test_ec_encode_reserves_every_shard_before_the_first_write(
        self, monkeypatch
    ):
        """The writer pool reserves the shard files (ISSUE 29): in the
        recorded trace every shard inode's trunc events (create, then
        the posix_fallocate to its final size) precede the operation's
        first shard write, whichever pool thread made them."""
        seen = {}
        real_sweep = crash.sweep

        def keep_trace(trace, *args, **kwargs):
            seen["trace"] = trace
            return real_sweep(trace, *args, **kwargs)

        monkeypatch.setattr(crash, "sweep", keep_trace)
        crash.run_ec_encode(budget=8)
        events = seen["trace"].events
        shards = {
            ev.ino for ev in events
            if ev.kind == "link" and re.search(r"\.ec\d\d$", ev.path)
        }
        assert len(shards) == 14
        first_write = min(
            i for i, ev in enumerate(events)
            if ev.kind == "write" and ev.ino in shards
        )
        for ino in shards:
            truncs = [
                (i, ev.size) for i, ev in enumerate(events)
                if ev.kind == "trunc" and ev.ino == ino
            ]
            assert [size > 0 for _, size in truncs] == [False, True], truncs
            assert truncs[-1][0] < first_write, (ino, truncs, first_write)

    def test_ec_encode_pre_fix_ordering_detected(self):
        """Regression proof the durable flag is load-bearing: replaying
        the OLD ordering (no shard fsyncs, .ecx written in place) must
        yield complete-looking-index-over-page-cache-only-shards
        states — the exact finding the sweep fixed."""
        rep = crash.run_ec_encode(budget=96, durable=False)
        assert rep.violations, (
            "the unsynced encode should be catchable — either the "
            "enumerator went blind or posix_fallocate/pwritev streams "
            "stopped being recorded"
        )

    def test_ecc_publish_durable_ordering_clean(self):
        """The `.ecc` sidecar attests shard bytes, so it must never
        reach its final name before those bytes are durable: with the
        durable ordering (shard fsyncs, then durable.publish for the
        sidecar) no crash state shows a complete sidecar vouching for
        missing/torn shard tails."""
        rep = crash.run_ecc_publish(budget=1200)
        assert rep.states_tested >= 256
        assert rep.violations == []

    def test_ecc_publish_unsynced_ordering_detected(self):
        """Regression proof the ordering is load-bearing: skipping the
        shard fsyncs and publishing the sidecar with a bare rename must
        yield confident-sidecar-over-page-cache-only-shards states.
        budget=1200: the planted states live deep in the enumeration
        (durable-data frontier + all-namespace syncs)."""
        rep = crash.run_ecc_publish(budget=1200, durable=False)
        assert rep.violations, (
            "the unsynced sidecar publish should be catchable — either "
            "the enumerator went blind or the sidecar rename/fsync "
            "stream stopped being recorded"
        )

    def test_shard_handback_acked_writes_survive(self):
        """-shardWrites ownership handback: worker-owned appends,
        release, lead catch-up appends, commit — every needle acked at
        the commit survives recovery, idx never outruns the .dat."""
        rep = crash.run_shard_handback(budget=96)
        assert rep.states_tested >= 32
        assert rep.violations == []

    def test_legacy_unsynced_swap_is_caught(self):
        """Regression proof that the commit marker protocol is load-
        bearing: replaying the OLD commit_compact (bare double rename,
        no fsync, no marker) through the enumerator yields violations —
        the exact bug class ISSUE 11 named as the known suspect."""
        with tempfile.TemporaryDirectory() as d:
            v = Volume(d, 1)
            live = {i: b"legacy-%03d\xaa" % i * 50 for i in range(1, 7)}
            for nid, data in live.items():
                v.write_needle(_mk(nid, data))
            old_rev = v.super_block.compaction_revision
            v.commit()
            v.close()
            rec = crash.Recorder(d)
            rec.mark(dict(live))
            with rec:
                v = Volume(d, 1, create=False)
                v.compact()
                cpd, cpx = v.base_name + ".cpd", v.base_name + ".cpx"
                v._makeup_diff(cpd, cpx)
                v._dat.close()
                v.nm.close()
                os.replace(cpd, v.base_name + ".dat")
                os.replace(cpx, v.base_name + ".idx")
                v._dat = open(v.base_name + ".dat", "r+b")
                v._bind_fd()
                v.nm = v._load_needle_map()
                v.close()

            def recover(state_dir, _st, acked_payloads):
                acked: dict[int, bytes] = {}
                for p in acked_payloads:
                    acked.update(p)
                crash.verify_volume(
                    state_dir, 1, acked, revisions=(old_rev, old_rev + 1)
                )

            rep = crash.sweep(
                rec.trace, recover, workload="legacy-swap", budget=200
            )
            assert rep.violations, (
                "the unsynced two-rename swap should be catchable — "
                "either the enumerator went blind or the model lost "
                "rename-before-data states"
            )


# ---------------------------------------------------------------------------
# fixed-site regression: scrub state publish survives every crash state


class TestScrubStatePublish:
    def test_scrub_state_save_is_atomic_and_durable(self):
        from seaweedfs_tpu.scrub.state import ScrubState

        with tempfile.TemporaryDirectory() as d:
            sp = os.path.join(d, "scrub_state.json")
            st = ScrubState(path=sp)
            h = st.get(5, False)
            h.cursor = 100
            st.save()
            rec = crash.Recorder(d)
            with rec:
                h.cursor = 200
                h.sweeps += 1
                st.save()

            def recover(state_dir, _s, _a):
                with open(os.path.join(state_dir, "scrub_state.json")) as f:
                    doc = json.load(f)  # torn JSON = violation
                (row,) = doc["volumes"]
                assert row["cursor"] in (100, 200)

            rep = crash.sweep(
                rec.trace, recover, workload="scrub-state", budget=64
            )
            assert rep.violations == []
