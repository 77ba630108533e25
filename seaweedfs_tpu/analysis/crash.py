"""Dynamic crash-state enumerator: record a live workload's effect
trace, enumerate every disk state a crash could legally leave behind,
and re-run real recovery against each one (docs/ANALYSIS.md v3).

The ALICE idea (the crash-state exploration literature the ISSUE/
PAPERS.md cite): the kernel only promises what fsync promises. Between
barriers, data writes may be lost or torn and directory operations may
land without the data they publish. So instead of arguing "the .idx
entry is appended after the pwritev" in a comment, we

  1. RECORD the ordered effect trace of a real workload — a shim over
     os.pwrite/pwritev/write/fsync/replace/rename/ftruncate/unlink
     plus a buffered-`open` proxy, installed/uninstalled the way
     tests/faults.py and the lock witness install themselves;
  2. ENUMERATE legal post-crash states under this model:
       * per-file data writes persist as a PREFIX of their issue
         order, with the final applied write optionally TORN at any
         iov boundary or byte cut (the ordered-writeback model of an
         append-only file; see non-goals below);
       * directory operations (create/rename/unlink) are totally
         ordered among themselves; a crash keeps a prefix of them —
         independently of data durability, which is exactly the
         rename-visible-before-data hazard;
       * an fsync of a file pins every earlier write to that file;
         an fsync of a directory pins every earlier namespace op;
     bounded by WEED_CRASH_BUDGET with deterministic seeded sampling
     (WEED_CRASH_SEED) and an explicit `truncated` flag — never a
     silent cap;
  3. MATERIALIZE each candidate into a scratch dir (WEED_CRASH_SCRATCH
     or a tempdir) and run REAL recovery — `Volume(create=False,
     repair=True)` + idx replay, scrub-state load — asserting the
     workload's invariants: no acked needle lost, no torn record
     surfaced as valid (CRC gate), .idx never references bytes past
     the .dat, vacuum recovers to wholly-old or wholly-new.

Non-goals (stated, per the no-silent-caps rule): no sector-granularity
tearing (tears are byte cuts of one logical write, plus iov
boundaries); within ONE file writes persist in issue order (cross-file
and data-vs-namespace reordering is fully modeled — that is where
every bug this plane has caught lives); no modeling of filesystem
metadata corruption beyond lost/landed namespace ops.
"""

from __future__ import annotations

import builtins
import hashlib
import os
import random
import shutil
import tempfile
from dataclasses import dataclass, field

from seaweedfs_tpu.util import wlog

# ---------------------------------------------------------------------------
# knobs (documented in OPERATIONS.md "Environment knobs")


def budget_default() -> int:
    try:
        return int(os.environ.get("WEED_CRASH_BUDGET", "256"))
    except ValueError:
        return 256


def seed_default() -> int:
    try:
        return int(os.environ.get("WEED_CRASH_SEED", "0"))
    except ValueError:
        return 0


def scratch_base() -> str | None:
    return os.environ.get("WEED_CRASH_SCRATCH") or None


# ---------------------------------------------------------------------------
# the recorded effect trace


@dataclass
class Event:
    kind: str  # write | trunc | fsync | link | rename | unlink | dirsync | ack
    ino: int = -1  # write/trunc/fsync target
    offset: int = 0
    chunks: tuple = ()  # write payload, one entry per iov
    size: int = 0  # trunc
    path: str = ""  # link/unlink target, rename SRC
    dst: str = ""  # rename destination
    payload: object = None  # ack marker

    def nbytes(self) -> int:
        return sum(len(c) for c in self.chunks)


@dataclass
class Trace:
    root: str
    initial: dict[int, bytes] = field(default_factory=dict)  # ino -> bytes
    ns0: dict[str, int] = field(default_factory=dict)  # rel path -> ino
    events: list[Event] = field(default_factory=list)


class Recorder:
    """Installable effect-trace shim. Paths outside `root` pass through
    unrecorded; everything under it lands in the trace with inode
    identity preserved across renames (the two-generation vacuum swap
    depends on it)."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.trace = Trace(root=self.root)
        self._ns: dict[str, int] = {}  # live rel-path -> ino mirror
        self._next_ino = 0
        self._fd: dict[int, int] = {}  # os-level fd -> ino
        self._dirfd: set[int] = set()  # fds opened on directories
        self._installed = False
        self._orig: dict[str, object] = {}
        self._snapshot()

    # -- helpers ---------------------------------------------------------
    def _rel(self, path) -> str | None:
        try:
            p = os.path.abspath(os.fspath(path))
        except TypeError:
            return None
        if p == self.root or p.startswith(self.root + os.sep):
            return os.path.relpath(p, self.root)
        return None

    def _snapshot(self) -> None:
        for dirpath, _dirs, files in os.walk(self.root):
            for name in files:
                p = os.path.join(dirpath, name)
                rel = os.path.relpath(p, self.root)
                with open(p, "rb") as f:
                    data = f.read()
                ino = self._next_ino
                self._next_ino += 1
                self.trace.initial[ino] = data
                self.trace.ns0[rel] = ino
                self._ns[rel] = ino

    def _emit(self, **kw) -> None:
        self.trace.events.append(Event(**kw))

    def mark(self, payload) -> None:
        """Workload marker (e.g. 'these needle ids are now acked'):
        rides the trace so invariants can be crash-point-relative."""
        self._emit(kind="ack", payload=payload)

    def _creat(self, rel: str, truncate: bool) -> int:
        ino = self._ns.get(rel)
        if ino is None:
            ino = self._next_ino
            self._next_ino += 1
            self._ns[rel] = ino
            self._emit(kind="link", path=rel, ino=ino)
            self._emit(kind="trunc", ino=ino, size=0)
        elif truncate:
            self._emit(kind="trunc", ino=ino, size=0)
        return ino

    # -- install/uninstall ----------------------------------------------
    def install(self) -> None:
        assert not self._installed
        self._installed = True
        rec = self
        self._orig = {
            "open": builtins.open,
            "os_open": os.open,
            "os_close": os.close,
            "pwrite": os.pwrite,
            "pwritev": os.pwritev,
            "write": os.write,
            "fsync": os.fsync,
            "fdatasync": os.fdatasync,
            "replace": os.replace,
            "rename": os.rename,
            "truncate": os.truncate,
            "ftruncate": os.ftruncate,
            "remove": os.remove,
            "unlink": os.unlink,
            "posix_fallocate": os.posix_fallocate,
        }
        o = self._orig

        def _open(path, mode="r", *a, **kw):
            f = o["open"](path, mode, *a, **kw)
            rel = rec._rel(path) if isinstance(path, (str, bytes, os.PathLike)) else None
            if rel is None or getattr(f, "readable", None) is None:
                return f
            writable = any(m in mode for m in ("w", "a", "+", "x"))
            if not writable:
                # read opens are invisible to the crash model; fd-based
                # fsyncs arrive via os.open (durable.fsync_path), which
                # registers its own mapping
                return f
            ino = rec._creat(rel, truncate="w" in mode)
            rec._fd[f.fileno()] = ino
            return _RecordingFile(f, rec, ino)

        def _os_open(path, flags, *a, **kw):
            fd = o["os_open"](path, flags, *a, **kw)
            rel = rec._rel(path)
            if rel is not None:
                try:
                    is_dir = os.path.isdir(path)
                except OSError:
                    is_dir = False
                if is_dir:
                    rec._dirfd.add(fd)
                else:
                    if flags & (os.O_WRONLY | os.O_RDWR | os.O_CREAT):
                        rec._creat(rel, truncate=bool(flags & os.O_TRUNC))
                    ino = rec._ns.get(rel)
                    if ino is not None:
                        rec._fd[fd] = ino
            return fd

        def _os_close(fd):
            rec._fd.pop(fd, None)
            rec._dirfd.discard(fd)
            return o["os_close"](fd)

        def _pwrite(fd, data, offset):
            n = o["pwrite"](fd, data, offset)
            ino = rec._fd.get(fd)
            if ino is not None:
                rec._emit(kind="write", ino=ino, offset=offset,
                          chunks=(bytes(data[:n]),))
            return n

        def _pwritev(fd, buffers, offset, *a):
            bufs = [bytes(b) for b in buffers]
            n = o["pwritev"](fd, bufs, offset, *a)
            ino = rec._fd.get(fd)
            if ino is not None:
                rec._emit(kind="write", ino=ino, offset=offset,
                          chunks=tuple(bufs))
            return n

        def _write(fd, data):
            ino = rec._fd.get(fd)
            pos = os.lseek(fd, 0, os.SEEK_CUR) if ino is not None else 0
            n = o["write"](fd, data)
            if ino is not None:
                rec._emit(kind="write", ino=ino, offset=pos,
                          chunks=(bytes(data[:n]),))
            return n

        def _fsync(fd):
            r = o["fsync"](fd)
            if fd in rec._dirfd:
                rec._emit(kind="dirsync")
            else:
                ino = rec._fd.get(fd)
                if ino is not None:
                    rec._emit(kind="fsync", ino=ino)
            return r

        def _replace(src, dst, **kw):
            r = o["replace"](src, dst, **kw)
            rs, rd = rec._rel(src), rec._rel(dst)
            if rs is not None and rd is not None and rs in rec._ns:
                rec._ns[rd] = rec._ns.pop(rs)
                rec._emit(kind="rename", path=rs, dst=rd)
            return r

        def _truncate(path, length):
            r = o["truncate"](path, length)
            if isinstance(path, int):
                ino = rec._fd.get(path)
            else:
                rel = rec._rel(path)
                ino = rec._ns.get(rel) if rel is not None else None
            if ino is not None:
                rec._emit(kind="trunc", ino=ino, size=length)
            return r

        def _ftruncate(fd, length):
            r = o["ftruncate"](fd, length)
            ino = rec._fd.get(fd)
            if ino is not None:
                rec._emit(kind="trunc", ino=ino, size=length)
            return r

        def _posix_fallocate(fd, offset, length):
            r = o["posix_fallocate"](fd, offset, length)
            ino = rec._fd.get(fd)
            if ino is not None:
                # modeled as a size-extension whose durability is
                # independent (a trunc event the enumerator may apply
                # or drop) — the EC stream drivers preallocate with
                # exactly this call before their pwritev streams. The
                # recorded size is the REAL post-call st_size, not
                # offset+length: fallocate never shrinks, so emitting
                # the smaller value for an already-larger file would
                # let the sweep materialize shrunken states no
                # hardware can produce
                rec._emit(kind="trunc", ino=ino,
                          size=os.fstat(fd).st_size)
            return r

        def _remove(path, **kw):
            r = o["remove"](path, **kw)
            rel = rec._rel(path)
            if rel is not None and rel in rec._ns:
                rec._ns.pop(rel)
                rec._emit(kind="unlink", path=rel)
            return r

        builtins.open = _open
        os.open = _os_open
        os.close = _os_close
        os.pwrite = _pwrite
        os.pwritev = _pwritev
        os.write = _write
        os.fsync = _fsync
        os.fdatasync = _fsync
        os.replace = _replace
        os.rename = _replace
        os.truncate = _truncate
        os.ftruncate = _ftruncate
        os.remove = _remove
        os.unlink = _remove
        os.posix_fallocate = _posix_fallocate

    def uninstall(self) -> None:
        if not self._installed:
            return
        o = self._orig
        builtins.open = o["open"]
        os.open = o["os_open"]
        os.close = o["os_close"]
        os.pwrite = o["pwrite"]
        os.pwritev = o["pwritev"]
        os.write = o["write"]
        os.fsync = o["fsync"]
        os.fdatasync = o["fdatasync"]
        os.replace = o["replace"]
        os.rename = o["rename"]
        os.truncate = o["truncate"]
        os.ftruncate = o["ftruncate"]
        os.remove = o["remove"]
        os.unlink = o["unlink"]
        os.posix_fallocate = o["posix_fallocate"]
        self._installed = False

    def __enter__(self) -> "Recorder":
        self.install()
        return self

    def __exit__(self, *exc) -> None:
        self.uninstall()


class _RecordingFile:
    """Buffered-file proxy: records write/truncate effects at the
    OS-visible layer (offset = position at write time) and delegates
    everything else. App-buffer vs page-cache is deliberately NOT
    modeled separately: both are lost without fsync, which is the only
    distinction the crash model needs."""

    def __init__(self, f, rec: Recorder, ino: int):
        self._f = f
        self._rec = rec
        self._ino = ino
        # text handles: tell() returns an opaque cookie, so byte
        # positions are tracked here (text writes in this tree are
        # sequential json/str dumps into fresh tmp files)
        self._text = "b" not in getattr(f, "mode", "b")
        self._pos = os.fstat(f.fileno()).st_size if self._text else 0

    def write(self, data):
        if self._text:
            n = self._f.write(data)
            payload = data[:n].encode(
                getattr(self._f, "encoding", None) or "utf-8"
            )
            self._rec._emit(kind="write", ino=self._ino, offset=self._pos,
                            chunks=(payload,))
            self._pos += len(payload)
            return n
        pos = self._f.tell()
        n = self._f.write(data)
        self._rec._emit(kind="write", ino=self._ino, offset=pos,
                        chunks=(bytes(data[:n]),))
        return n

    def writelines(self, lines):
        for line in lines:
            self.write(line)

    def truncate(self, size=None):
        size = self._f.tell() if size is None else size
        r = self._f.truncate(size)
        self._rec._emit(kind="trunc", ino=self._ino, size=size)
        return r

    def close(self):
        try:
            fd = self._f.fileno()
        except ValueError:
            fd = -1  # already closed
        self._rec._fd.pop(fd, None)
        return self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __getattr__(self, name):
        return getattr(self._f, name)

    def __iter__(self):
        return iter(self._f)


# ---------------------------------------------------------------------------
# legal-crash-state enumeration


@dataclass
class CrashState:
    label: str
    crash_index: int
    files: dict[str, bytes]  # rel path -> content

    def digest(self) -> str:
        h = hashlib.sha1()
        for path in sorted(self.files):
            h.update(path.encode())
            h.update(b"\0")
            h.update(hashlib.sha1(self.files[path]).digest())
        return h.hexdigest()


def _apply_write(buf: bytearray, ev: Event, upto: int | None = None) -> None:
    data = b"".join(ev.chunks)
    if upto is not None:
        data = data[:upto]
    end = ev.offset + len(data)
    if len(buf) < end:
        buf.extend(bytes(end - len(buf)))
    buf[ev.offset:end] = data


def _materialize(trace: Trace, crash_index: int, cuts: dict[int, int],
                 ns_cut: int, torn: tuple[int, int] | None,
                 label: str) -> CrashState:
    """Build the on-disk state: per-ino apply the first cuts[ino] of
    its data ops (writes + truncs, in issue order), `torn` = (event
    index, byte prefix) partially applies one more write; namespace =
    ns0 + the first ns_cut namespace ops."""
    per_ino: dict[int, list[tuple[int, Event]]] = {}
    ns_ops: list[Event] = []
    for idx, ev in enumerate(trace.events[:crash_index]):
        if ev.kind in ("write", "trunc"):
            per_ino.setdefault(ev.ino, []).append((idx, ev))
        elif ev.kind in ("link", "rename", "unlink"):
            ns_ops.append(ev)
    content: dict[int, bytearray] = {
        ino: bytearray(data) for ino, data in trace.initial.items()
    }
    for ino, ops in per_ino.items():
        buf = content.setdefault(ino, bytearray())
        n = cuts.get(ino, len(ops))
        for _idx, ev in ops[:n]:
            if ev.kind == "write":
                _apply_write(buf, ev)
            else:
                if ev.size < len(buf):
                    del buf[ev.size:]
                else:
                    buf.extend(bytes(ev.size - len(buf)))
        if torn is not None and n < len(ops):
            t_idx, t_bytes = torn
            if ops[n][0] == t_idx and ops[n][1].kind == "write":
                _apply_write(buf, ops[n][1], upto=t_bytes)
    ns: dict[str, int] = dict(trace.ns0)
    for ev in ns_ops[:ns_cut]:
        if ev.kind == "link":
            ns[ev.path] = ev.ino
        elif ev.kind == "rename":
            if ev.path in ns:
                ns[ev.dst] = ns.pop(ev.path)
        elif ev.kind == "unlink":
            ns.pop(ev.path, None)
    files = {
        path: bytes(content.get(ino, bytearray())) for path, ino in ns.items()
    }
    return CrashState(label=label, crash_index=crash_index, files=files)


def _mandatory(trace: Trace, crash_index: int
               ) -> tuple[dict[int, int], int, dict[int, int], int]:
    """(per-ino mandatory cut, mandatory ns cut, per-ino total ops,
    total ns ops) at a crash index: fsync pins all earlier writes to
    that file; dirsync pins all earlier namespace ops."""
    counts: dict[int, int] = {}
    mand: dict[int, int] = {}
    ns_total = 0
    ns_mand = 0
    for ev in trace.events[:crash_index]:
        if ev.kind in ("write", "trunc"):
            counts[ev.ino] = counts.get(ev.ino, 0) + 1
        elif ev.kind in ("link", "rename", "unlink"):
            ns_total += 1
        elif ev.kind == "fsync":
            mand[ev.ino] = counts.get(ev.ino, 0)
        elif ev.kind == "dirsync":
            ns_mand = ns_total
    return mand, ns_mand, counts, ns_total


def enumerate_states(trace: Trace, budget: int | None = None,
                     seed: int | None = None
                     ) -> tuple[list[CrashState], bool, int]:
    """(deduped states, truncated?, candidate count before budget)."""
    budget = budget_default() if budget is None else budget
    seed = seed_default() if seed is None else seed
    events = trace.events
    # candidates are cheap PARAMETER tuples (crash_index, cuts, ns_cut,
    # torn, label); _materialize — which replays the trace and copies
    # every file's bytes — runs only on the states the budget keeps
    specs: list[tuple] = []

    # 1. in-order prefixes: crash after event i with everything issued
    #    so far on disk (writeback caught up, then power cut)
    for i in range(len(events) + 1):
        specs.append((i, {}, 1 << 30, None, f"prefix@{i}"))

    # 2. reorder states at each barrier-relevant point: only durable
    #    data survived, with (a) all namespace ops landed — the
    #    rename-visible-before-data shape — and (b) only durable
    #    namespace ops landed
    for i in range(1, len(events) + 1):
        mand, ns_mand, counts, ns_total = _mandatory(trace, i)
        if all(mand.get(k, 0) == v for k, v in counts.items()) and \
                ns_mand == ns_total:
            continue  # nothing pending: identical to the prefix state
        cuts = {ino: mand.get(ino, 0) for ino in counts}
        specs.append((i, cuts, ns_total, None, f"durable-data+all-ns@{i}"))
        specs.append((i, cuts, ns_mand, None, f"durable-only@{i}"))

    # 3. torn final write: iov boundaries + byte cuts of each write
    for i, ev in enumerate(events):
        if ev.kind != "write":
            continue
        total = ev.nbytes()
        if total <= 1:
            continue
        cutpoints: list[int] = []
        acc = 0
        for c in ev.chunks[:-1]:
            acc += len(c)
            cutpoints.append(acc)  # every iov boundary
        cutpoints += [1, total // 2, total - 1]
        seen_cut: set[int] = set()
        per_ino_ops = sum(
            1 for e in events[:i]
            if e.kind in ("write", "trunc") and e.ino == ev.ino
        )
        for cut in cutpoints:
            if not 0 < cut < total or cut in seen_cut:
                continue
            seen_cut.add(cut)
            specs.append((
                i + 1, {ev.ino: per_ino_ops}, 1 << 30, (i, cut),
                f"torn@{i}+{cut}B",
            ))

    n_candidates = len(specs)
    truncated = False
    rng = random.Random(seed)
    if n_candidates > budget:
        truncated = True
        # half the budget is a deterministic even spread INCLUDING both
        # endpoints (a floor-stride spread can never pick the last
        # ~n/budget candidates — which are exactly the torn states of
        # the trace's final writes, generated last); the rest is a
        # seeded sample of the remainder so repeated runs with
        # different WEED_CRASH_SEEDs cover different slices
        det = max(2, budget // 2)
        stride = (n_candidates - 1) / (det - 1)
        idxs = {int(round(k * stride)) for k in range(det)}
        idxs.add(n_candidates - 1)
        rest = [i for i in range(n_candidates) if i not in idxs]
        rng.shuffle(rest)
        idxs.update(rest[: max(0, budget - len(idxs))])
        specs = [specs[i] for i in sorted(idxs)]
    else:
        # spend the remaining budget on seeded random mixed states
        extra = budget - n_candidates
        for _ in range(extra):
            if not events:
                break
            i = rng.randint(1, len(events))
            mand, ns_mand, counts, ns_total = _mandatory(trace, i)
            cuts = {
                ino: rng.randint(mand.get(ino, 0), total)
                for ino, total in counts.items()
            }
            ns_cut = rng.randint(ns_mand, ns_total)
            specs.append((i, cuts, ns_cut, None, f"random@{i}"))
    candidates = [_materialize(trace, *spec) for spec in specs]

    # dedup on (materialized content, acked-set): two states with the
    # same bytes but different ack coverage are DIFFERENT test cases —
    # the later one carries stronger invariants (keying on content
    # alone silently dropped the "batch fully applied AND acked" case)
    ack_prefix = [0]
    for ev in events:
        ack_prefix.append(ack_prefix[-1] + (ev.kind == "ack"))
    deduped: list[CrashState] = []
    seen: set[tuple[str, int]] = set()
    for st in candidates:
        key = (st.digest(), ack_prefix[min(st.crash_index, len(events))])
        if key not in seen:
            seen.add(key)
            deduped.append(st)
    return deduped, truncated, n_candidates


# ---------------------------------------------------------------------------
# the sweep harness


@dataclass
class CrashReport:
    workload: str
    states_tested: int = 0
    candidates: int = 0
    truncated: bool = False
    violations: list[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "workload": self.workload,
            "states_tested": self.states_tested,
            "candidates": self.candidates,
            "truncated": self.truncated,
            "violations": self.violations,
        }


def acked_at(trace: Trace, crash_index: int) -> list:
    """Every mark() payload whose ack event precedes the crash point —
    client-visible promises the recovered state must keep."""
    return [
        ev.payload for ev in trace.events[:crash_index]
        if ev.kind == "ack"
    ]


def sweep(trace: Trace, recover, workload: str = "workload",
          budget: int | None = None, seed: int | None = None,
          scratch: str | None = None) -> CrashReport:
    """Materialize every enumerated state and run `recover(dirpath,
    state, acked)` against it; any exception it raises is a recorded
    invariant violation. `acked` is the list of mark() payloads already
    acknowledged at the state's crash point."""
    report = CrashReport(workload=workload)
    states, report.truncated, report.candidates = enumerate_states(
        trace, budget=budget, seed=seed
    )
    scratch = scratch or scratch_base()
    base = tempfile.mkdtemp(prefix=f"weedcrash-{workload}-", dir=scratch)
    try:
        for st in states:
            state_dir = os.path.join(base, f"s{report.states_tested}")
            os.makedirs(state_dir)
            for rel, data in st.files.items():
                p = os.path.join(state_dir, rel)
                os.makedirs(os.path.dirname(p), exist_ok=True)
                with open(p, "wb") as f:
                    f.write(data)
            try:
                recover(state_dir, st, acked_at(trace, st.crash_index))
            except Exception as e:  # noqa: BLE001 — every failure is a finding
                report.violations.append(
                    f"[{st.label}] {type(e).__name__}: {e}"
                )
            shutil.rmtree(state_dir, ignore_errors=True)
            report.states_tested += 1
    finally:
        shutil.rmtree(base, ignore_errors=True)
    if report.truncated:
        # no-silent-caps: a bounded sweep must say it was bounded
        wlog.warning(
            "weedcrash[%s]: state budget hit — tested %d of %d "
            "candidate states (WEED_CRASH_BUDGET raises the bound)",
            workload, report.states_tested, report.candidates,
        )
    return report


# ---------------------------------------------------------------------------
# recovery invariants shared by the volume workloads


def verify_volume(state_dir: str, vid: int, acked: dict[int, bytes],
                  deleted: set[int] = frozenset(),
                  revisions: tuple[int, ...] | None = None):
    """Open the volume the way server startup does and assert the
    recovery invariants. Returns the recovered Volume's stats for
    workload-specific extra checks."""
    from seaweedfs_tpu.storage import types as t
    from seaweedfs_tpu.storage.needle import get_actual_size
    from seaweedfs_tpu.storage.volume import NeedleNotFound, Volume

    v = Volume(state_dir, vid, create=False, repair=True)
    try:
        if revisions is not None:
            rev = v.super_block.compaction_revision
            assert rev in revisions, (
                f"hybrid generation: compaction revision {rev} not in "
                f"{revisions}"
            )
        dat_size = v.data_file_size()
        for nv in v.nm.items():
            if nv.offset == 0 or nv.size == t.TOMBSTONE_FILE_SIZE:
                continue
            end = nv.actual_offset + get_actual_size(nv.size, v.version)
            assert end <= dat_size, (
                f"idx references bytes past .dat: needle {nv.key} ends "
                f"at {end}, .dat is {dat_size}"
            )
        for nid, data in acked.items():
            n = v.read_needle(nid)  # CRC-gated read
            assert n.data == data, (
                f"acked needle {nid}: recovered {len(n.data)}B != "
                f"written {len(data)}B"
            )
        for nid in deleted:
            try:
                v.read_needle(nid)
            except NeedleNotFound:
                continue
            raise AssertionError(f"deleted needle {nid} resurrected")
        return v.stats_snapshot()
    finally:
        v.close()


# ---------------------------------------------------------------------------
# workload traces (the ones the acceptance gate sweeps)


def _mk_needle(nid: int, payload: bytes):
    from seaweedfs_tpu.storage.needle import Needle

    return Needle(cookie=0x5EED, id=nid, data=payload)


def run_group_commit(budget: int | None = None,
                     seed: int | None = None) -> CrashReport:
    """Group-commit POST burst: base needles durably acked, then one
    write_needles batch (ONE pwritev + ONE fsync) acked at the end.
    Invariants: acked-at-crash needles survive every legal state, torn
    batch tails never surface as valid records."""
    from seaweedfs_tpu.storage.volume import Volume

    with tempfile.TemporaryDirectory() as d:
        v = Volume(d, 1)
        base = {i: b"base-%03d\xff" % i * 40 for i in range(1, 4)}
        for nid, data in base.items():
            v.write_needle(_mk_needle(nid, data))
        v.commit()
        v.close()
        # the volume is REOPENED inside the recording window so its
        # .dat fd and .idx append handle are the recording proxies —
        # handles opened before install() would bypass the trace
        rec = Recorder(d)
        rec.mark(dict(base))
        batch = {i: b"batch-%03d\x00\xfe" % i * 60 for i in range(10, 18)}
        with rec:
            v = Volume(d, 1, create=False)
            results = v.write_needles(
                [(_mk_needle(nid, data), None) for nid, data in batch.items()],
                durable=True,
            )
            for r in results:
                if isinstance(r, BaseException):
                    raise r
            rec.mark(dict(batch))
            v.close()

        def recover(state_dir, _st, acked_payloads):
            acked: dict[int, bytes] = {}
            for p in acked_payloads:
                acked.update(p)
            verify_volume(state_dir, 1, acked)

        return sweep(rec.trace, recover, workload="group-commit",
                     budget=budget, seed=seed)


def run_vacuum(budget: int | None = None,
               seed: int | None = None) -> CrashReport:
    """Vacuum crash matrix: compact() → post-snapshot write →
    commit_compact(), crashed at every enumerated point. Invariants:
    recovery reaches wholly-old or wholly-new (never the new .dat
    under the old .idx), every durably-acked needle survives both
    generations, deletes stay deleted."""
    from seaweedfs_tpu.storage.volume import Volume

    with tempfile.TemporaryDirectory() as d:
        v = Volume(d, 1)
        live = {i: b"vac-%03d\xaa" % i * 50 for i in range(1, 7)}
        for nid, data in live.items():
            v.write_needle(_mk_needle(nid, data))
        v.delete_needle(_mk_needle(2, b""))
        del live[2]
        old_rev = v.super_block.compaction_revision
        v.commit()
        v.close()
        rec = Recorder(d)
        rec.mark(dict(live))
        with rec:
            # reopened under the recorder: see run_group_commit
            v = Volume(d, 1, create=False)
            v.compact()
            extra = {20: b"post-snapshot\xbb" * 30}
            v.write_needle(_mk_needle(20, extra[20]))
            v.commit()
            rec.mark(dict(extra))
            v.commit_compact()
            v.close()

        def recover(state_dir, _st, acked_payloads):
            acked: dict[int, bytes] = {}
            for p in acked_payloads:
                acked.update(p)
            verify_volume(
                state_dir, 1, acked, deleted={2},
                revisions=(old_rev, old_rev + 1),
            )

        return sweep(rec.trace, recover, workload="vacuum",
                     budget=budget, seed=seed)


def run_quarantine(budget: int | None = None,
                   seed: int | None = None) -> CrashReport:
    """Scrub quarantine: the `.bad` rename of a corrupt EC shard plus
    the scrub_state.json cursor publish. Invariants: the shard's bytes
    exist under exactly one of its two names and are unmodified (the
    rename moves, never rewrites — rebuild needs the forensic copy
    intact), and the state file is always a complete JSON document —
    old or new, never torn."""
    import json

    from seaweedfs_tpu.ec import ec_files
    from seaweedfs_tpu.scrub.state import ScrubState

    with tempfile.TemporaryDirectory() as d:
        shard_rel = "7" + ec_files.to_ext(3)
        shard_path = os.path.join(d, shard_rel)
        shard_bytes = bytes(range(256)) * 64
        with open(shard_path, "wb") as f:
            f.write(shard_bytes)
        state = ScrubState(path=os.path.join(d, "scrub_state.json"))
        h = state.get(7, True)
        h.cursor = 11
        state.save()
        rec = Recorder(d)
        with rec:
            # the quarantine rename exactly as EcVolume performs it
            # (shard object graph elided: the effect trace is the
            # rename + dir fsync, which is what the invariant audits)
            os.replace(shard_path, shard_path + ".bad")
            from seaweedfs_tpu.util import durable

            durable.fsync_dir(d)
            h.cursor = 999
            h.corruptions_found += 1
            state.save()

        def recover(state_dir, _st, _acked):
            good = os.path.join(state_dir, shard_rel)
            bad = good + ".bad"
            names = [p for p in (good, bad) if os.path.exists(p)]
            assert len(names) == 1, (
                f"shard exists under {len(names)} names (want exactly 1)"
            )
            with open(names[0], "rb") as f:
                assert f.read() == shard_bytes, "shard bytes changed"
            sp = os.path.join(state_dir, "scrub_state.json")
            if os.path.exists(sp):
                with open(sp) as f:
                    doc = json.load(f)  # torn JSON raises = violation
                cursors = {
                    row.get("cursor")
                    for row in doc.get("volumes", [])
                }
                assert cursors <= {11, 999}, f"hybrid cursor {cursors}"

        return sweep(rec.trace, recover, workload="quarantine",
                     budget=budget, seed=seed)


def run_handoff_hint(budget: int | None = None, seed: int | None = None,
                     durable: bool = True) -> CrashReport:
    """Hinted-handoff spool (weedguard, docs/HEALTH.md): the primary
    durably publishes a replica request as a hint BEFORE acking the
    client (server/handoff.HintStore.write_hint → util/durable), and a
    replay after crash must deliver the exact bytes. Invariants per
    crash state: once the hint write is acked, EXACTLY one complete
    hint exists and parses back byte-identical (acked-with-hint is a
    durability promise — losing or tearing it loses an acked write);
    before the ack, any *.hint under the final name must still be
    complete (rename only ever publishes fsynced bytes). A delivered
    hint (post-unlink + dirsync mark) must stay gone — a resurrected
    hint is the double-apply shape.

    `durable=False` replays the BUG ordering (plain write + rename, no
    fsyncs) as the positive control: the enumerator must surface
    rename-before-data states where the published hint is torn."""
    import struct as _struct

    body = bytes(range(256)) * 40 + b"\x00tail"
    target = "127.0.0.1:18080"
    path = "/3,0203fbfb?type=replicate"
    headers = {"Content-Type": "application/octet-stream"}

    with tempfile.TemporaryDirectory() as d:
        rec = Recorder(d)
        with rec:
            if durable:
                from seaweedfs_tpu.server.handoff import HintStore

                hs = HintStore(os.path.join(d, "spool"))
                assert hs.write_hint(target, "POST", path, body, headers)
            else:
                # the planted bug: same wire format, no fsync before
                # the rename, no dirsync after
                import json as _json

                tdir = os.path.join(d, "spool", "127.0.0.1_18080")
                os.makedirs(tdir, exist_ok=True)
                head = _json.dumps(
                    {"target": target, "method": "POST", "path": path,
                     "headers": headers}
                ).encode()
                tmp = os.path.join(tdir, "0000000000001-000001.hint.tmp")
                with open(tmp, "wb") as f:
                    f.write(_struct.pack(">I", len(head)))
                    f.write(head)
                    f.write(body)
                os.replace(tmp, tmp[: -len(".tmp")])
            rec.mark({"hint": True})

        def recover(state_dir, _st, acked_payloads):
            from seaweedfs_tpu.server.handoff import HintStore

            hs = HintStore(os.path.join(state_dir, "spool"))
            hints = []
            for _t, tdir in hs.targets():
                for e in sorted(os.scandir(tdir), key=lambda e: e.name):
                    if e.name.endswith(".hint"):
                        hints.append(e.path)
            if acked_payloads:
                assert len(hints) == 1, (
                    f"acked hint missing/duplicated: {len(hints)} found"
                )
            for hp in hints:
                parsed = hs.read_hint(hp)
                assert parsed is not None, f"torn hint published: {hp}"
                head, got = parsed
                assert got == body, (
                    f"hint body corrupt: {len(got)}B != {len(body)}B"
                )
                assert head["target"] == target and head["path"] == path

        return sweep(rec.trace, recover, workload="handoff-hint",
                     budget=budget, seed=seed)


def run_handoff_delivery(budget: int | None = None,
                         seed: int | None = None) -> CrashReport:
    """The other half of the hint lifecycle: after the agent delivers a
    hint it unlinks the file and fsyncs the spool dir — a crash then
    must never resurrect the hint (a revived hint replays a write the
    replica already applied: the double-apply shape; harmless for
    byte-identical needles but the contract is audited anyway)."""
    body = b"delivered-hint" * 64
    with tempfile.TemporaryDirectory() as d:
        from seaweedfs_tpu.server.handoff import HintStore

        hs = HintStore(os.path.join(d, "spool"))
        assert hs.write_hint(
            "127.0.0.1:18081", "POST", "/4,01aa?type=replicate", body, {}
        )
        (tgt, tdir), = hs.targets()
        (name,) = [
            e.name for e in os.scandir(tdir) if e.name.endswith(".hint")
        ]
        rec = Recorder(d)
        with rec:
            hs2 = HintStore(os.path.join(d, "spool"))
            hs2.remove(os.path.join(tdir, name))
            rec.mark({"delivered": True})

        def recover(state_dir, _st, acked_payloads):
            hp = os.path.join(
                state_dir, "spool", "127.0.0.1_18081", name
            )
            if acked_payloads:
                assert not os.path.exists(hp), (
                    "delivered hint resurrected after crash"
                )

        return sweep(rec.trace, recover, workload="handoff-delivery",
                     budget=budget, seed=seed)


def run_broken_publish(budget: int | None = None,
                       seed: int | None = None) -> CrashReport:
    """Positive control (the planted bug tests/test_crash.py must DETECT
    on every run): a tmp+rename publish with NO fsync of the bytes. The
    enumerator must produce at least one legal state where the rename
    landed but the data did not — an empty/torn file under the final
    name."""
    import json

    with tempfile.TemporaryDirectory() as d:
        final = os.path.join(d, "state.json")
        with open(final, "w") as f:
            json.dump({"gen": 1}, f)
        rec = Recorder(d)
        with rec:
            tmp = final + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"gen": 2, "pad": "x" * 64}, f)
            os.replace(tmp, final)  # the bug: no fsync before, no dirsync after

        def recover(state_dir, _st, _acked):
            with open(os.path.join(state_dir, "state.json")) as f:
                doc = json.load(f)
            assert doc.get("gen") in (1, 2), f"hybrid doc {doc}"

        return sweep(rec.trace, recover, workload="broken-publish",
                     budget=budget, seed=seed)


def run_ec_encode(budget: int | None = None, seed: int | None = None,
                  durable: bool = True) -> CrashReport:
    """EC shard writer-pool flush ordering (the PR-11 follow-on): sweep
    stream_write_ec_files — the pooled preallocate+pwritev driver — plus
    the .ecx publish that acks the encode. Invariant: whenever the .ecx
    exists complete under its final name, every shard file byte-equals
    a control encode (the generate verb's contract: a visible index
    never fronts page-cache-only shard bytes).

    durable=False replays the PRE-FIX ordering (no shard fsyncs, .ecx
    written in place) — the regression control that must DETECT the
    complete-index-over-torn-shards states."""
    import shutil as _shutil

    from seaweedfs_tpu.ec import ec_files, ec_stream
    from seaweedfs_tpu.ec.codec import new_encoder
    from seaweedfs_tpu.storage.volume import Volume

    # tiny block geometry keeps shard files (and every materialized
    # state) a few KB; .ecx content only depends on the .idx
    blocks = {"large_block_size": 8192, "small_block_size": 4096}
    with tempfile.TemporaryDirectory() as d:
        v = Volume(d, 1)
        for nid in range(1, 4):
            v.write_needle(_mk_needle(nid, b"ec-%03d\xee" % nid * 30))
        v.commit()
        v.close()
        base = os.path.join(d, "1")
        rs = new_encoder(backend="cpu")
        parity_fn, fetch_fn = ec_stream.local_encode_fns(rs)

        def encode(target_base: str, durable_arm: bool) -> None:
            ec_stream.stream_write_ec_files(
                target_base, tile_bytes=4096, parity_fn=parity_fn,
                fetch_fn=fetch_fn, writer_threads=2, reader_threads=1,
                durable=durable_arm, **blocks,
            )
            ec_files.write_sorted_file_from_idx(
                target_base, durable=durable_arm
            )

        # control: the byte-exact expected outputs, encoded outside the
        # recorder from a copy of the same .dat/.idx
        ctl = os.path.join(d, "ctl")
        os.makedirs(ctl)
        for ext in (".dat", ".idx"):
            _shutil.copy(base + ext, os.path.join(ctl, "1" + ext))
        encode(os.path.join(ctl, "1"), durable_arm=True)
        expect = {}
        for i in range(ec_files.TOTAL_SHARDS):
            with open(os.path.join(ctl, "1" + ec_files.to_ext(i)), "rb") as f:
                expect[ec_files.to_ext(i)[1:]] = f.read()
        with open(os.path.join(ctl, "1.ecx"), "rb") as f:
            expect["ecx"] = f.read()
        _shutil.rmtree(ctl)

        rec = Recorder(d)
        with rec:
            encode(base, durable_arm=durable)
            rec.mark("encoded")

        def recover(state_dir, _st, _acked):
            ecx = os.path.join(state_dir, "1.ecx")
            if not os.path.exists(ecx):
                return  # encode never acked: nothing is promised
            with open(ecx, "rb") as f:
                got = f.read()
            assert got == expect["ecx"], (
                f".ecx visible but torn: {len(got)}B of "
                f"{len(expect['ecx'])}B"
            )
            for i in range(ec_files.TOTAL_SHARDS):
                ext = ec_files.to_ext(i)
                p = os.path.join(state_dir, "1" + ext)
                assert os.path.exists(p), f".ecx complete but {ext} missing"
                with open(p, "rb") as f:
                    shard = f.read()
                assert shard == expect[ext[1:]], (
                    f".ecx complete but {ext} bytes wrong "
                    f"({len(shard)}B, want {len(expect[ext[1:]])}B)"
                )

        return sweep(rec.trace, recover, workload="ec-encode",
                     budget=budget, seed=seed)


def run_ecc_publish(budget: int | None = None, seed: int | None = None,
                    durable: bool = True) -> CrashReport:
    """`.ecc` scrub-sidecar publish ordering (ec/ecc_sidecar.py): the
    sidecar ATTESTS shard bytes, so it must never reach its final name
    before those bytes are durable. Sweep: write 14 shard files, fsync
    them, publish the sidecar through util/durable.publish. Invariant:
    whenever a parseable sidecar exists under its final name, every
    shard it attests exists with exactly the attested size and
    CRC-32C — a crash can leave NO sidecar (scrub takes the parity
    path, fine) or a torn one (load fails, parity path, fine), but
    never a confident sidecar over lost shard bytes.

    durable=False replays the planted ordering bug — shard fsyncs
    skipped, sidecar still published durably — which the sweep must
    DETECT: the durable-only reorder state has the fsynced sidecar
    complete and visible over empty shard files."""
    from seaweedfs_tpu.ec import ec_files, ecc_sidecar
    from seaweedfs_tpu.util import durable as _durable
    from seaweedfs_tpu.util.crc import crc32c

    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "1")
        rec = Recorder(d)
        with rec:
            crcs = []
            for sid in range(ec_files.TOTAL_SHARDS):
                data = bytes([0x40 + sid]) * (512 + 64 * sid)
                with open(base + ec_files.to_ext(sid), "wb") as f:
                    f.write(data)
                crcs.append(crc32c(data))
            if durable:
                # the ordering under test: shard bytes durable BEFORE
                # the sidecar that vouches for them becomes visible
                for sid in range(ec_files.TOTAL_SHARDS):
                    _durable.fsync_path(base + ec_files.to_ext(sid))
            ecc_sidecar.write_sidecar(
                base, crcs, total_shards=ec_files.TOTAL_SHARDS
            )
            rec.mark("published")

        def recover(state_dir, _st, _acked):
            b = os.path.join(state_dir, "1")
            doc = ecc_sidecar.load_sidecar(b)
            if doc is None:
                return  # absent/torn sidecar: the parity path covers it
            for sid_s, ent in doc["shards"].items():
                p = b + ec_files.to_ext(int(sid_s))
                assert os.path.exists(p), (
                    f"sidecar attests shard {sid_s} that does not exist"
                )
                with open(p, "rb") as f:
                    got = f.read()
                assert len(got) == ent["size"], (
                    f"sidecar attests shard {sid_s} at {ent['size']}B "
                    f"but {len(got)}B are on disk"
                )
                assert crc32c(got) == ent["crc"], (
                    f"sidecar CRC mismatch on shard {sid_s}: the "
                    f"sidecar outlived the bytes it attests"
                )

        return sweep(rec.trace, recover, workload="ecc-publish",
                     budget=budget, seed=seed)


def run_shard_handback(budget: int | None = None,
                       seed: int | None = None) -> CrashReport:
    """-shardWrites ownership handback (the PR-11 follow-on): a worker
    OWNS a vid's writes (SharedReadVolume appends through the same
    Volume write path as the lead), releases ownership, and the lead
    appends more and commits. Sweeps the combined append stream.
    Invariants: every needle acked at the final durability point
    survives recovery (the lead's commit fsyncs the .dat; repair-mode
    open re-indexes fsynced-but-unindexed records), the .idx never
    references past the .dat, torn tails never surface as valid."""
    from seaweedfs_tpu.server.volume_workers import SharedReadVolume
    from seaweedfs_tpu.storage.volume import Volume

    with tempfile.TemporaryDirectory() as d:
        v = Volume(d, 1)
        base = {i: b"lead-%03d\xaa" % i * 40 for i in range(1, 4)}
        for nid, data in base.items():
            v.write_needle(_mk_needle(nid, data))
        v.commit()
        v.close()
        rec = Recorder(d)
        rec.mark(dict(base))
        with rec:
            # worker-owned phase: appends ride the shared wrapper the
            # -shardWrites read workers use for owned vids
            w = SharedReadVolume(d, 1)
            worker_writes = {i: b"wrk-%03d\x00\xfe" % i * 50
                             for i in range(10, 14)}
            for nid, data in worker_writes.items():
                w.write_needle(_mk_needle(nid, data))
            # handback: worker stops writing forever; the lead reopens,
            # catches up from the on-disk .idx, appends, and COMMITS —
            # the durability point the final ack rides
            lead = Volume(d, 1, create=False)
            lead_writes = {i: b"ld2-%03d\xbb" % i * 45
                           for i in range(20, 23)}
            for nid, data in lead_writes.items():
                lead.write_needle(_mk_needle(nid, data))
            lead.commit()
            rec.mark({**worker_writes, **lead_writes})
            lead.close()
            w.close()

        def recover(state_dir, _st, acked_payloads):
            acked: dict[int, bytes] = {}
            for p in acked_payloads:
                acked.update(p)
            verify_volume(state_dir, 1, acked)

        return sweep(rec.trace, recover, workload="shard-handback",
                     budget=budget, seed=seed)


ALL_WORKLOADS = {
    "group-commit": run_group_commit,
    "vacuum": run_vacuum,
    "quarantine": run_quarantine,
    "ec-encode": run_ec_encode,
    "ecc-publish": run_ecc_publish,
    "shard-handback": run_shard_handback,
    "handoff-hint": run_handoff_hint,
    "handoff-delivery": run_handoff_delivery,
}


def run_all(budget: int | None = None, seed: int | None = None
            ) -> list[CrashReport]:
    return [fn(budget=budget, seed=seed) for fn in ALL_WORKLOADS.values()]
