"""weedlint self-tests: the analysis plane must catch what it claims.

A checker that silently goes blind is worse than no checker — every
rule here gets a positive control (a synthetic tree with a planted
bug the rule MUST flag) and the real tree gets the negative control
(`python -m seaweedfs_tpu.analysis` exits 0).
"""

from __future__ import annotations

import subprocess
import sys
import textwrap
import threading
import time

import pytest

from seaweedfs_tpu.analysis import (
    Finding,
    apply_suppressions,
    scan_suppressions,
)


def _write_pkg(tmp_path, files: dict[str, str]) -> str:
    root = tmp_path / "fakepkg"
    root.mkdir()
    (root / "__init__.py").write_text("")
    for name, src in files.items():
        (root / name).write_text(textwrap.dedent(src))
    return str(root)


# ---------------------------------------------------------------------------
# suppression policy


class TestSuppressions:
    def test_reason_required(self):
        sup = scan_suppressions(
            "x = 1  # weedlint: ignore[hot-loop-sleep]\n"
            "y = 2  # weedlint: ignore[lock-order] — held across tx\n"
        )
        assert sup.bare == [(1, "hot-loop-sleep")]
        assert "lock-order" in sup.by_line[2]

    def test_bare_ignore_becomes_finding(self):
        kept, _ = apply_suppressions(
            [], {"mod.py": "a = 1  # weedlint: ignore[x]\n"}
        )
        assert [f.rule for f in kept] == ["bare-ignore"]

    def test_comment_above_silences_next_line(self):
        findings = [Finding("hot-loop-sleep", "mod.py", 2, "m")]
        kept, suppressed = apply_suppressions(
            findings,
            {"mod.py": "# weedlint: ignore[hot-loop-sleep] — bounded\n"
                       "time.sleep(1)\n"},
        )
        assert not kept and len(suppressed) == 1

    def test_inline_ignore_does_not_bleed_to_next_line(self):
        """An inline ignore must not silence an adjacent unannotated
        finding on the following line."""
        findings = [
            Finding("hot-loop-sleep", "mod.py", 1, "annotated"),
            Finding("hot-loop-sleep", "mod.py", 2, "NOT annotated"),
        ]
        kept, suppressed = apply_suppressions(
            findings,
            {"mod.py": "time.sleep(a)  # weedlint: ignore[hot-loop-sleep] — bounded\n"
                       "time.sleep(b)\n"},
        )
        assert len(suppressed) == 1 and suppressed[0].line == 1
        assert len(kept) == 1 and kept[0].line == 2


# ---------------------------------------------------------------------------
# static lock-order


class TestLockOrder:
    def test_cycle_detected(self, tmp_path):
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class A:
                def __init__(self):
                    self.la = threading.Lock()
                    self.lb = threading.Lock()

                def ab(self):
                    with self.la:
                        with self.lb:
                            pass

                def ba(self):
                    with self.lb:
                        with self.la:
                            pass
        """})
        findings, _ = lockorder.check(root)
        assert any(f.rule == "lock-order" for f in findings)
        msg = next(f for f in findings if f.rule == "lock-order").message
        assert "A.la" in msg and "A.lb" in msg

    def test_interprocedural_cycle_via_method_call(self, tmp_path):
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class A:
                def __init__(self):
                    self.la = threading.Lock()
                    self.lb = threading.Lock()

                def helper(self):
                    with self.lb:
                        pass

                def ab(self):
                    with self.la:
                        self.helper()

                def ba(self):
                    with self.lb:
                        with self.la:
                            pass
        """})
        findings, _ = lockorder.check(root)
        assert any(f.rule == "lock-order" for f in findings)

    def test_callback_param_edge(self, tmp_path):
        """The precheck-callback idiom: locks a callback takes are
        ordered after locks the callee holds at its param() call."""
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Vol:
                def __init__(self):
                    self.vlock = threading.Lock()

                def write(self, precheck=None):
                    with self.vlock:
                        if precheck is not None and not precheck():
                            raise RuntimeError()

            class Worker:
                def __init__(self):
                    self.rlock = threading.Lock()
                    self.v = None

                def handle(self, v: Vol):
                    def still_owned():
                        with self.rlock:
                            return True
                    v.write(precheck=still_owned)

                def inverted(self, v: Vol):
                    with self.rlock:
                        with v.vlock:
                            pass
        """})
        findings, index = lockorder.check(root)
        edges = lockorder.build_lock_graph(index)
        assert ("Vol.vlock", "Worker.rlock") in edges
        assert any(f.rule == "lock-order" for f in findings)

    def test_sequential_not_a_cycle(self, tmp_path):
        """The _shard_release shape: take-release then take the other
        — no nesting, no edge, no finding."""
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class A:
                def __init__(self):
                    self.la = threading.Lock()
                    self.lb = threading.Lock()

                def ab(self):
                    with self.la:
                        with self.lb:
                            pass

                def sequential(self):
                    with self.lb:
                        x = 1
                    with self.la:
                        pass
        """})
        findings, _ = lockorder.check(root)
        assert not [f for f in findings if f.rule == "lock-order"]

    def test_unguarded_write_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class C:
                def __init__(self):
                    self.lock = threading.Lock()
                    self.count = 0

                def good(self):
                    with self.lock:
                        self.count += 1

                def bad(self):
                    self.count += 1
        """})
        findings, _ = lockorder.check(root)
        hits = [f for f in findings if f.rule == "unguarded-write"]
        assert len(hits) == 1 and "C.count" in hits[0].message

    def test_locked_helper_inherits_guard(self, tmp_path):
        """The _refill_locked idiom must NOT be flagged."""
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class C:
                def __init__(self):
                    self.lock = threading.Lock()
                    self.count = 0

                def _bump_locked(self):
                    self.count += 1

                def good(self):
                    with self.lock:
                        self._bump_locked()

                def also_good(self):
                    with self.lock:
                        self.count = 0
        """})
        findings, _ = lockorder.check(root)
        assert not [f for f in findings if f.rule == "unguarded-write"]

    def test_duplicate_class_names_do_not_merge(self, tmp_path):
        """Two classes sharing a bare name in different modules must
        stay distinct: the method-uniqueness probe must count BOTH
        `take` definitions (no resolution), never attribute one
        module's call to the other's lock."""
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {
            "mod_a.py": """
                import threading

                class Reader:
                    def __init__(self):
                        self.la = threading.Lock()

                    def take(self):
                        with self.la:
                            pass
            """,
            "mod_b.py": """
                import threading

                class Reader:
                    def __init__(self):
                        self.lb = threading.Lock()

                    def take(self):
                        pass

                    def caller(self, r):
                        with self.lb:
                            r.take()
            """,
        })
        findings, index = lockorder.check(root)
        assert len(index.classes_by_name["Reader"]) == 2
        assert len(index.methods_by_name["take"]) == 2
        # `r.take()` must stay UNRESOLVED (ambiguous), so no edge
        # lb -> la gets invented
        edges = lockorder.build_lock_graph(index)
        assert ("Reader.lb", "Reader.la") not in edges
        assert not [f for f in findings if f.rule == "lock-order"]

    def test_split_protocol_release_implies_held(self, tmp_path):
        """begin/commit transaction split: commit's writes are under
        the lock acquired in begin."""
        from seaweedfs_tpu.analysis import lockorder

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Tx:
                def __init__(self):
                    self.lock = threading.Lock()
                    self.depth = 0

                def begin(self):
                    self.lock.acquire()
                    self.depth += 1

                def commit(self):
                    self.depth -= 1
                    self.lock.release()
        """})
        findings, _ = lockorder.check(root)
        assert not [f for f in findings if f.rule == "unguarded-write"]


# ---------------------------------------------------------------------------
# hot-loop


class TestHotLoop:
    def test_sleep_in_dispatch_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import hotloop

        root = _write_pkg(tmp_path, {"srv.py": """
            import time
            from seaweedfs_tpu.util.httpd import FastHandler

            class H(FastHandler):
                def do_GET(self):
                    self._helper()

                def _helper(self):
                    time.sleep(1)
        """})
        findings, _ = hotloop.check(root)
        assert [f.rule for f in findings] == ["hot-loop-sleep"]

    def test_urlopen_without_timeout_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import hotloop

        root = _write_pkg(tmp_path, {"srv.py": """
            import urllib.request
            from seaweedfs_tpu.util.httpd import FastHandler

            class H(FastHandler):
                def do_POST(self):
                    urllib.request.urlopen("http://x/")

                def fine(self):
                    urllib.request.urlopen("http://x/", timeout=5)
        """})
        findings, _ = hotloop.check(root)
        assert [f.rule for f in findings] == ["hot-loop-no-timeout"]

    def test_off_dispatch_code_not_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import hotloop

        root = _write_pkg(tmp_path, {"bg.py": """
            import time

            class Sweeper:
                def loop(self):
                    time.sleep(600)
        """})
        findings, _ = hotloop.check(root)
        assert not findings


# ---------------------------------------------------------------------------
# contracts tier (weedlint v2)


class TestContracts:
    def test_unserved_route_flagged_and_served_not(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"srv.py": """
            import urllib.request
            from seaweedfs_tpu.util.httpd import FastHandler

            class H(FastHandler):
                def do_GET(self):
                    if self.path == "/served":
                        return

            def dial_ok():
                urllib.request.urlopen(
                    "http://127.0.0.1:1/served", timeout=5
                )

            def dial_drifted():
                urllib.request.urlopen(
                    "http://127.0.0.1:1/renamed-away", timeout=5
                )
        """})
        findings, _, reg = contracts.check(root=root)
        routes = [f for f in findings if f.rule == "contract-route"]
        assert len(routes) == 1 and "/renamed-away" in routes[0].message
        assert "/served" in reg.served.get("other", {})

    def test_relative_ui_link_checked_per_module(self, tmp_path):
        """The PR-6 filer bug class: a UI href must be served by the
        SAME module's dispatch — another daemon's route must not mask
        the 404."""
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"srv.py": """
            from seaweedfs_tpu.util.httpd import FastHandler

            class H(FastHandler):
                def do_GET(self):
                    if self.path == "/":
                        self.fast_reply(
                            200, b'<a href="/missing-page">x</a>'
                        )
        """})
        findings, _, _reg = contracts.check(root=root)
        assert any(
            f.rule == "contract-route" and "/missing-page" in f.message
            for f in findings
        )

    def test_orphan_metric_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"metrics.py": """
            class Registry:
                def counter(self, name, help_):
                    return object()

            R = Registry()
            USED = R.counter("weed_used_total", "written elsewhere")
            DEAD = R.counter("weed_dead_total", "never touched")
        """, "writer.py": """
            from . import metrics

            def bump():
                metrics.USED.inc()
        """})
        findings, _, _reg = contracts.check(root=root)
        orphans = [
            f for f in findings if f.rule == "contract-metric-orphan"
        ]
        assert len(orphans) == 1 and "weed_dead_total" in orphans[0].message

    def test_queried_unregistered_metric_flagged(self, tmp_path):
        """The alert-wiring drift class: a ring query against a family
        no Registry registers returns empty forever."""
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"alerts.py": """
            def evaluate(ts):
                return ts.rate_sum("weed_ghost_total", 120.0)
        """})
        findings, _, _reg = contracts.check(root=root)
        assert any(
            f.rule == "contract-metric" and "weed_ghost_total" in f.message
            for f in findings
        )

    def test_header_stamped_never_parsed(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"hop.py": """
            def stamp(headers):
                headers["x-weed-ghost"] = "1"

            def stamp_and_parse(headers):
                headers["x-weed-pair"] = "1"
                return headers.get("x-weed-pair")
        """})
        findings, _, _reg = contracts.check(root=root)
        hdr = [f for f in findings if f.rule == "contract-header"]
        assert len(hdr) == 1 and "x-weed-ghost" in hdr[0].message

    def test_status_without_reason_entry(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"handler.py": """
            class H:
                def reply(self):
                    self.fast_reply(418, b"teapot")
                    self.fast_reply(200, b"ok")
        """})
        (tmp_path / "fakepkg" / "util").mkdir()
        (tmp_path / "fakepkg" / "util" / "__init__.py").write_text("")
        (tmp_path / "fakepkg" / "util" / "httpd.py").write_text(
            '_REASON = {200: b"OK"}\n'
        )
        findings, _, _reg = contracts.check(root=str(tmp_path / "fakepkg"))
        hits = [
            f for f in findings if f.rule == "contract-status-reason"
        ]
        assert len(hits) == 1 and "418" in hits[0].message

    def test_env_var_contract_both_directions(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = _write_pkg(tmp_path, {"knobs.py": """
            import os

            DOCUMENTED = os.environ.get("WEED_FIXTURE_DOCUMENTED")
            SECRET = os.environ.get("WEED_FIXTURE_SECRET")
        """})
        docs = {"OPS.md": "set `WEED_FIXTURE_DOCUMENTED` and also "
                          "`WEED_FIXTURE_GONE` (removed in v2)\n"}
        findings, _, _reg = contracts.check(root=root, docs=docs)
        envs = {f.message.split()[2]: f for f in findings
                if f.rule == "contract-env"}
        assert "WEED_FIXTURE_SECRET" in envs  # read, undocumented
        assert "WEED_FIXTURE_GONE" in envs  # documented, never read
        assert "WEED_FIXTURE_DOCUMENTED" not in envs

    def test_real_tree_registries_extracted(self):
        """The real tree's contract registries must keep seeing the
        load-bearing edges (a checker whose extraction silently decays
        to empty would pass every cross-check forever)."""
        from seaweedfs_tpu.analysis import contracts

        _findings, _idx, reg = contracts.check()
        assert "/dir/assign" in reg.served.get("master", {})
        assert "/cluster/register" in reg.served.get("master", {})
        assert "/metrics" in reg.served.get("_funnel", {})
        client_paths = {p for _k, p, _h, _s in reg.client_routes}
        assert "/dir/assign" in client_paths
        assert "/cluster/health" in client_paths  # shell command side
        assert "x-weed-trace" in reg.header_stamped
        assert "x-weed-trace" in reg.header_parsed
        assert "weed_http_request_total" in reg.metric_registered
        assert "weed_http_request_total" in reg.metric_queried
        assert "WEED_NATIVE_POST" in reg.env_read
        assert "WEED_NATIVE_POST" in reg.env_documented

    def test_extra_source_findings_are_suppressible(self):
        """Review regression: findings anchored in tests/conftest.py /
        docs must be reachable by the suppression
        scan — check() merges those texts into index.sources so an
        inline `# weedlint: ignore[...]` there actually works."""
        from seaweedfs_tpu.analysis import contracts

        _findings, idx, _reg = contracts.check()
        assert "tests/conftest.py" in idx.sources
        assert "OPERATIONS.md" in idx.sources

    def test_dead_seed_metric_families_stay_gone(self):
        """Round-12 contract fix: the five registered-but-never-touched
        seed families must not come back to /metrics as constant-zero
        rows that look like live instrumentation."""
        from seaweedfs_tpu.stats.metrics import DEFAULT_REGISTRY

        text = DEFAULT_REGISTRY.render_text()
        for dead in (
            "weed_request_total",
            "weed_request_seconds",
            "weed_volumes",
            "weed_filer_store_total",
            "weed_filer_store_seconds",
        ):
            assert dead not in text
        assert "weed_http_request_total" in text  # the real family


class TestNoDeadline:
    """The deadline-bypass rule (docs/CHAOS.md): raw urlopen() on a
    data-plane module can never inherit the request's X-Weed-Deadline
    budget — each site either migrates to http_call or states why the
    bounded one-hop timeout suffices."""

    def _scoped_pkg(self, tmp_path, rel: str, src: str) -> str:
        import textwrap

        root = tmp_path / "seaweedfs_tpu"
        target = root / rel
        target.parent.mkdir(parents=True, exist_ok=True)
        (root / "__init__.py").write_text("")
        init = target.parent / "__init__.py"
        if not init.exists():
            init.write_text("")
        target.write_text(textwrap.dedent(src))
        return str(root)

    def test_planted_urlopen_on_data_plane_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = self._scoped_pkg(tmp_path, "server/mod.py", """
            import urllib.request

            def hop(url):
                return urllib.request.urlopen(url, timeout=10).read()
        """)
        findings, _, reg = contracts.check(root=root)
        hits = [f for f in findings if f.rule == "no-deadline"]
        assert len(hits) == 1 and hits[0].path.endswith("server/mod.py")
        assert len(reg.deadline_bypass) == 1

    def test_out_of_scope_module_not_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import contracts

        root = self._scoped_pkg(tmp_path, "telemetry/mod.py", """
            import urllib.request

            def scrape(url):
                return urllib.request.urlopen(url, timeout=5).read()
        """)
        findings, _, _reg = contracts.check(root=root)
        assert not [f for f in findings if f.rule == "no-deadline"]

    def test_suppression_with_reason_silences(self, tmp_path):
        from seaweedfs_tpu.analysis import apply_suppressions, contracts

        root = self._scoped_pkg(tmp_path, "server/mod.py", """
            import urllib.request

            def hop(url):
                # weedlint: ignore[no-deadline] — one bounded local hop
                return urllib.request.urlopen(url, timeout=10).read()
        """)
        findings, idx, _reg = contracts.check(root=root)
        kept, suppressed = apply_suppressions(findings, idx.sources)
        assert not [f for f in kept if f.rule == "no-deadline"]
        assert [f for f in suppressed if f.rule == "no-deadline"]

    def test_real_tree_deadline_header_contract_whole(self):
        """Satellite: x-weed-deadline joins the stamped-vs-parsed hop
        header registry — both sides must exist in the real tree."""
        from seaweedfs_tpu.analysis import contracts

        _findings, _idx, reg = contracts.check()
        assert "x-weed-deadline" in reg.header_stamped
        assert "x-weed-deadline" in reg.header_parsed


# ---------------------------------------------------------------------------
# lifecycle tier (weedlint v2)


class TestLifecycle:
    def _check(self, tmp_path, src: str):
        from seaweedfs_tpu.analysis import lifecycle

        root = _write_pkg(tmp_path, {"mod.py": src})
        findings, _ = lifecycle.check(root=root)
        return findings

    def test_fd_leaked_across_early_return(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def probe(p):
                fd = os.open(p, os.O_RDONLY)
                if os.fstat(fd).st_size == 0:
                    return None
                os.close(fd)
                return True
        """)
        assert [f.rule for f in findings] == ["lifecycle-fd-leak"]
        assert "returns at line" in findings[0].message

    def test_with_and_try_finally_are_clean(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def with_form(p):
                with open(p, "rb") as f:
                    return f.read()

            def finally_form(p):
                fd = os.open(p, os.O_RDONLY)
                try:
                    if os.fstat(fd).st_size == 0:
                        return None
                    return os.read(fd, 10)
                finally:
                    os.close(fd)
        """)
        assert findings == []

    def test_escapes_are_ownership_transfers(self, tmp_path):
        findings = self._check(tmp_path, """
            import os
            import socket

            class Pool:
                def __init__(self, p):
                    self.fd = os.open(p, os.O_RDONLY)  # stored: Pool owns

                def adopt(self, p):
                    fd = os.open(p, os.O_RDONLY)
                    self.fd = fd  # escapes to self

            def returned(p):
                f = open(p, "rb")
                return f  # caller owns now

            def closure(p):
                f = open(p, "rb")
                def gen():
                    with f:
                        yield f.read()
                return gen()
        """)
        assert findings == []

    def test_thread_started_never_joined(self, tmp_path):
        findings = self._check(tmp_path, """
            import threading

            def fire_and_forget(work):
                t = threading.Thread(target=work)
                t.start()

            def daemon_ok(work):
                t = threading.Thread(target=work, daemon=True)
                t.start()

            def joined_ok(work):
                t = threading.Thread(target=work)
                t.start()
                t.join()
        """)
        assert [f.rule for f in findings] == ["lifecycle-thread-leak"]
        assert "fire_and_forget" in findings[0].message

    def test_interprocedural_allocator_carries_obligation(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            def _open_shard(p):
                fd = os.open(p, os.O_RDONLY)
                return fd

            def reader_leaks(p):
                fd = _open_shard(p)
                if os.fstat(fd).st_size == 0:
                    return None
                os.close(fd)
                return fd

            def closer(fd):
                os.close(fd)

            def reader_transfers(p):
                fd = _open_shard(p)
                closer(fd)
        """)
        assert [f.rule for f in findings] == ["lifecycle-fd-leak"]
        assert "reader_leaks" in findings[0].message

    def test_acquisition_args_transfer_ownership(self, tmp_path):
        """Review regression: a tracked resource fed INTO another
        acquisition call transfers ownership — os.fdopen(fd) owns fd
        (f.close() closes it) and Thread(args=(conn,)) hands the
        accepted socket to the worker."""
        findings = self._check(tmp_path, """
            import os
            import threading

            def fdopen_owns_the_fd(p):
                fd = os.open(p, os.O_RDONLY)
                f = os.fdopen(fd)
                f.close()
                return True

            def worker_owns_the_conn(listener, handle):
                conn, addr = listener.accept()
                t = threading.Thread(
                    target=handle, args=(conn,), daemon=True
                )
                t.start()
        """)
        assert findings == []

    def test_owns_annotation_transfers_ownership(self, tmp_path):
        findings = self._check(tmp_path, """
            import os

            # weedlint: owns[fd] — the C ring adopts the descriptor
            def ring_register(fd):
                _native_register(fd)

            def no_leak(p):
                fd = os.open(p, os.O_RDONLY)
                ring_register(fd)
        """)
        assert findings == []


# ---------------------------------------------------------------------------
# race: shared-state escape lint (weedlint v4)


class TestRaceLint:
    """Positive/negative matrix for `race-check-then-act`: escaped
    check-then-act caught; constructor, classmethod, confined-class,
    and continuous-hold shapes stay silent."""

    def test_escaped_check_then_act_flagged(self, tmp_path):
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._primed = False

                def prime(self):
                    if not self._primed:
                        self._primed = True

            def spin(p: "Pump"):
                threading.Thread(target=p.prime).start()
        """})
        findings, _ = racelint.check(root)
        assert any(
            f.rule == "race-check-then-act" and "prime" in f.message
            for f in findings
        )
        msg = next(f.message for f in findings)
        assert "thread target" in msg  # the escape reason is named

    def test_same_lock_separate_holds_flagged(self, tmp_path):
        """The PR-9 shape: both halves take the SAME lock, but in two
        holds — held-set intersection would pass it; span tracking
        must not."""
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Gate:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._inflight = 0

                def enter(self):
                    with self._lock:
                        if self._inflight >= 4:
                            return False
                    with self._lock:
                        self._inflight += 1
                    return True

            def serve(g: "Gate"):
                threading.Thread(target=g.enter).start()
        """})
        findings, _ = racelint.check(root)
        hits = [f for f in findings if f.rule == "race-check-then-act"]
        assert hits, "torn same-lock check-then-act not flagged"
        assert "SEPARATE holds" in hits[0].message

    def test_continuous_hold_is_silent(self, tmp_path):
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Gate:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._inflight = 0

                def enter(self):
                    with self._lock:
                        if self._inflight >= 4:
                            return False
                        self._inflight += 1
                    return True

            def serve(g: "Gate"):
                threading.Thread(target=g.enter).start()
        """})
        findings, _ = racelint.check(root)
        assert not findings, findings[:2]

    def test_ctor_and_classmethod_are_silent(self, tmp_path):
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._primed = False
                    if not self._primed:
                        self._primed = True

                @classmethod
                def load(cls):
                    p = cls()
                    if not p._primed:
                        p._primed = True
                    return p

                def run(self):
                    pass

            def spin(p: "Pump"):
                threading.Thread(target=p.run).start()
        """})
        findings, _ = racelint.check(root)
        assert not findings, findings[:2]

    def test_confined_class_is_silent(self, tmp_path):
        """Same torn shape, but the instance never escapes a single
        thread — no finding (escape gate)."""
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Local:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._primed = False

                def prime(self):
                    if not self._primed:
                        self._primed = True

            def run_inline():
                p = Local()
                p.prime()
        """})
        findings, _ = racelint.check(root)
        assert not findings, findings[:2]

    def test_module_global_singleton_escapes(self, tmp_path):
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = {}

                def put(self, k, v):
                    if k not in self._items:
                        self._items[k] = v

            REGISTRY = Registry()
        """})
        findings, _ = racelint.check(root)
        assert any(
            "module-global" in f.message for f in findings
        ), findings[:2]

    def test_locked_helper_idiom_is_silent(self, tmp_path):
        """A method only ever called under the caller's hold runs
        inside one continuous hold — lockorder's guarded fixpoint
        carries over."""
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._free = []

                def _take_locked(self):
                    if self._free:
                        return self._free.pop()
                    return None

                def take(self):
                    with self._lock:
                        return self._take_locked()

            def serve(p: "Pool"):
                threading.Thread(target=p.take).start()
        """})
        findings, _ = racelint.check(root)
        assert not findings, findings[:2]

    def test_suppression_with_reason_silences(self, tmp_path):
        from seaweedfs_tpu.analysis import racelint

        root = _write_pkg(tmp_path, {"mod.py": """
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._primed = False

                def prime(self):
                    if not self._primed:
                        # weedlint: ignore[race-check-then-act] — idempotent flag flip; double prime is a no-op
                        self._primed = True

            def spin(p: "Pump"):
                threading.Thread(target=p.prime).start()
        """})
        findings, index = racelint.check(root)
        kept, suppressed = apply_suppressions(findings, index.sources)
        assert suppressed and not kept


# ---------------------------------------------------------------------------
# stale-suppression audit


class TestStaleSuppressions:
    def test_stale_and_unknown_rule_ignores_become_findings(self):
        from seaweedfs_tpu.analysis import find_stale_suppressions

        sources = {
            "mod.py": (
                "x = 1  # weedlint: ignore[hot-loop-sleep] — was real once\n"
                "y = 2  # weedlint: ignore[hot-loop-lock] — rule never existed\n"
                "z = 3  # weedlint: ignore[hot-loop-sleep] — still live\n"
            )
        }
        live = [Finding("hot-loop-sleep", "mod.py", 3, "m")]
        stale = find_stale_suppressions(live, sources)
        assert sorted(f.line for f in stale) == [1, 2]
        assert all(f.rule == "stale-suppression" for f in stale)

    def test_placeholder_grammar_examples_are_skipped(self):
        from seaweedfs_tpu.analysis import find_stale_suppressions

        sources = {
            "DOC.md": "syntax: `# weedlint: ignore[rule-name] — reason`\n"
        }
        assert find_stale_suppressions([], sources) == []


# ---------------------------------------------------------------------------
# the real tree + CLI


class TestRealTree:
    def test_cli_exits_zero_on_tree(self):
        # --stale-suppressions runs every tier AND the ignore audit in
        # one subprocess: exit 0 proves the tree is finding-free and no
        # suppression has outlived its bug
        proc = subprocess.run(
            [
                sys.executable, "-m", "seaweedfs_tpu.analysis",
                "--stale-suppressions",
            ],
            capture_output=True,
            text=True,
            timeout=300,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_contracts_and_lifecycle_rules_selectable(self):
        """The acceptance-gate invocation: `--rules contracts,lifecycle`
        must run exactly the new tiers and exit clean on this tree."""
        from seaweedfs_tpu.analysis.__main__ import main

        assert main(["--rules", "contracts,lifecycle"]) == 0

    def test_race_rules_selectable_and_clean(self):
        """weedlint v4 acceptance gate: `--rules race` runs the
        shared-state escape lint alone and exits clean on this tree —
        the true positives it found (double-spawn start() in scrub
        engine/repair/tier scheduler, the tier-move cap recheck) are
        fixed, and every deliberate pattern carries a reasoned
        suppression."""
        from seaweedfs_tpu.analysis.__main__ import main

        assert main(["--rules", "race"]) == 0

    def test_crash_rules_selectable_and_clean(self, capsys):
        """weedlint v3 acceptance gate: `--rules crash` runs the
        durability-order tier alone and exits clean on this tree (the
        true positives it found — the commit_compact swap, the scrub
        state publish, the quarantine rename — are fixed, not
        suppressed). `c` must still select only the C tier."""
        import json as _json

        from seaweedfs_tpu.analysis.__main__ import main

        assert main(["--rules", "crash"]) == 0
        out = capsys.readouterr().out
        assert "suppressed" in out  # the fuzz/_build crash ignores ran
        # family-matcher boundary: "c" and "crash" never cross-select
        assert main(["--rules", "c", "--json"]) == 0
        assert "contracts" not in _json.loads(capsys.readouterr().out)

    def test_c_and_contracts_families_do_not_cross_select(self, capsys):
        """Review regression: `--rules c` must run ONLY the C tier —
        "contracts".startswith("c") used to drag the whole contract
        tier (and its package walk) into a C-only run, and vice
        versa. The --json registry dump is the observable: present
        exactly when the contracts tier ran."""
        import json as _json

        from seaweedfs_tpu.analysis.__main__ import main

        assert main(["--rules", "c", "--json"]) == 0
        assert "contracts" not in _json.loads(capsys.readouterr().out)
        assert main(["--rules", "contracts", "--json"]) == 0
        assert "contracts" in _json.loads(capsys.readouterr().out)

    def test_ctier_failure_message_has_no_nameerror(self, monkeypatch):
        """Regression: ctier's compile-failure message referenced an
        undefined `mode` — reachable exactly when a shim FAILS to
        compile, i.e. when the diagnostics matter. Force the failure
        path and assert it formats."""
        from seaweedfs_tpu.analysis import ctier

        monkeypatch.setattr(
            ctier, "_UNITS", (("does_not_exist.c", False),)
        )
        findings = ctier.check_warnings()
        if findings:  # toolchain present: the path must format cleanly
            assert findings[0].rule == "c-warnings"

    def test_full_rule_name_selects_its_family(self, capsys):
        """`--rules hot-loop-no-timeout` must run the hot-loop family
        (regression: the old prefix test selected NOTHING and false-
        greened), and an unknown rule must be an argparse error."""
        from seaweedfs_tpu.analysis.__main__ import main

        assert main(["--rules", "hot-loop-no-timeout"]) == 0
        out = capsys.readouterr().out
        assert "suppressed" in out  # the hot-loop suppressions ran
        with pytest.raises(SystemExit) as exc:
            main(["--rules", "no-such-rule"])
        assert exc.value.code == 2

    def test_gil_release_check_passes(self):
        from seaweedfs_tpu.analysis import ctier

        assert ctier.check_gil_release() == []


# ---------------------------------------------------------------------------
# dynamic witness


class TestWitness:
    def test_inversion_detected_and_clean_order_passes(self):
        """Two locks taken A→B on one thread and B→A on another must
        produce exactly one inversion; consistent order produces none.
        Runs against the installed witness when tier-1 has it on,
        else installs locally."""
        from seaweedfs_tpu.analysis import witness

        installed_here = not witness._installed
        if installed_here:
            witness.install()
        try:
            la = threading.Lock()
            lb = threading.Lock()
            if not isinstance(la, witness._WitnessLock):
                pytest.skip("witness not active (WEED_LOCK_WITNESS=0)")
            before = len(witness.inversions())
            with la:
                with lb:
                    pass
            assert len(witness.inversions()) == before  # consistent

            def invert():
                with lb:
                    with la:
                        pass

            t = threading.Thread(target=invert)
            t.start()
            t.join()
            found = witness.inversions()[before:]
            assert len(found) == 1
            assert "test_weedlint.py" in found[0]["acquiring"]
            # consume the planted inversion so the autouse tier-1
            # witness fixture doesn't fail THIS test for it
            with witness._state_lock:
                del witness._inversions[before:]
            # and unwind the planted edges so later tests that take
            # these site-locks in either order stay clean
            with witness._state_lock:
                for k in list(witness._edges):
                    if "test_weedlint.py" in k:
                        del witness._edges[k]
        finally:
            if installed_here:
                witness.uninstall()

    def test_condition_keeps_held_stack_honest(self):
        from seaweedfs_tpu.analysis import witness

        installed_here = not witness._installed
        if installed_here:
            witness.install()
        try:
            lk = threading.Lock()
            if not isinstance(lk, witness._WitnessLock):
                pytest.skip("witness not active (WEED_LOCK_WITNESS=0)")
            cond = threading.Condition(lk)
            hits = []

            def waiter():
                with cond:
                    cond.wait(timeout=5)
                    hits.append(len(witness._held()))

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.05)
            with cond:
                cond.notify()
            t.join()
            # inside the with after wakeup exactly the cv lock is held
            assert hits == [1]
            assert not witness._held()  # this thread released cleanly
        finally:
            if installed_here:
                witness.uninstall()
