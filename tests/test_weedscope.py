"""weedscope plane tests (ISSUE-20, docs/TELEMETRY.md + docs/TRACING.md).

Units: the SLO burn-rate engine's multi-window math (availability
excluding client-attributable 503/504, latency from pooled bucket
increases, plane filtering), the flapping-suppression and resolve-
hysteresis state machine, bounded AlertManager history, the on_fire
edge hook, the blackbox flight recorder's tail-biased retention, the
exemplar render/parse round trip, incident-capsule durability and the
/capsule HTTP surface's path-traversal guard, and the collector's
dead-node TTL (the PR-14 NodeHealth prune, mirrored for scrape
targets).
"""

from __future__ import annotations

import json
import os
import time

from seaweedfs_tpu.telemetry import slo as slo_mod
from seaweedfs_tpu.telemetry.alerts import AlertManager, AlertRule
from seaweedfs_tpu.telemetry.ring import TargetStore

# ----------------------------------------------------------------------
# SLO engine: measurement math against a real TargetStore


def _scrape(ts: TargetStore, t: float, rows):
    """rows: [(name, {labels}, value), ...]"""
    ts.record_scrape(
        [(n, tuple(sorted(labels.items())), v) for n, labels, v in rows], t
    )


class TestSLOMeasurement:
    def test_availability_excludes_client_attributable_5xx(self):
        ts = TargetStore("n1:8080", "volume")
        fam = "weed_http_request_total"
        _scrape(ts, 100.0, [
            (fam, {"status": "200"}, 100.0),
            (fam, {"status": "503"}, 50.0),
            (fam, {"status": "500"}, 0.0),
        ])
        _scrape(ts, 160.0, [
            (fam, {"status": "200"}, 200.0),
            (fam, {"status": "503"}, 150.0),
            (fam, {"status": "500"}, 1.0),
        ])
        obj = slo_mod.SLOObjective("avail", "availability", 0.999, family=fam)
        eng = slo_mod.SLOEngine(objectives=[obj], fast_s=100.0, slow_s=100.0)
        bad, total = eng._bad_total(obj, [ts], 100.0, 170.0)
        # 503 is shed (client-attributable, docs/HEALTH.md): only the
        # one 500 burns the budget; the 100 shed requests still count
        # toward total served
        assert bad == 1.0
        assert total == 201.0

    def test_latency_counts_bad_above_threshold_bucket(self):
        ts = TargetStore("n1:8080", "volume")
        b = "weed_http_request_seconds_bucket"
        _scrape(ts, 100.0, [
            (b, {"le": "0.1"}, 10.0),
            (b, {"le": "1.0"}, 10.0),
            (b, {"le": "+Inf"}, 10.0),
        ])
        _scrape(ts, 160.0, [
            (b, {"le": "0.1"}, 10.0),
            (b, {"le": "1.0"}, 15.0),
            (b, {"le": "+Inf"}, 20.0),
        ])
        obj = slo_mod.SLOObjective(
            "lat", "latency", 0.99,
            family="weed_http_request_seconds", threshold_s=0.5,
        )
        eng = slo_mod.SLOEngine(objectives=[obj])
        # threshold 0.5 falls between buckets: judged at the 1.0 bound
        # (conservative). good = +5 at le=1.0, total = +10 → 5 bad.
        bad, total = eng._bad_total(obj, [ts], 100.0, 170.0)
        assert (bad, total) == (5.0, 10.0)

    def test_latency_plane_filter(self):
        ts = TargetStore("n1:8080", "volume")
        b = "weed_span_seconds_bucket"
        _scrape(ts, 100.0, [
            (b, {"le": "+Inf", "plane": "serve"}, 0.0),
            (b, {"le": "+Inf", "plane": "scrub"}, 0.0),
        ])
        _scrape(ts, 160.0, [
            (b, {"le": "+Inf", "plane": "serve"}, 100.0),
            (b, {"le": "+Inf", "plane": "scrub"}, 7.0),
        ])
        obj = slo_mod.SLOObjective(
            "scrub-lat", "latency", 0.95, plane="scrub",
            family="weed_span_seconds", threshold_s=3.0,
        )
        eng = slo_mod.SLOEngine(objectives=[obj])
        pooled = eng._pooled_buckets(obj, [ts], 100.0, 170.0)
        assert pooled[float("inf")] == 7.0  # serve-plane buckets excluded


# ----------------------------------------------------------------------
# SLO engine: multi-window state machine (stub targets drive exact burns)


class _StubTarget:
    """increase_sum-level stub: (bad, total) per window size, so tests
    dial in exact fast/slow burn rates without fabricating rings."""

    kind = "volume"

    def __init__(self, by_window):
        self.by_window = by_window  # {window_s: (bad, total)}

    def increase_sum(self, name, window_s, now=None, label_filter=None):
        bad, total = self.by_window[window_s]
        return bad if label_filter is not None else total

    def bucket_increases(self, family, window_s, now=None, label_filter=None):
        return {}


_AVAIL = slo_mod.SLOObjective(
    "avail", "availability", 0.9, family="weed_http_request_total"
)


def _engine():
    return slo_mod.SLOEngine(
        objectives=[_AVAIL], fast_s=60.0, slow_s=600.0,
        burn_threshold=1.0, resolve_factor=0.5,
    )


def _active(conds):
    [(rule, target, active, _v, _d)] = conds
    assert rule is slo_mod.RULE_SLO_BURN and target == "avail"
    return active


class TestSLOBurnStateMachine:
    def test_fast_only_burst_does_not_fire(self):
        eng = _engine()
        # fast window: 10 bad of 20 → burn 5x. slow window: the same 10
        # bad diluted by 10k requests → burn 0.01x. Multi-window says:
        # this burst never endangers the budget — do not page.
        tgt = _StubTarget({60.0: (10.0, 20.0), 600.0: (10.0, 10000.0)})
        assert not _active(eng.evaluate([tgt], now=1000.0))
        assert eng.payload()["Breaching"] == []

    def test_both_windows_burning_fires_and_exports_gauges(self):
        from seaweedfs_tpu.stats.metrics import (
            SLO_BUDGET_REMAINING, SLO_BURN_RATE,
        )

        eng = _engine()
        tgt = _StubTarget({60.0: (10.0, 20.0), 600.0: (300.0, 1000.0)})
        assert _active(eng.evaluate([tgt], now=1000.0))
        assert eng.payload()["Breaching"] == ["avail"]
        assert SLO_BURN_RATE.value("avail", "fast") == 5.0
        assert SLO_BURN_RATE.value("avail", "slow") == 3.0
        assert SLO_BUDGET_REMAINING.value("avail") == 0.0

    def test_resolve_hysteresis(self):
        eng = _engine()
        burning = _StubTarget({60.0: (10.0, 20.0), 600.0: (300.0, 1000.0)})
        assert _active(eng.evaluate([burning], now=1000.0))
        # cooled below the threshold but not below threshold×0.5:
        # a burn oscillating around 1.0x must not flap the alert
        warm = _StubTarget({60.0: (8.0, 100.0), 600.0: (10.0, 10000.0)})
        assert _active(eng.evaluate([warm], now=1060.0))
        # only a real cool-down (fast burn < 0.5x) resolves
        cold = _StubTarget({60.0: (1.0, 100.0), 600.0: (10.0, 10000.0)})
        assert not _active(eng.evaluate([cold], now=1120.0))
        assert eng.payload()["Breaching"] == []
        # and the warm level does NOT re-fire from the resolved state
        assert not _active(eng.evaluate([warm], now=1180.0))


# ----------------------------------------------------------------------
# AlertManager: on_fire edge hook + bounded history


class TestAlertManagerScope:
    def test_on_fire_fires_only_on_edge(self):
        rows = []
        rule = AlertRule("edge", "critical", for_s=0.0)
        mgr = AlertManager(on_fire=rows.append)
        mgr.evaluate([(rule, "t1", True, 1.0, "d")], now=10.0)
        assert len(rows) == 1 and rows[0]["Alert"] == "edge"
        # still firing: no second invocation
        mgr.evaluate([(rule, "t1", True, 2.0, "d")], now=11.0)
        assert len(rows) == 1
        mgr.evaluate([(rule, "t1", False, 0.0, "")], now=12.0)
        mgr.evaluate([(rule, "t1", True, 3.0, "d")], now=13.0)
        assert len(rows) == 2  # re-fire after resolve is a new edge

    def test_on_fire_exception_never_breaks_evaluation(self):
        rule = AlertRule("boom", for_s=0.0)

        def hook(_row):
            raise RuntimeError("capture exploded")

        mgr = AlertManager(on_fire=hook)
        mgr.evaluate([(rule, "t1", True, 1.0, "d")], now=10.0)
        assert mgr.firing()  # state machine advanced despite the hook

    def test_history_stays_bounded_under_flapping(self):
        rule = AlertRule("flappy", for_s=0.0)
        mgr = AlertManager()
        for i in range(200):
            mgr.evaluate([(rule, "t1", True, 1.0, "d")], now=float(i))
            mgr.evaluate([(rule, "t1", False, 0.0, "")], now=i + 0.5)
        assert len(mgr._history) <= 128
        assert len(mgr.payload()["History"]) <= 32
        # gauge row removed, not zeroed, once resolved
        from seaweedfs_tpu.stats.metrics import ALERT_FIRING

        assert ("flappy", "t1") not in ALERT_FIRING._values


# ----------------------------------------------------------------------
# blackbox flight recorder: tail-biased retention


class TestBlackboxRetention:
    def test_tail_bias_and_ok_sampling(self):
        from seaweedfs_tpu.trace import blackbox

        blackbox.reset()
        rec = blackbox.recorder("test", "n1")
        ok_every = blackbox.snapshot(0)["ok_every"]
        n_ok = 2 * ok_every
        for _ in range(n_ok):
            rec("GET", "", "serve", 200, 0.001, 10, "p", 0, None)
        rec("GET", "t-err", "serve", 404, 0.001, 0, "p", 0, None)
        rec("GET", "t-slow", "serve", 200, 0.5, 10, "p", 0, None)
        rec(
            "GET", "t-retry", "serve", 200, 0.001, 10, "p",
            blackbox.FLAG_RETRY, None,
        )
        snap = blackbox.snapshot(64)
        # every error/slow/flagged record survives; OKs are 1-in-N
        # (any 2N consecutive draws win exactly twice)
        assert snap["tail_recorded"] == 3
        assert snap["ok_recorded"] == 2
        by_trace = {r["trace"]: r for r in snap["tail"]}
        assert by_trace["t-err"]["status"] == 404
        assert by_trace["t-slow"]["dur_ms"] == 500.0
        assert by_trace["t-retry"]["flags"] == ["retry"]
        assert all(r["name"] == "test.GET" for r in snap["tail"])

    def test_kill_switch_drops_records(self):
        from seaweedfs_tpu.trace import blackbox

        blackbox.reset()
        rec = blackbox.recorder("test", "n1")
        blackbox.set_enabled(False)
        try:
            rec("GET", "t", "serve", 500, 1.0, 0, "p", 0, None)
            snap = blackbox.snapshot(8)
            assert snap["enabled"] is False
            assert snap["tail_recorded"] == 0
        finally:
            blackbox.set_enabled(True)

    def test_stage_dict_rides_the_record(self):
        from seaweedfs_tpu.trace import blackbox

        blackbox.reset()
        rec = blackbox.recorder("volume", "n1")
        rec(
            "GET", "tid", "serve", 404, 0.2, 0, "p", 0,
            {"parse": 0.001, "resolve": 0.002, "send": 0.003},
        )
        [row] = blackbox.snapshot(8)["tail"]
        assert set(row["stages_ms"]) == {"parse", "resolve", "send"}

    def test_request_flags(self):
        from seaweedfs_tpu.trace import blackbox

        f = blackbox.request_flags({"x-weed-retry": "1"}, 200)
        assert f == blackbox.FLAG_RETRY
        f = blackbox.request_flags({"x-weed-hedge": "1"}, 503)
        assert f == blackbox.FLAG_HEDGE | blackbox.FLAG_SHED
        assert blackbox.request_flags({}, 504) == blackbox.FLAG_DEADLINE


# ----------------------------------------------------------------------
# exemplars: render + parse round trip


class TestExemplars:
    def test_render_and_parse_round_trip(self):
        from seaweedfs_tpu.stats import metrics as metrics_mod
        from seaweedfs_tpu.telemetry.parse import parse_prometheus_text

        reg = metrics_mod.Registry()
        hist = reg.histogram("x_seconds", "h", ("k",), buckets=(0.1, 1.0))
        hist.observe(0.05, "a")
        hist.put_exemplar(0.05, "traceabc", "a")
        text = reg.render_text()
        assert '# {trace_id="traceabc"}' in text
        samples = parse_prometheus_text(text)
        buckets = {
            dict(lt)["le"]: v
            for n, lt, v in samples
            if n == "x_seconds_bucket"
        }
        # exemplar suffix must not perturb the parsed sample values
        assert buckets == {"0.1": 1.0, "1.0": 1.0, "+Inf": 1.0}

    def test_kill_switch_reverts_to_plain_exposition(self):
        from seaweedfs_tpu.stats import metrics as metrics_mod

        reg = metrics_mod.Registry()
        hist = reg.histogram("y_seconds", "h", (), buckets=(1.0,))
        hist.observe(0.5)
        hist.put_exemplar(0.5, "tid")
        metrics_mod.set_exemplars_enabled(False)
        try:
            assert "trace_id" not in reg.render_text()
        finally:
            metrics_mod.set_exemplars_enabled(True)
        assert "trace_id" in reg.render_text()


# ----------------------------------------------------------------------
# incident capsules: durability, retention, traversal guard


class TestCapsules:
    def test_capture_is_durable_and_manifest_complete(self, tmp_path):
        from seaweedfs_tpu.telemetry import capsule

        man = capsule.capture("unit test!", node="n1:80", root=str(tmp_path))
        assert man["Node"] == "n1:80" and man["Trigger"] == "manual"
        cap_dir = tmp_path / man["Id"]
        assert (cap_dir / "MANIFEST.json").exists()
        names = {f["Name"] for f in man["Files"]}
        assert {
            "blackbox.json", "traces.json", "profile.txt", "metrics.txt"
        } <= names
        for f in man["Files"]:
            if f["Ok"]:
                assert (cap_dir / f["Name"]).exists()
        # the published manifest round-trips through list + read_file
        [listed] = [
            c for c in capsule.list_capsules(root=str(tmp_path))
            if c["Id"] == man["Id"]
        ]
        assert listed == json.loads(
            capsule.read_file(man["Id"], "MANIFEST.json", root=str(tmp_path))
        )

    def test_read_file_blocks_path_traversal(self, tmp_path):
        from seaweedfs_tpu.telemetry import capsule

        man = capsule.capture("guard", root=str(tmp_path))
        root = str(tmp_path)
        assert capsule.read_file("../evil", "x", root=root) is None
        assert capsule.read_file("no/slash", "x", root=root) is None
        assert capsule.read_file(man["Id"], "../MANIFEST.json", root=root) is None
        assert capsule.read_file(man["Id"], ".hidden", root=root) is None
        assert capsule.read_file(man["Id"], "MANIFEST.json", root=root)

    def test_retention_keeps_newest_and_prunes_stale_partials(self, tmp_path):
        from seaweedfs_tpu.telemetry import capsule

        root = str(tmp_path)
        # a crash partial: id-shaped dir, no manifest, older than 1 h
        partial = tmp_path / "1000000000000-0-crashed"
        partial.mkdir()
        os.utime(partial, (time.time() - 7200, time.time() - 7200))
        ids = [
            capsule.capture(f"cap{i}", root=root)["Id"]
            for i in range(capsule._KEEP + 3)
        ]
        kept = [c["Id"] for c in capsule.list_capsules(root=root)]
        assert len(kept) == capsule._KEEP
        assert kept == ids[-capsule._KEEP:]  # newest win, oldest pruned
        assert not partial.exists()

    def test_autocapture_cooldown(self):
        from seaweedfs_tpu.telemetry import capsule

        key = "unit-cooldown-key"
        assert capsule.should_autocapture(key, now=5000.0)
        assert not capsule.should_autocapture(key, now=5001.0)
        assert capsule.should_autocapture(
            key, now=5001.0 + capsule._COOLDOWN_S
        )

    def test_coordinator_respects_kill_switch(self):
        from seaweedfs_tpu.telemetry import capsule

        calls = []
        coord = capsule.CaptureCoordinator(
            node="n1", peers_fn=lambda row: calls.append(row),
            enabled_fn=lambda: False,
        )
        coord({"Alert": "a", "Target": "t"})
        assert calls == []  # WEED_SCOPE=0: no auto-capture side effects


class TestCapsuleOnALiveCluster:
    def test_forced_slo_breach_captures_on_every_node(self, tmp_path):
        """The SLO burn-rate rule fires on a live cluster and the
        alert-triggered capsule lands durably on every implicated node:
        the leader's manifest lists blackbox, traces, profile, /metrics,
        the TSDB window and the cluster verdict. The breach is forced:
        the in-process cluster shares this process's metric registry,
        so one 10 s observation between two scrape cycles burns both
        windows of a seconds-scale latency objective."""
        import urllib.request

        from seaweedfs_tpu.stats.metrics import HTTP_REQUEST_HISTOGRAM
        from seaweedfs_tpu.telemetry import ClusterCollector
        from seaweedfs_tpu.telemetry import capsule as capsule_mod
        from seaweedfs_tpu.util.availability import start_cluster

        (tmp_path / "vol").mkdir()
        capsule_mod.set_dir(str(tmp_path / "capsules"))
        master, servers = start_cluster([str(tmp_path / "vol")])
        lead_node = f"{master.host}:{master.port}"
        try:
            forced = slo_mod.SLOObjective(
                "check-forced-breach", "latency", 0.999,
                family="weed_http_request_seconds", threshold_s=0.5,
            )
            collector = ClusterCollector(
                master, interval=0.5,
                slo_objectives=[forced], slo_fast_s=30.0, slo_slow_s=60.0,
            )
            master.telemetry = collector
            master._wire_capsules()
            # light real traffic so the blackbox/trace sections have events
            with urllib.request.urlopen(
                f"http://127.0.0.1:{servers[0].port}/debug/traces?n=8",
                timeout=10,
            ) as r:
                r.read()
            # cycle 1's own /metrics GET births the request-histogram
            # series; cycle 2 rings their baseline; the slow observation
            # then shows as an increase in cycle 3 and fires
            collector.collect_once()
            collector.collect_once()
            HTTP_REQUEST_HISTOGRAM.observe(10.0, "volume", "GET")
            collector.collect_once()
            assert any(
                a["Alert"] == "slo_burn_rate"
                and a["Target"] == "check-forced-breach"
                for a in collector.alerts.firing()
            )
            # the CaptureCoordinator runs off-thread: a local capture on
            # the leader plus /capsule/capture on every up peer
            caps: list[dict] = []
            deadline = time.time() + 20.0
            while time.time() < deadline:
                caps = [
                    c for c in capsule_mod.list_capsules()
                    if c.get("Trigger") == "alert"
                ]
                if len({c.get("Node") for c in caps}) >= 2:
                    break
                time.sleep(0.25)
            assert len({c.get("Node") for c in caps}) >= 2, caps
            [lead] = [c for c in caps if c.get("Node") == lead_node]
            ok_names = {f["Name"] for f in lead["Files"] if f.get("Ok")}
            assert {
                "blackbox.json", "traces.json", "profile.txt",
                "metrics.txt", "tsdb.json", "cluster.json",
            } <= ok_names
            bb = json.loads(
                capsule_mod.read_file(lead["Id"], "blackbox.json") or b"{}"
            )
            assert bb.get("tail") or bb.get("ok")
            mtxt = capsule_mod.read_file(lead["Id"], "metrics.txt") or b""
            assert "weed_slo_burn_rate" in mtxt.decode()
            tsdb = json.loads(
                capsule_mod.read_file(lead["Id"], "tsdb.json") or b"{}"
            )
            assert tsdb.get("Targets")
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
            capsule_mod.set_dir("")


# ----------------------------------------------------------------------
# collector: sticky scrape targets with a dead-node TTL (satellite 1)


class _StubTopology:
    @staticmethod
    def data_nodes():
        return []


class _StubMaster:
    host, port = "127.0.0.1", 1
    is_leader = True
    repair = None
    topology = _StubTopology()

    @staticmethod
    def gateway_registrations():
        return {}


class TestDeadNodeTTL:
    def _collector(self):
        from seaweedfs_tpu.telemetry.collector import ClusterCollector

        # floor: forget_after = stale_after + 2×interval = 5 s, so the
        # staleness alert always fires before the target is forgotten
        return ClusterCollector(_StubMaster(), interval=1.0, forget_after=0.0)

    def test_forget_after_floored_above_staleness_grace(self):
        c = self._collector()
        assert c.forget_after >= c.stale_after + 2.0 * c.interval

    def test_stale_target_alerts_first_then_is_forgotten(self):
        from seaweedfs_tpu.stats.metrics import SCRAPE_STALENESS, SCRAPE_UP

        c = self._collector()
        url = "10.9.9.9:8080"
        now = time.time()
        ts = TargetStore(url, "volume")
        ts.last_success = now - (c.stale_after + 0.5)  # stale, not dead
        c.targets[url] = ts
        SCRAPE_UP.set(0.0, url)
        SCRAPE_STALENESS.set(99.0, url)
        c._discover()
        assert url in c.targets  # sticky: absent from topology but kept
        c._evaluate(list(c.targets.values()), now)
        assert any(
            a["Alert"] == "scrape_staleness" and a["Target"] == url
            for a in c.alerts.firing()
        )
        # past the TTL: forgotten, gauge rows removed (not zeroed)
        ts.last_success = now - (c.forget_after + 0.5)
        c._discover()
        assert url not in c.targets
        assert (url,) not in SCRAPE_UP._values
        assert (url,) not in SCRAPE_STALENESS._values
        # the vanished rule×target pair auto-resolves next cycle
        c._evaluate(list(c.targets.values()), now)
        assert not any(a["Target"] == url for a in c.alerts.firing())

    def test_discovered_target_never_forgotten(self):
        c = self._collector()
        url = f"{_StubMaster.host}:{_StubMaster.port}"  # always discovered
        c._discover()
        ts = c.targets[url]
        ts.first_seen = time.time() - 10_000.0  # ancient and never up
        c._discover()
        assert url in c.targets
