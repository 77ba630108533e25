"""weedrace v4 (docs/ANALYSIS.md): the dynamic schedule enumerator,
the shm GCRA model check, and the cross-process SIGKILL sweep over the
real mmap'd admission bucket.

The proof structure mirrors weedcrash's: every fixed unit must hold
its invariant under the explored schedules (negative controls), and
the pre-fix PR-9 / PR-15 orderings replayed as planted-bug arms must
be DETECTED (positive controls) — an enumerator that cannot re-find
the tree's own historical races certifies nothing. The GCRA check is
exhaustive for 2 workers (every load/CAS interleaving including
SIGKILL-mid-update arms), and the sweep at the bottom runs the same
kill against the REAL serve.c bucket across live sibling processes.
"""

from __future__ import annotations

import os
import signal
import struct
import subprocess
import sys
import time

import pytest

from seaweedfs_tpu.analysis import race
from seaweedfs_tpu.util import native_serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# dynamic enumerator: fixed arms hold, planted arms detected


class TestFixedUnits:
    """Every concurrency unit's stated invariant must survive the
    schedule budget — these are the shapes the tree actually ships
    (AdmissionController, TileCache, GroupCommitter, gather_first_k,
    HandoffAgent, SingleFlight)."""

    @pytest.mark.parametrize("unit", sorted(race.ALL_UNITS))
    def test_invariant_holds_under_schedules(self, unit):
        rep = race.ALL_UNITS[unit](budget=15, seed=0)
        assert rep.violations == [], (
            f"{unit}: {rep.violations[:2]} after {rep.schedules_run} "
            f"schedules"
        )
        assert rep.schedules_run > 0
        # the scheduler must actually have interleaved something — a
        # run with zero decision points explored exactly one ordering
        # and proves nothing
        assert rep.decision_points > 0, (
            f"{unit}: no scheduling decisions taken "
            f"({rep.schedules_run} schedules ran free)"
        )

    def test_report_shape(self):
        rep = race.run_admission(budget=6, seed=0)
        d = rep.to_dict()
        assert d["unit"] == "admission"
        assert d["schedules_run"] <= 6
        assert isinstance(d["violations"], list)


class TestPlantedArms:
    """The regression arms: pre-fix orderings out of the tree's own
    git history, replayed through the enumerator."""

    def test_pr9_admission_ordering_detected(self):
        # check under one lock hold, count under a later one — the
        # burst that breached the in-flight cap in PR 9
        rep = race.run_admission(budget=64, seed=0, pre_fix=True)
        assert any("cap breached" in v for v in rep.violations), (
            f"pre-fix admission survived {rep.schedules_run} schedules"
        )
        # every violation carries its replay token
        assert all(v.startswith("[") for v in rep.violations)

    def test_pr15_handoff_ordering_detected(self):
        # remove-then-count: the agent that unlinked the hint before
        # counting it, leaving a window where the spool looks empty
        # with nothing counted yet
        rep = race.run_handoff(budget=72, seed=0, pre_fix=True)
        assert rep.violations, (
            f"pre-fix handoff survived {rep.schedules_run} schedules"
        )

    def test_pr12_tile_cache_ordering_detected(self):
        # generation check outside the insert's lock hold: an
        # invalidation between them leaves a stale tile resident
        rep = race.run_tile_cache(budget=32, seed=0, pre_fix=True)
        assert any("stale" in v for v in rep.violations), (
            f"pre-fix tile cache survived {rep.schedules_run} schedules"
        )


class TestKnobs:
    def test_budget_and_seed_env(self, monkeypatch):
        monkeypatch.setenv("WEED_RACE_BUDGET", "7")
        monkeypatch.setenv("WEED_RACE_SEED", "3")
        assert race.budget_default() == 7
        assert race.seed_default() == 3
        rep = race.run_admission()
        assert rep.schedules_run <= 7

    def test_garbage_env_falls_back(self, monkeypatch):
        monkeypatch.setenv("WEED_RACE_BUDGET", "plenty")
        assert race.budget_default() == 64


# ---------------------------------------------------------------------------
# shm GCRA model check


class TestGcraModelCheck:
    def test_two_workers_exhaustive_with_kill_arms(self):
        rep = race.model_check_gcra(
            workers=2, attempts_per_worker=2, budget=20000
        )
        assert not rep.truncated, "2-worker space must enumerate fully"
        assert rep.violations == []
        # burst=2.0 at one instant: EXACTLY two tokens exist, and every
        # interleaving (including every SIGKILL placement) grants both
        assert (rep.admitted_min, rep.admitted_max) == (2, 2)
        assert rep.interleavings > 1000

    def test_kill_arms_enlarge_the_space(self):
        base = race.model_check_gcra(
            workers=2, attempts_per_worker=2, budget=20000, kill_arm=False
        )
        with_kill = race.model_check_gcra(
            workers=2, attempts_per_worker=2, budget=20000
        )
        assert with_kill.interleavings > base.interleavings
        assert base.violations == []

    def test_three_workers_clean(self):
        rep = race.model_check_gcra(
            workers=3, attempts_per_worker=1, budget=20000
        )
        assert rep.violations == []
        assert (rep.admitted_min, rep.admitted_max) == (2, 2)

    def test_blind_store_double_spends(self):
        # the planted arm: replace the CAS with a plain store and the
        # model check must observe a double-spend — this is the bug
        # class the shm-atomics ctier rule guards serve.c against
        rep = race.model_check_gcra(
            workers=2, attempts_per_worker=2,
            blind_store=True, kill_arm=False,
        )
        assert any("double-spend" in v for v in rep.violations)

    def test_shm_atomics_rule_flags_a_plain_store_mutant(self):
        """The ctier rule that guards serve.c's GCRA slot sees the same
        bug class in C: a plain-store mutant of weed_shm_admit's CAS is
        flagged, and the shipped serve.c is clean."""
        from seaweedfs_tpu.analysis import ctier

        with open(os.path.join(ctier._NATIVE_DIR, "serve.c"), encoding="utf-8") as f:
            src = f.read()
        mutant = src.replace(
            "if (__atomic_compare_exchange_n(slot, &tat, base + T, 0,",
            "if ((*slot = base + T) && (0,", 1,
        )
        assert mutant != src, "the CAS this control mutates moved"
        assert ctier.check_shm_atomics(source=mutant)
        assert not ctier.check_shm_atomics(source=src)


# ---------------------------------------------------------------------------
# the real bucket: SIGKILL a sibling mid-update (weedcrash idiom)

_needs_shm = pytest.mark.skipif(
    not native_serve.available(),
    reason="native serve extension (shm bucket) unavailable",
)

_CHILD = """\
import sys, time
from seaweedfs_tpu.util import native_serve as ns
path, rate, burst, dur = sys.argv[1], float(sys.argv[2]), \
    float(sys.argv[3]), float(sys.argv[4])
ns.admission_shm_attach(path, rate, burst, 0.0)
print("up", flush=True)
t0 = time.monotonic()
n = 0
while time.monotonic() - t0 < dur:
    if ns.admission_shm_admit("tenant") == 0.0:
        n += 1
    time.sleep(0.001)
print(n, flush=True)
"""


@_needs_shm
class TestShmSigkillSweep:
    def _spawn(self, path: str, rate: float, burst: float, dur: float):
        return subprocess.Popen(
            [sys.executable, "-c", _CHILD, path, str(rate), str(burst),
             str(dur)],
            cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE,
            text=True,
        )

    def test_sigkill_mid_update_survivors_keep_budget(self, tmp_path):
        """Three siblings hammer one bucket; one dies by SIGKILL
        mid-loop. Survivors must neither wedge nor overrun the GLOBAL
        budget, and a fresh process must attach the same file and get
        a sane bucket afterwards (no corrupt state inherited)."""
        shm = str(tmp_path / "adm.tb")
        rate, burst, dur = 50.0, 10.0, 1.2
        t0 = time.monotonic()
        procs = [self._spawn(shm, rate, burst, dur) for _ in range(3)]
        try:
            for p in procs:  # all attached and admitting
                assert p.stdout.readline().strip() == "up"
            time.sleep(0.3)
            victim = procs[0]
            victim.kill()  # SIGKILL: no atexit, no detach, no unlock
            victim.wait(timeout=10)
            counts = []
            for p in procs[1:]:
                out, _ = p.communicate(timeout=30)
                assert p.returncode == 0, "survivor wedged or crashed"
                counts.append(int(out.strip().splitlines()[-1]))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        elapsed = time.monotonic() - t0
        budget = burst + rate * elapsed
        # the victim's pre-death admits also drew real tokens, so the
        # survivors alone must land under the whole-bucket cap
        assert sum(counts) <= 1.1 * budget + 1, (
            f"survivors admitted {sum(counts)} of a {budget:.1f} budget "
            f"— the killed sibling's state leaked tokens back"
        )
        assert all(c > 0 for c in counts), (
            f"a survivor starved entirely ({counts}) — wedged bucket"
        )
        # recovery arm: a clean successor attaches the same file and a
        # NEW tenant still gets its exact burst
        probe = subprocess.run(
            [sys.executable, "-c",
             "import sys\n"
             "from seaweedfs_tpu.util import native_serve as ns\n"
             f"ns.admission_shm_attach({shm!r}, {rate}, {burst}, 0.0)\n"
             "print(sum(1 for _ in range(40)"
             " if ns.admission_shm_admit('fresh-tenant') == 0.0))\n"],
            cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120,
        )
        assert probe.returncode == 0, probe.stderr[-2000:]
        assert int(probe.stdout.strip()) == int(burst), (
            "successor did not inherit a sane bucket"
        )

    def test_torn_header_rejected_not_inherited(self, tmp_path):
        """The torn-state arm: a corrupted header (bad magic) must be
        REJECTED at attach — never silently mapped as a budget."""
        shm = str(tmp_path / "adm.tb")
        init = subprocess.run(
            [sys.executable, "-c",
             "from seaweedfs_tpu.util import native_serve as ns\n"
             f"ns.admission_shm_attach({shm!r}, 50.0, 10.0, 0.0)\n"
             "ns.admission_shm_admit('t')\n"],
            cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120,
        )
        assert init.returncode == 0, init.stderr[-2000:]
        with open(shm, "r+b") as f:  # scribble the magic
            f.write(struct.pack("<Q", 0xDEADBEEF))
        probe = subprocess.run(
            [sys.executable, "-c",
             "from seaweedfs_tpu.util import native_serve as ns\n"
             "try:\n"
             f"    ns.admission_shm_attach({shm!r}, 50.0, 10.0, 0.0)\n"
             "except OSError:\n"
             "    print('rejected')\n"
             "else:\n"
             "    print('accepted')\n"],
            cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120,
        )
        assert probe.returncode == 0, probe.stderr[-2000:]
        assert probe.stdout.strip() == "rejected", (
            "corrupt bucket header was silently accepted"
        )
