"""weedlint CLI: `python -m seaweedfs_tpu.analysis`.

Runs every checker over the package tree and exits 0 only when the
tree is clean (no unsuppressed findings — and no suppression missing
its mandatory reason). tests/test_weedlint.py runs it in tier-1;
docs/ANALYSIS.md is the catalog.

    python -m seaweedfs_tpu.analysis                   # all checkers
    python -m seaweedfs_tpu.analysis --rules contracts,lifecycle
    python -m seaweedfs_tpu.analysis --json            # machine-readable
    python -m seaweedfs_tpu.analysis --fuzz 200        # + fuzz smoke
    python -m seaweedfs_tpu.analysis --stale-suppressions  # audit ignores
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from seaweedfs_tpu.analysis import (
    Finding,
    apply_suppressions,
    find_stale_suppressions,
)

# rule families, in the order they run; --rules filters by prefix,
# e.g. `--rules lock-order`. lock-order and unguarded-write are
# separate families that share one index walk — selecting either
# runs the walk once and keeps only the selected family's findings
_FAMILIES = {
    "lock-order": (
        "static lock-acquisition graph: cycles are deadlock candidates"
    ),
    "unguarded-write": (
        "writes to lock-guarded attributes reached without the guard"
    ),
    "hot-loop": (
        "blocking calls (sleep/subprocess/deadline-less IO) reachable "
        "from the FastHandler dispatch tree"
    ),
    "c": (
        "C shim tier: -Wall -Wextra -Werror compile + structural "
        "Py_BEGIN_ALLOW_THREADS checks"
    ),
    "contracts": (
        "cross-component string contracts: served routes vs client "
        "paths, registered vs referenced metrics, stamped vs parsed "
        "headers, fast_reply statuses vs _REASON, WEED_* env vars and "
        "CLI flags vs docs"
    ),
    "lifecycle": (
        "fd/socket/thread acquire-release pairing: early-return leaks, "
        "started-never-joined threads (interprocedural, owns[] aware)"
    ),
    "crash": (
        "crash-consistency durability ordering: write-then-rename "
        "without fsync of file + parent dir, fsync-after-close, .idx "
        "publish before its .dat write, unflushed os.replace sources, "
        "recovery-critical state mutated outside atomic publish"
    ),
    "race": (
        "shared-state escape lint: check-then-act on attributes of "
        "objects that escape to another thread (Thread targets/args, "
        "pool submits, module-global singletons) where check and act "
        "share no continuous lock hold — two separate holds of the "
        "SAME lock count as torn"
    ),
}


def main(argv: list[str] | None = None) -> int:
    tier_help = "; ".join(f"{k}: {v}" for k, v in _FAMILIES.items())
    ap = argparse.ArgumentParser(
        prog="python -m seaweedfs_tpu.analysis",
        description="weedlint — the repo-native static-analysis plane "
        "(docs/ANALYSIS.md). Tiers: " + tier_help,
    )
    ap.add_argument(
        "--rules",
        default="",
        help="comma-separated tier prefixes to run (default: all of "
        + ", ".join(_FAMILIES) + ")",
    )
    ap.add_argument(
        "--json", action="store_true", help="machine-readable output "
        "(includes the contract registries when the contracts tier runs)"
    )
    ap.add_argument(
        "--fuzz",
        type=int,
        default=0,
        metavar="N",
        help="also run N iterations of the C-vs-Python POST fuzzer",
    )
    ap.add_argument(
        "--stale-suppressions",
        action="store_true",
        help="audit mode: run every tier, then report each "
        "`# weedlint: ignore[...]` whose rule no longer fires on its "
        "line (silence that outlived its bug)",
    )
    args = ap.parse_args(argv)
    wanted = [r.strip() for r in args.rules.split(",") if r.strip()]
    if args.stale_suppressions and wanted:
        ap.error("--stale-suppressions audits ALL tiers; drop --rules")

    def matches(w: str, family: str) -> bool:
        # exact family, a full rule name within it (`hot-loop-no-timeout`
        # → hot-loop, `contract-route` → contracts), or a shorthand
        # prefix (`lock` → lock-order). A token that IS another family's
        # exact name never prefix-matches across the boundary — `c` must
        # select only the C tier, never `contracts` (and vice versa).
        if w == family:
            return True
        if w.startswith(family + "-"):
            return True
        if family == "contracts" and w.startswith("contract-"):
            return True
        if family == "contracts" and w == "no-deadline":
            return True  # the deadline-bypass rule rides this tier
        return w not in _FAMILIES and family.startswith(w)

    for w in wanted:
        if not any(matches(w, f) for f in _FAMILIES):
            ap.error(
                f"--rules {w!r} matches no checker family "
                f"{list(_FAMILIES)}"
            )

    def active(family: str) -> bool:
        return not wanted or any(matches(w, family) for w in wanted)

    t0 = time.time()
    findings: list[Finding] = []
    index = None
    registry = None

    if active("lock-order") or active("unguarded-write"):
        from seaweedfs_tpu.analysis import lockorder

        lock_findings, index = lockorder.check()
        if active("lock-order"):
            findings += [f for f in lock_findings if f.rule == "lock-order"]
        if active("unguarded-write"):
            findings += [
                f for f in lock_findings if f.rule == "unguarded-write"
            ]
    if index is None and (
        active("hot-loop") or active("contracts") or active("lifecycle")
        or active("crash") or active("race")
    ):
        # these tiers only need the package index, not the full
        # lock-graph/cycle/unguarded-write analyses
        from seaweedfs_tpu.analysis import lockorder

        index = lockorder.build_index()
    if active("hot-loop"):
        from seaweedfs_tpu.analysis import hotloop

        hot_findings, index = hotloop.check(index=index)
        findings += hot_findings
    if active("contracts"):
        from seaweedfs_tpu.analysis import contracts

        contract_findings, index, registry = contracts.check(index=index)
        findings += contract_findings
    if active("lifecycle"):
        from seaweedfs_tpu.analysis import lifecycle

        life_findings, index = lifecycle.check(index=index)
        findings += life_findings
    if active("crash"):
        from seaweedfs_tpu.analysis import crashlint

        crash_findings, index = crashlint.check(index=index)
        findings += crash_findings
    if active("race"):
        from seaweedfs_tpu.analysis import racelint

        race_findings, index = racelint.check(index=index)
        findings += race_findings
    if active("c"):
        from seaweedfs_tpu.analysis import ctier

        findings += ctier.check()

    if index is None:
        # `--rules c` alone never walked the package, but the bare-ignore
        # contract (every suppression carries a reason) must hold on
        # every invocation path, so build the source index regardless
        from seaweedfs_tpu.analysis import lockorder

        index = lockorder.build_index()
    kept, suppressed = apply_suppressions(findings, index.sources)
    if args.stale_suppressions:
        kept += find_stale_suppressions(suppressed, index.sources)

    fuzz_report = None
    if args.fuzz > 0:
        from seaweedfs_tpu.analysis import fuzz_post

        fuzz_report = fuzz_post.run(iterations=args.fuzz)
        for div in fuzz_report.divergences:
            kept.append(
                Finding(
                    "fuzz-divergence",
                    "seaweedfs_tpu/native/post.c",
                    1,
                    f"C and Python POST paths diverged: {div}",
                )
            )

    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    if args.json:
        out = {
            "findings": [f.__dict__ for f in kept],
            "suppressed": [f.__dict__ for f in suppressed],
            "elapsed_s": round(time.time() - t0, 2),
            "ok": not kept,
        }
        if registry is not None:
            out["contracts"] = registry.to_dict()
        if fuzz_report is not None:
            out["fuzz"] = fuzz_report.to_dict()
        print(json.dumps(out, indent=2))
    else:
        for f in kept:
            print(f.format())
        note = (
            f"weedlint: {len(kept)} finding(s), "
            f"{len(suppressed)} suppressed (justified), "
            f"{time.time() - t0:.1f}s"
        )
        if fuzz_report is not None:
            note += (
                f"; fuzz {fuzz_report.iterations} iters, "
                f"{fuzz_report.handled} C-handled, "
                f"{len(fuzz_report.divergences)} divergence(s)"
            )
        print(note)
    return 1 if kept else 0


if __name__ == "__main__":
    sys.exit(main())
