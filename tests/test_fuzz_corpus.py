"""Regression sweep: C vs Python byte-identity on every corpus entry.

tests/corpus/ holds adversarial multipart/POST inputs — the
deterministic seed set from `fuzz_post --seed-corpus`, handcrafted
edge framings, and any div_*/pending_* entries a fuzz run ever
persisted (a pending_* file in the tree means a past run CRASHED on
that input; it must now pass, or stay red until the C bug is fixed).
Each entry runs through the same oracle the fuzzer uses: the C path
either declines or matches the pure-Python path byte for byte on
.dat, .idx, and the HTTP reply.

Runs under the sanitizer builds too: WEED_NATIVE_SAN=asan plus the
LD_PRELOAD recipe from `_build.asan_preload_env()` turns this sweep
into a heap-corruption gate.
"""

from __future__ import annotations

import os
import pathlib

import pytest

from seaweedfs_tpu.analysis import fuzz_post

CORPUS = pathlib.Path(__file__).parent / "corpus"

pytestmark = pytest.mark.usefixtures("native_post_toolchain")


def _entries() -> list[str]:
    return sorted(p.name for p in CORPUS.glob("*.json"))


def test_corpus_is_seeded():
    """The corpus must keep its adversarial floor: ≥20 entries."""
    assert len(_entries()) >= 20, (
        "tests/corpus/ lost entries; re-seed with "
        "`python -m seaweedfs_tpu.analysis.fuzz_post --seed-corpus`"
    )


@pytest.mark.parametrize("name", _entries())
def test_corpus_entry_byte_identity(tmp_path, name):
    case = fuzz_post.case_from_json(
        (CORPUS / name).read_text(encoding="utf-8")
    )
    verdict, divergence = fuzz_post.run_case(case, str(tmp_path))
    assert divergence is None, f"{name} [{verdict}]: {divergence}"


def test_fresh_fuzz_round(tmp_path):
    """A small live round on top of the standing corpus, so tier-1
    keeps probing NEW inputs every run (fixed seed: deterministic)."""
    report = fuzz_post.run(
        iterations=25, seed=1234, corpus_dir=str(tmp_path / "corpus")
    )
    assert report.iterations == 25
    assert not report.divergences, report.divergences
