"""A rehearsal-size run of each cell on the CPU (`--rehearse`, which
skips the look for a chip and drives the rest of a run), once sound and
once with each control and each fault the cell can have: `correct` has
to come out true for the first and false for every other.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q      (about 3 minutes)
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CASES = [
    ("encode-1g", [], True, None),
    ("encode-1g", ["--control", "cauchy"], False, "parity_shards_differ"),
    ("encode-1g", ["--control", "crc32"], False, "ecc_crcs_differ"),
    ("encode-1g", ["--fault", "state_unchanged"], False, "shards_not_rewritten"),
    ("encode-1g", ["--fault", "answer_altered"], False, "parity_shards_differ"),
    ("encode-1g", ["--fault", "operand_altered"], False, "dat_needles_differ"),
    ("batch-encode-256m", [], True, None),
    ("batch-encode-256m", ["--control", "cauchy"], False, "parity_shards_differ"),
    ("batch-encode-256m", ["--fault", "state_unchanged"], False, "shards_not_rewritten"),
    ("batch-encode-256m", ["--fault", "half_batch"], False, "shards_not_rewritten"),
    ("batch-encode-256m", ["--fault", "answer_altered"], False, "parity_shards_differ"),
]


@pytest.mark.parametrize("workload,extra,correct,number", CASES,
                         ids=[f"{w}{'-'.join([''] + e[1:])}" for w, e, _, _ in CASES])
def test_correct_comes_out(workload, extra, correct, number):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", workload,
         "--seed", str(2**31 + 17), "--seconds", "2", "--trace", "0", "--rehearse", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert list(line)[-1] == "compared"
    if number:
        assert line["compared"][number]["value"] > line["compared"][number]["limit"]
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
