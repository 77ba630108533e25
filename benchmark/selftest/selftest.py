"""`python benchmark/run.py --selftest`: the trace reducer and the
metric readers on a small recorded trace (a CPU rehearsal of encode-1g,
`rehearsal_encode.xplane.pb`) and on a canned node log, with the
arithmetic checked by hand, and the walk of a volume file on one built
here byte by byte. No chip, a few seconds.
What it reads off the CPU trace is checked for consistency only; it is
no device number and is printed as none.
"""

from __future__ import annotations

import os
import struct
import tempfile

from harness import readers, reference, roofline, trace_reduce
from harness.loader import digest
from harness.node import verb_reports

HERE = os.path.dirname(os.path.abspath(__file__))


def close(a: float, b: float) -> bool:
    return abs(a - b) <= 1e-9 * max(1.0, abs(a), abs(b))


def record(key: int, data: bytes) -> bytes:
    """One version-3 needle record with no name, mime or pairs."""
    body = struct.pack(">I", len(data)) + data + b"\0"
    rec = struct.pack(">IQI", 0xC00C1E, key, len(body)) + body + bytes(4 + 8)
    return rec + bytes(8 - len(rec) % 8)


def check_walk() -> None:
    needles = {7: b"seven" * 100, 0x1234: b"x", 9: bytes(range(256)) * 9}
    want = {k: digest(v) for k, v in needles.items()}
    dat = bytes([3, 0, 0, 0, 0, 0, 0, 0]) + b"".join(record(k, v) for k, v in needles.items())
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "v.dat")

        def differ(blob: bytes) -> int:
            with open(path, "wb") as f:
                f.write(blob)
            return reference.dat_needles_differ(path, want, digest)

        assert differ(dat) == 0
        flipped = bytearray(dat)
        flipped[8 + 16 + 4 + 3] ^= 1
        assert differ(bytes(flipped)) == 1
        assert differ(dat + record(7, needles[7])) == 1  # a needle stored twice
        assert differ(dat + record(8, b"new")) == 1
        assert differ(dat[:-8]) == 3  # a file that does not parse
        assert differ(dat[: len(dat) - len(record(9, needles[9]))]) == 1


def main() -> int:
    with open(os.path.join(HERE, "node_log.txt")) as f:
        reports = verb_reports(f.read(), "generate")
    assert len(reports) == 2, reports
    trace = trace_reduce.reduce_trace(
        os.path.join(HERE, "rehearsal_encode.xplane.pb"), "cpu", window_s=0.648139238357544)
    assert trace["busy_s"] and 0 < trace["busy_s"] < trace["window_s"], trace
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    obs = {
        "reports": reports,
        "window": {"seconds": 5.0, "gib": 2.0, "requests": 400},
        "trace": trace,
        "traced_work": {"encode_hbm_bytes": roofline.encode_hbm_bytes(1 << 30)},
        "device_kind": "TPU v5 lite",
    }
    want = {
        "handler_overhead_pct": 100 * (1 - 4.0 / 5.0),
        "dispatch_s_per_gib": 2.0 / 2.0,
        "writeback_s_per_gib": 5.0 / 2.0,
        "read_s_per_gib": 1.0 / 2.0,
        "write_s_per_gib": 3.0 / 2.0,
    }
    for name, value in want.items():
        got = readers.read_metric(readers.load_metric(name), obs)
        assert got is not None and close(got, value), (name, got, value)
    idle = readers.read_metric(readers.load_metric("device_idle_pct.ec"), obs)
    busy_pct = 100 * trace["busy_s"] / trace["window_s"]
    assert close(idle + busy_pct, 100.0), (idle, busy_pct)
    # the worked example of harness/roofline.py
    assert roofline.encode_hbm_bytes(1 << 30) == 1_512_046_592
    assert abs(roofline.encode_floor_s(1 << 30, "TPU v5 lite") - 1.846e-3) < 1e-6
    try:
        roofline.peaks("cpu")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind has to be an error")
    # a reader with nothing to read returns nothing, never 0
    empty = dict(obs, reports=[], trace=None)
    for name in list(want) + ["device_idle_pct.ec", "encode_kernel_roofline"]:
        assert readers.read_metric(readers.load_metric(name), empty) is None, name
    check_walk()
    print(f"selftest ok: {len(want) + 2} metric readers, trace busy + idle = 100 %, "
          f"{len(trace['op_seconds'])} operation names in the recorded trace")
    return 0
