"""Operations and bytes of the EC kernels, counted from shapes: the same
whatever implements the kernel.

RS(10,4) encode of one stripe tile reads 10 data rows and writes 4
parity rows, each once through HBM; the fused CRC adds no HBM traffic
that an ideal kernel would need. So the least time for `dat_bytes` of a
volume is

    rows      = ceil(dat_bytes / (10 * 1 MiB))           # stripe rows
    hbm_bytes = rows * (10 + 4) * 1 MiB
    floor_s   = hbm_bytes / peak HBM bytes/s

Worked example: a 1 GiB `.dat` is 103 rows -> 103 * 14 MiB =
1,512,046,592 bytes -> 1.846 ms at 819 GB/s (TPU v5e). It is the HBM
bound: the GF(2^8) arithmetic runs on the VPU's integer lanes, for which
no peak is published, so no compute bound is claimed.
"""

from __future__ import annotations

import json
import os

HARNESS = os.path.dirname(os.path.abspath(__file__))
DATA, PARITY, BLOCK = 10, 4, 1 << 20


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HARNESS, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def encode_hbm_bytes(dat_bytes: int) -> int:
    rows = -(-dat_bytes // (DATA * BLOCK))
    return rows * (DATA + PARITY) * BLOCK


def encode_floor_s(dat_bytes: int, device_kind: str) -> float:
    return encode_hbm_bytes(dat_bytes) / peaks(device_kind)["hbm_bytes_per_s"]
