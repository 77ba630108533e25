"""Bytes of an EC rebuild, counted from shapes: the same whatever
implements the kernel (the encode's are `roofline.py`'s).

A rebuild of `targets` lost shards reads one tile of each of the 10
survivor shards and writes one tile of each target, each once through
HBM; the inversion of the survivor submatrix is host work done once per
survivor set, and the fused CRC adds no HBM traffic that an ideal kernel
would need. Shard files are walked end to end, so for shard files of
`shard_bytes` the least is

    hbm_bytes = shard_bytes * (10 + targets)
    floor_s   = hbm_bytes / peak HBM bytes/s

Worked example: a 1 GiB volume has 103 stripe rows and shard files of
103 MiB = 108,003,328 bytes; one lost shard -> 108,003,328 * 11 =
1,188,036,608 bytes -> 1.451 ms at 819 GB/s (TPU v5e). It is the HBM
bound, as the encode's: the GF(2^8) arithmetic runs on the VPU's integer
lanes, for which no peak is published, so no compute bound is claimed.
The peak is read by `readers.py` from `peaks.json` (`trace:floor_s:<work>`).
"""

from __future__ import annotations

DATA = 10


def rebuild_hbm_bytes(shard_bytes: int, targets: int = 1) -> int:
    if shard_bytes < 0 or targets < 1:
        raise ValueError(f"rebuild of {targets} target(s) of {shard_bytes} bytes")
    return shard_bytes * (DATA + targets)
