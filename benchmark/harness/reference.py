"""The plain reference: RS(10,4) over GF(2^8) and the shard-file layout,
in numpy, importing nothing of the program.

It is what the upstream library fixes (klauspost/reedsolomon, used by
weed/storage/erasure_coding/ec_encoder.go): the field with reducing
polynomial 0x11D, the systematic matrix A = V * inverse(V[:10]) from
the Vandermonde matrix V[r][c] = r**c, and the two-tier row layout, of
which a volume of at most 10 GiB has only 1 MiB rows: row r of the
`.dat` is bytes [r*10 MiB, (r+1)*10 MiB), block i of it goes to shard
i, the last row is padded with zeros, and parity shard 10+p is the
GF(2^8) combination of the ten data blocks by row p of A's lower part.
The `.ecc` sidecar holds the CRC-32C of each whole shard file
(google_crc32c, a package of the installation, not of the program).

`check_shards` compares a node's 14 shard files and its `.ecc` with
that. `write_shards` puts the reference in the program's place: it is
how a control (one guarantee broken) and the tests produce shard files.

The operand is held to the seed too: `dat_needles_differ` walks every
record of the sealed `.dat` by upstream's version-3 volume layout
(weed/storage/needle/needle_read_write.go) and compares each needle's
bytes with what the seed says was stored under that id, so that the
reference encodes nothing that rests on the program alone.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import google_crc32c
import numpy as np

DATA, PARITY = 10, 4
TOTAL = DATA + PARITY
BLOCK = 1 << 20
LARGE_BLOCK = 1 << 30


def shard_ext(i: int) -> str:
    return f".ec{i:02d}"


def _mul_table() -> np.ndarray:
    """MUL[a, b] = a*b in GF(2^8) modulo x^8+x^4+x^3+x^2+1."""
    exp, log = [0] * 510, [0] * 256
    x = 1
    for i in range(255):
        exp[i], log[x] = x, i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:510] = exp[0:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            mul[a, b] = exp[log[a] + log[b]]
    return mul


MUL = _mul_table()


def _gf_pow(a: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = int(MUL[out, a])
    return out


def _gf_inv(a: int) -> int:
    return int(np.nonzero(MUL[a] == 1)[0][0])


def _mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for k in range(a.shape[1]):
                acc ^= int(MUL[a[i, k], b[k, j]])
            out[i, j] = acc
    return out


def _mat_inv(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    w = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for c in range(n):
        p = next(r for r in range(c, n) if w[r, c])
        w[[c, p]] = w[[p, c]]
        w[c] = MUL[_gf_inv(int(w[c, c])), w[c]]
        for r in range(n):
            if r != c and w[r, c]:
                w[r] ^= MUL[int(w[r, c]), w[c]]
    return w[:, n:].copy()


def parity_matrix(kind: str = "vandermonde") -> np.ndarray:
    """[4, 10] parity coefficients. `vandermonde` is the code the
    configuration states; `cauchy` (1/(x_i + y_j), x = 10..13, y = 0..9)
    is also MDS and cheaper to build, but its shards are another code's:
    it is the control, never the reference."""
    if kind == "cauchy":
        return np.array(
            [[_gf_inv((DATA + p) ^ j) for j in range(DATA)] for p in range(PARITY)],
            dtype=np.uint8,
        )
    if kind != "vandermonde":
        raise ValueError(f"unknown parity matrix {kind!r}")
    vm = np.array(
        [[_gf_pow(r, c) for c in range(DATA)] for r in range(TOTAL)], dtype=np.uint8
    )
    return _mat_mul(vm, _mat_inv(vm[:DATA]))[DATA:]


def _pair_tables(coef: np.ndarray) -> np.ndarray:
    """[10, 65536] uint64: for data shard j and a little-endian byte
    pair (b0, b1), the four parity rows' products packed 16 bits each,
    so one lookup per byte pair serves all four parity shards."""
    lo = np.arange(65536, dtype=np.uint32) & 0xFF
    hi = np.arange(65536, dtype=np.uint32) >> 8
    out = np.zeros((DATA, 65536), dtype=np.uint64)
    for j in range(DATA):
        for p in range(PARITY):
            row = MUL[int(coef[p, j])]
            pair = row[lo].astype(np.uint64) | (row[hi].astype(np.uint64) << np.uint64(8))
            out[j] |= pair << np.uint64(16 * p)
    return out


def n_rows(dat_size: int) -> int:
    if dat_size > DATA * LARGE_BLOCK:
        raise ValueError("the reference covers volumes of at most 10 GiB (1 MiB rows)")
    return -(-dat_size // (DATA * BLOCK))


def read_rows(dat_path: str) -> np.ndarray:
    """The `.dat` as [rows, 10, 1 MiB] uint8, zero-padded."""
    size = os.path.getsize(dat_path)
    rows = n_rows(size)
    buf = np.zeros(rows * DATA * BLOCK, dtype=np.uint8)
    with open(dat_path, "rb") as f:
        got = f.readinto(memoryview(buf)[:size])
    if got != size:
        raise OSError(f"short read of {dat_path}: {got} of {size}")
    return buf.reshape(rows, DATA, BLOCK)


def parity_rows(rows: np.ndarray, kind: str = "vandermonde"):
    """Yield, row by row, the [4, 1 MiB] parity blocks."""
    tables = _pair_tables(parity_matrix(kind))
    acc = np.empty(BLOCK // 2, dtype=np.uint64)
    tmp = np.empty(BLOCK // 2, dtype=np.uint64)
    for r in range(rows.shape[0]):
        acc[:] = 0
        for j in range(DATA):
            np.take(tables[j], rows[r, j].view(np.uint16), out=tmp, mode="wrap")
            acc ^= tmp
        packed = acc.view(np.uint8).reshape(-1, PARITY, 2)
        yield np.ascontiguousarray(packed.transpose(1, 0, 2)).reshape(PARITY, BLOCK)


def crc32c(data) -> int:
    return google_crc32c.value(bytes(data))


def encode(rows: np.ndarray, parity: str = "vandermonde") -> list[np.ndarray]:
    """The 14 shards of `read_rows`' array, each one contiguous uint8."""
    par = np.empty((PARITY, rows.shape[0], BLOCK), dtype=np.uint8)
    for r, blocks in enumerate(parity_rows(rows, parity)):
        par[:, r, :] = blocks
    return ([np.ascontiguousarray(rows[:, i, :]).reshape(-1) for i in range(DATA)]
            + [par[p].reshape(-1) for p in range(PARITY)])


def write_shards(dat_path: str, base: str, *, parity: str = "vandermonde",
                 checksum: str = "crc32c") -> None:
    """The reference in the program's place: all 14 shard files and the
    `.ecc` of `dat_path`, written at `base`. `parity="cauchy"` or
    `checksum="crc32"` (zlib's IEEE polynomial) each break one stated
    guarantee: they are the controls."""
    digest = crc32c if checksum == "crc32c" else (lambda d: zlib.crc32(bytes(d)))
    doc = {"version": 1, "shards": {}}
    for i, shard in enumerate(encode(read_rows(dat_path), parity)):
        with open(base + shard_ext(i), "wb") as f:
            f.write(memoryview(shard))
        doc["shards"][str(i)] = {"crc": digest(shard), "size": int(shard.size)}
    with open(base + ".ecc", "w") as f:
        json.dump(doc, f)


def _read_shard(base: str, i: int, size: int) -> np.ndarray | None:
    """Shard file i of `base` if it has exactly `size` bytes."""
    path = base + shard_ext(i)
    if os.path.exists(path) and os.path.getsize(path) == size:
        return np.fromfile(path, dtype=np.uint8)
    return None


def check_shards(dat_path: str, base: str) -> dict:
    """Compare the node's shard files at `base` with the reference
    encode of `dat_path`. Returns counts, each of which has to be 0:
    data and parity shard files that differ (or are missing or of the
    wrong size), and `.ecc` entries that differ from the CRC-32C of the
    reference's shard (a missing sidecar counts all 14)."""
    rows = read_rows(dat_path)
    with ThreadPoolExecutor(max_workers=4) as pool:
        files = pool.map(lambda i: _read_shard(base, i, rows.shape[0] * BLOCK), range(TOTAL))
        want = encode(rows)  # while the pool reads the files
        differ = [got is None or not np.array_equal(got, w) for got, w in zip(files, want)]
    want_crcs = [crc32c(w) for w in want]
    try:
        with open(base + ".ecc") as f:
            ecc = json.load(f)["shards"]
    except (OSError, ValueError, KeyError):
        ecc = {}
    return {
        "data_shards_differ": sum(differ[:DATA]),
        "parity_shards_differ": sum(differ[DATA:]),
        "ecc_crcs_differ": sum(
            1 for i in range(TOTAL) if (ecc.get(str(i)) or {}).get("crc") != want_crcs[i]),
    }


SUPER_BLOCK = 8
TOMBSTONE = 0xFFFFFFFF


def walk_dat(buf) -> list[tuple[int, int, int]]:
    """(needle id, offset of its data, length of its data) of every record
    of a version-3 volume file, upstream's layout: a superblock of 8 bytes
    (version, ..., u16 length of what follows it), then records of
    cookie u32, id u64, size u32 big-endian, `size` bytes of body that
    begin with u32 data length and the data, a CRC u32, the append time
    u64 and 1 to 8 bytes of padding to a multiple of 8. A record that
    does not parse ends the walk with ValueError."""
    if len(buf) < SUPER_BLOCK or buf[0] != 3:
        raise ValueError("not a version-3 volume file")
    at = SUPER_BLOCK + struct.unpack_from(">H", buf, 6)[0]
    out = []
    while at < len(buf):
        if at + 16 > len(buf):
            raise ValueError(f"record header cut off at {at}")
        _, key, size = struct.unpack_from(">IQI", buf, at)
        if size in (0, TOMBSTONE):
            raise ValueError(f"needle {key:x} at {at} is empty or deleted")
        n_data = struct.unpack_from(">I", buf, at + 16)[0]
        if n_data + 5 > size or at + 16 + size + 12 > len(buf):
            raise ValueError(f"needle {key:x} at {at}: data {n_data} in body {size}")
        out.append((key, at + 20, n_data))
        unpadded = 16 + size + 4 + 8
        at += unpadded + 8 - unpadded % 8
    return out


def dat_needles_differ(dat_path: str, want: dict[int, bytes], digest) -> int:
    """How far the sealed `.dat` is from what the seed stored: needles of
    `want` (id -> digest of its bytes) that are not in the file exactly
    once with those bytes, plus records the seed knows nothing of. A file
    that does not parse differs in every needle."""
    with open(dat_path, "rb") as f, mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        try:
            records = walk_dat(buf)
        except (ValueError, struct.error):
            return len(want)
        view = memoryview(buf)
        good, unknown = set(), 0
        for key, at, n in records:
            if key not in want or key in good:
                unknown += 1
            elif digest(view[at:at + n]) == want[key]:
                good.add(key)
        view.release()
    return len(want) - len(good) + unknown
