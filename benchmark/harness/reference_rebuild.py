"""The plain reference of the DECODE: a lost shard of RS(10,4) from ten
survivor files, in numpy, importing `harness/reference.py` and nothing
of the program.

The code's generator matrix is G = [I(10); A] (14 x 10): shard i of a
stripe row is row i of G times the ten data blocks, where A is the
parity matrix `reference.parity_matrix()` fixes (klauspost/reedsolomon's,
which weed/storage/erasure_coding/ec_encoder.go:227-281 RebuildEcFiles
uses through `Reconstruct`). Any ten rows of G are invertible (the code
is MDS), so with survivors S (ten shard ids) and targets T

    data     = inverse(G[S]) * survivors
    targets  = G[T] * data = (G[T] * inverse(G[S])) * survivors

and the [len(T) x 10] product is applied byte by byte over GF(2^8) to
the survivor FILES as they lie on disk, in blocks, whatever wrote them.
A shard file is a plain concatenation of its blocks of every row, so
the decode needs no row layout: byte k of a target is the combination
of byte k of the ten survivors.

`survivors_on_disk` takes the ten lowest shard ids whose files exist and
are not targets, which is also what the program's drivers pick; any
other ten give the same bytes where the files are one code's.
"""

from __future__ import annotations

import os

import numpy as np

from harness import reference
from harness.reference import DATA, MUL, TOTAL, shard_ext

DECODE_BLOCK = 8 << 20


def generator_matrix(kind: str = "vandermonde") -> np.ndarray:
    """[14, 10]: the identity over the parity matrix of `kind`."""
    return np.concatenate(
        [np.eye(DATA, dtype=np.uint8), reference.parity_matrix(kind)], axis=0
    )


def decode_rows(survivors, targets, kind: str = "vandermonde") -> np.ndarray:
    """[len(targets), 10] coefficients that give the target shards from
    the survivor shards, in the survivors' order."""
    survivors, targets = list(survivors), list(targets)
    if len(survivors) != DATA or len(set(survivors)) != DATA:
        raise ValueError(f"a decode takes {DATA} distinct survivors, got {survivors}")
    if set(survivors) & set(targets):
        raise ValueError(f"targets {targets} among the survivors {survivors}")
    g = generator_matrix(kind)
    return reference._mat_mul(g[targets], reference._mat_inv(g[survivors]))


def apply_rows(rows: np.ndarray, blocks: list[np.ndarray]) -> list[np.ndarray]:
    """rows [t, 10] times ten equal-length uint8 blocks: t blocks."""
    out = []
    for row in rows:
        acc = np.zeros(len(blocks[0]), dtype=np.uint8)
        for coef, block in zip(row, blocks):
            if coef:
                acc ^= MUL[int(coef)][block]
        out.append(acc)
    return out


def survivors_on_disk(base: str, targets) -> list[int]:
    have = [i for i in range(TOTAL)
            if i not in set(targets) and os.path.exists(base + shard_ext(i))]
    if len(have) < DATA:
        raise ValueError(f"{len(have)} survivor files at {base}: a decode takes {DATA}")
    return have[:DATA]


def decode(base: str, targets, *, parity: str = "vandermonde",
           block: int = DECODE_BLOCK) -> list[np.ndarray]:
    """The target shards of `base`, decoded from the ten survivor files
    on disk: one contiguous uint8 array per target, in their order.
    `parity="cauchy"` decodes under another code's matrix: the control."""
    targets = list(targets)
    survivors = survivors_on_disk(base, targets)
    sizes = {os.path.getsize(base + shard_ext(i)) for i in survivors}
    if len(sizes) != 1:
        raise ValueError(f"survivor files of {base} differ in size: {sorted(sizes)}")
    size = sizes.pop()
    rows = decode_rows(survivors, targets, parity)
    out = [np.empty(size, dtype=np.uint8) for _ in targets]
    files = [open(base + shard_ext(i), "rb") for i in survivors]
    try:
        for off in range(0, size, block):
            n = min(block, size - off)
            blocks = [np.fromfile(f, dtype=np.uint8, count=n) for f in files]
            if any(len(b) != n for b in blocks):
                raise OSError(f"short read of a survivor of {base} at {off}")
            for dest, got in zip(out, apply_rows(rows, blocks)):
                dest[off:off + n] = got
    finally:
        for f in files:
            f.close()
    return out


def write_decoded(base: str, targets, *, parity: str = "vandermonde") -> None:
    """The reference in the program's place: each target's file written
    over (the same inode, as a mounted volume holds it open) with the
    decode of the survivors."""
    for sid, shard in zip(targets, decode(base, targets, parity=parity)):
        with open(base + shard_ext(sid), "wb") as f:
            f.write(memoryview(shard))


def rebuilt_differ(base: str, targets) -> int:
    """Target shard files that are missing, of the wrong size or differ
    from the reference's decode of the ten survivors on disk."""
    targets = list(targets)
    bad = 0
    for sid, want in zip(targets, decode(base, targets)):
        got = reference._read_shard(base, sid, want.size)
        bad += got is None or not np.array_equal(got, want)
    return bad

