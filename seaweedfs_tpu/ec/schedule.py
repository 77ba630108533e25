"""Precompiled GF(2^8) XOR/mul schedules (arXiv:2108.02692-style CSE).

Multiplying a byte stream by a CONSTANT GF(2^8) coefficient is
GF(2)-linear, so an RS coefficient matrix is really a straight-line
XOR *program* that can be optimized once at codec construction and
replayed for every tile:

1. **Horner bit realization.** Write each output row as
   ``y[p] = Σ_j 2^j · u_{p,j}`` in the field, where ``u_{p,j}`` is the
   XOR of the input columns whose coefficient has bit j set (the same
   schedule the SWAR Pallas kernel bakes in, codec_tpu._swar_schedule).
   Evaluated Horner-style, a row costs ≤7 branchless GF-doublings plus
   the XOR terms — all full-width SIMD passes, replacing the naive
   chain's per-entry 256-way LUT gathers (the gathers are what hold
   the numpy backend to ~0.1 GB/s; pure bitwise passes run ~2.7x
   faster on the same matrix).

2. **Paar-style common-pair CSE.** The 32 per-(row, bit) XOR sets of
   RS(10,4) share many column pairs. The greedy Paar heuristic (the
   base algorithm the arXiv:2108.02692 schedulers extend) repeatedly
   extracts the most frequent pair into a temp until no pair repeats —
   for this code matrix that cuts 156 XOR terms to 46 plus 24 shared
   temps — so common subexpressions are computed once per tile instead
   of once per use.

Both rewrites are exact — XOR reassociation and GF(2)-linearity hold
bitwise — so scheduled output is byte-identical to the naive chain.

The compiler is shared by the numpy backend (ec/codec.py wraps its
apply with the per-matrix program cache here) and the SWAR Pallas
kernel builder (ec/codec_tpu.py runs the same cse_pairs over its
per-bit XOR sets). ``WEED_EC_SCHEDULE=0`` is the kill switch restoring
the naive chains everywhere (read at codec/kernel construction).
"""

from __future__ import annotations

import os
from typing import Sequence

import numpy as np


def schedule_enabled() -> bool:
    """`WEED_EC_SCHEDULE` env knob: any value but "0" keeps the
    optimized schedules on (kill switch restores the naive chains)."""
    return os.environ.get("WEED_EC_SCHEDULE", "1") != "0"


def cse_pairs(
    sets: Sequence[Sequence[int]], n_inputs: int, max_temps: int | None = None
) -> tuple[list[tuple[int, int]], list[list[int]]]:
    """Greedy Paar pass over XOR sets of input slots 0..n_inputs-1.

    Returns (temps, new_sets): ``temps[t] = (a, b)`` defines slot
    ``n_inputs + t`` as ``slot[a] ^ slot[b]`` (a/b may themselves be
    temps — evaluate in order); every new_sets[i] XORs to the same
    value as sets[i]. Pairs are extracted while any pair of slots
    co-occurs in ≥ 2 sets, most frequent first (ties broken
    deterministically by slot index so compiled programs are stable
    across runs).
    """
    work = [sorted(set(s)) for s in sets]
    temps: list[tuple[int, int]] = []
    next_slot = n_inputs
    while max_temps is None or len(temps) < max_temps:
        counts: dict[tuple[int, int], int] = {}
        for s in work:
            for i in range(len(s)):
                for j in range(i + 1, len(s)):
                    pair = (s[i], s[j])
                    counts[pair] = counts.get(pair, 0) + 1
        if not counts:
            break
        best = max(counts.items(), key=lambda kv: (kv[1], (-kv[0][0], -kv[0][1])))
        if best[1] < 2:
            break
        a, b = best[0]
        temps.append((a, b))
        for idx, s in enumerate(work):
            if a in s and b in s:
                work[idx] = sorted((set(s) - {a, b}) | {next_slot})
        next_slot += 1
    return temps, work


class CompiledSchedule:
    """One matrix's straight-line XOR program: shared temp definitions,
    then per output row a Horner chain over the CSE'd per-bit sets."""

    __slots__ = ("rows", "cols", "temps", "sel", "maxj", "n_terms", "n_terms_naive")

    def __init__(self, matrix: np.ndarray):
        matrix = np.asarray(matrix, dtype=np.uint8)
        self.rows, self.cols = matrix.shape
        sel = [
            [
                [c for c in range(self.cols) if (int(matrix[p, c]) >> j) & 1]
                for j in range(8)
            ]
            for p in range(self.rows)
        ]
        self.maxj = [
            max((j for j in range(8) if sel[p][j]), default=0)
            for p in range(self.rows)
        ]
        self.n_terms_naive = sum(len(s) for row in sel for s in row)
        flat = [sel[p][j] for p in range(self.rows) for j in range(8)]
        self.temps, new_flat = cse_pairs(flat, self.cols)
        it = iter(new_flat)
        self.sel = [[next(it) for _ in range(8)] for _ in range(self.rows)]
        self.n_terms = sum(len(s) for row in self.sel for s in row)

    def apply(self, inputs: np.ndarray) -> np.ndarray:
        """inputs [C, N] uint8 → [R, N] uint8, byte-identical to
        codec.cpu_apply_matrix on the same matrix."""
        assert inputs.shape[0] == self.cols
        slots: list[np.ndarray] = [inputs[c] for c in range(self.cols)]
        for a, b in self.temps:
            slots.append(slots[a] ^ slots[b])
        n = inputs.shape[1]
        out = np.empty((self.rows, n), dtype=np.uint8)
        red = np.uint8(0x1D)
        hb = np.empty(n, dtype=np.uint8)  # doubling scratch, reused
        for p in range(self.rows):
            y = out[p]
            live = False
            for j in range(self.maxj[p], -1, -1):
                if live:
                    # branchless GF(2^8) doubling on uint8 lanes:
                    # y' = (y << 1) ^ 0x1D·highbit(y)  (poly 0x11D)
                    np.right_shift(y, 7, out=hb)
                    np.left_shift(y, 1, out=y)
                    hb *= red
                    y ^= hb
                s = self.sel[p][j]
                if s:
                    if live:
                        for c in s:
                            y ^= slots[c]
                    else:
                        np.copyto(y, slots[s[0]])
                        for c in s[1:]:
                            y ^= slots[c]
                        live = True
            if not live:
                y.fill(0)
        return out


# (shape, matrix bytes) -> CompiledSchedule. Distinct matrices are few:
# the parity rows plus one decode-rows matrix per survivor/target pair,
# each already cached in its own right upstream.
_PROGRAM_CACHE: dict[tuple, CompiledSchedule] = {}


def compile_schedule(matrix: np.ndarray) -> CompiledSchedule:
    m = np.asarray(matrix, dtype=np.uint8)
    key = (m.shape, m.tobytes())
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        if len(_PROGRAM_CACHE) > 512:
            _PROGRAM_CACHE.clear()  # bound, rarely hit
        prog = _PROGRAM_CACHE[key] = CompiledSchedule(m)
    return prog


def scheduled_apply_matrix(matrix: np.ndarray, inputs: np.ndarray) -> np.ndarray:
    """Drop-in for codec.cpu_apply_matrix running the compiled
    program (compiled once per distinct matrix, then replayed)."""
    return compile_schedule(matrix).apply(inputs)
