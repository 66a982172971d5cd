"""GF(2^8) arithmetic by tables: the benchmark's own reference.

Polynomial x^8 + x^4 + x^3 + x^2 + 1 (0x11D), generator 2, the field of
the store under test.  Addition is XOR.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables():
    exp = np.zeros(510, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:] = exp[:255]
    nz = np.arange(1, 256)
    mul = np.zeros((256, 256), np.uint8)
    mul[1:, 1:] = exp[log[nz][:, None] + log[nz][None, :]]
    inv = np.zeros(256, np.uint8)
    inv[1:] = exp[255 - log[nz]]
    return mul, inv


#: MUL[a, b] = a * b in GF(2^8); INV[a] = 1 / a (INV[0] unused)
MUL, INV = _tables()


def matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """C = A @ B over GF(2^8), one inner index at a time."""
    a = np.asarray(a, np.uint8)
    b = np.asarray(b, np.uint8)
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"shapes {a.shape} and {b.shape} do not chain")
    out = np.zeros((a.shape[0], b.shape[1]), np.uint8)
    for i in range(a.shape[1]):
        out ^= MUL[a[:, i][:, None], b[i][None, :]]
    return out


def rank(v: np.ndarray) -> int:
    """Rank over GF(2^8) by Gaussian elimination."""
    m = np.array(v, np.uint8)
    rows, cols = m.shape
    r = 0
    for c in range(cols):
        if r == rows:
            break
        nz = np.nonzero(m[r:, c])[0]
        if nz.size == 0:
            continue
        p = r + int(nz[0])
        if p != r:
            m[[r, p]] = m[[p, r]]
        m[r, c:] = MUL[INV[m[r, c]], m[r, c:]]
        below = np.nonzero(m[r + 1:, c])[0] + r + 1
        if below.size:
            m[below, c:] ^= MUL[m[below, c][:, None], m[r, c:][None, :]]
        r += 1
    return r


def column_mask(rng: np.random.Generator, n: int) -> np.ndarray:
    """A random selection of columns as bytes 0x00 / 0xFF."""
    return np.where(rng.random(n) < 0.5, 0, 0xFF).astype(np.uint8)


def row_sums(x: np.ndarray, mask: np.ndarray | None = None,
             rows_per_block: int = 16) -> np.ndarray:
    """Per row, the GF(2^8) sum (XOR) of the columns that ``mask`` selects
    (all columns without a mask).

    A sum of columns is GF-linear, so for C = V @ F it holds that
    row_sums(C) = V @ row_sums(F): a check of every byte of C against F at
    the cost of one pass over each (Freivalds' test with 0/1 weights).
    """
    x = np.asarray(x, np.uint8)
    rows, n = x.shape
    out = np.zeros(rows, np.uint8)
    wide = n % 8 == 0 and x.flags.c_contiguous
    mwords = None
    if mask is not None and wide:
        mwords = np.ascontiguousarray(mask).view(np.uint64)
    for lo in range(0, rows, rows_per_block):
        blk = x[lo:lo + rows_per_block]
        if wide:
            w = blk.view(np.uint64)
            if mwords is not None:
                w = w & mwords
            acc = np.bitwise_xor.reduce(w, axis=1)
            out[lo:lo + len(blk)] = np.bitwise_xor.reduce(
                acc.view(np.uint8).reshape(-1, 8), axis=1)
        else:
            b = blk if mask is None else blk & mask
            out[lo:lo + len(blk)] = np.bitwise_xor.reduce(b, axis=1)
    return out
