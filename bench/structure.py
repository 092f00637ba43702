"""Block structure of the benchmark's matrices, computed without the library.

Coordinates are ``[nnzb, 2]`` int64 (block row, block col) in Z-order
(Morton order, row bits in the odd positions), the canonical block order the
library stores and places matrices in.  The symbolic product and the task
count are the yardstick the work counts and the structure check use, so they
are computed here from the coordinates alone and never read from a plan.
"""

from __future__ import annotations

import numpy as np


def _spread_bits(x: np.ndarray) -> np.ndarray:
    """Spread the low 32 bits of ``x`` so a zero bit sits between each."""
    x = np.asarray(x).astype(np.uint64)
    for shift, mask in ((16, 0x0000FFFF0000FFFF), (8, 0x00FF00FF00FF00FF),
                        (4, 0x0F0F0F0F0F0F0F0F), (2, 0x3333333333333333),
                        (1, 0x5555555555555555)):
        x = (x | (x << np.uint64(shift))) & np.uint64(mask)
    return x


def morton_codes(coords: np.ndarray) -> np.ndarray:
    coords = np.asarray(coords)
    return (_spread_bits(coords[:, 0]) << np.uint64(1)) | _spread_bits(coords[:, 1])


def morton_sorted(coords: np.ndarray) -> np.ndarray:
    coords = np.asarray(coords, dtype=np.int64).reshape(-1, 2)
    return coords[np.argsort(morton_codes(coords), kind="stable")]


def band_coords(nb: int, hw_blocks: int) -> np.ndarray:
    """Morton-sorted coordinates of every block within ``hw_blocks`` of the
    diagonal of an ``nb x nb`` block grid."""
    i = np.arange(nb)
    parts = []
    for d in range(-hw_blocks, hw_blocks + 1):
        j = i + d
        keep = (j >= 0) & (j < nb)
        parts.append(np.stack([i[keep], j[keep]], axis=1))
    return morton_sorted(np.concatenate(parts))


def random_block_starts(n: int, size: int, count: int, seed: int = 0) -> list[int]:
    """Element offsets of ``count`` non-overlapping ``size`` x ``size`` dense
    blocks on the diagonal, the gaps between them drawn as in the paper's
    random family.  The positions are fixed (``seed`` 0), so every run's
    ``--seed`` gets the same structure and the same work."""
    rng = np.random.default_rng(seed)
    gaps = rng.multinomial(n - size * count, np.ones(count + 1) / (count + 1))
    starts, pos = [], 0
    for k in range(count):
        pos += int(gaps[k])
        starts.append(pos)
        pos += size
    return starts


def random_coords(nb: int, hw_blocks: int, n: int, bs: int, size: int,
                  count: int) -> np.ndarray:
    """The band of :func:`band_coords` plus ``count`` dense diagonal blocks of
    ``size`` elements, each covering the ``ceil(size / bs) + 1`` block rows
    and columns from its first block (the element block straddles that many
    leaf blocks wherever it starts)."""
    keys = {tuple(x) for x in band_coords(nb, hw_blocks).tolist()}
    sb = -(-size // bs)
    for start in random_block_starts(n, size, count):
        b0 = start // bs
        span = range(b0, min(b0 + sb + 1, nb))
        keys.update((i, j) for i in span for j in span)
    return morton_sorted(np.array(sorted(keys), dtype=np.int64))


def product_terms(a: np.ndarray, b: np.ndarray) -> dict[tuple[int, int], list[tuple[int, int]]]:
    """Symbolic A·B: for each output block (i, j), the (A index, B index)
    pairs of stack positions whose product lands there, in ascending k."""
    by_row: dict[int, list[tuple[int, int]]] = {}
    for g, (k, j) in enumerate(np.asarray(b).tolist()):
        by_row.setdefault(k, []).append((j, g))
    out: dict[tuple[int, int], list[tuple[int, int]]] = {}
    for ga, (i, k) in sorted(enumerate(np.asarray(a).tolist()),
                             key=lambda t: (t[1][0], t[1][1])):
        for j, gb in by_row.get(k, ()):
            out.setdefault((i, j), []).append((ga, gb))
    return out


def product_tasks(a: np.ndarray, b: np.ndarray) -> int:
    """Number of block products in A·B: sum over k of |A col k| x |B row k|."""
    a, b = np.asarray(a), np.asarray(b)
    if a.size == 0 or b.size == 0:
        return 0
    hi = int(max(a[:, 1].max(), b[:, 0].max())) + 1
    return int(np.dot(np.bincount(a[:, 1], minlength=hi).astype(np.int64),
                      np.bincount(b[:, 0], minlength=hi).astype(np.int64)))


def product_coords(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Morton-sorted coordinates of the blocks of A·B."""
    return morton_sorted(np.array(sorted(product_terms(a, b)), dtype=np.int64))
