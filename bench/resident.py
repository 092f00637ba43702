"""Resident operands for the library's ``DistBSMatrix``, built on the device.

The library's layout (see the top of ``repro/dist/matrix.py``): block ``g``
lives on device ``owner[g]`` in row ``slot[g]`` of that device's store, the
slots of one device ascend in global (Morton) order, and ``cap`` is the
largest number of blocks on any device.  The benchmark places blocks with
the library's default ``partition_morton`` so the plans reproduce the
operands' ``cap``; it never ships a host stack through ``scatter``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import make_store


def layout(owner: np.ndarray, nparts: int) -> tuple[np.ndarray, int]:
    """(slot per block, cap) for an owner map, slots ascending per device."""
    owner = np.asarray(owner)
    slot = np.zeros(owner.shape[0], np.int32)
    counts = np.zeros(nparts, np.int64)
    for p in range(nparts):
        idx = np.nonzero(owner == p)[0]
        slot[idx] = np.arange(idx.size, dtype=np.int32)
        counts[p] = idx.size
    return slot, int(max(counts.max(initial=0), 1))


def resident_operand(fn, seed: int, coords: np.ndarray, shape, bs: int, mesh):
    """A ``DistBSMatrix`` whose block ``(i, j)`` is ``fn(key, i, j)``."""
    from repro.core.distributed import AXIS
    from repro.core.schedule import partition_morton
    from repro.dist import DistBSMatrix

    nparts = int(mesh.devices.size)
    owner = partition_morton(len(coords), nparts).astype(np.int32)
    slot, cap = layout(owner, nparts)
    rows = np.zeros((nparts, cap), np.int32)
    cols = np.zeros((nparts, cap), np.int32)
    valid = np.zeros((nparts, cap), bool)
    rows[owner, slot] = coords[:, 0]
    cols[owner, slot] = coords[:, 1]
    valid[owner, slot] = True
    store = make_store(fn, seed, rows, cols, valid, mesh, AXIS)
    return DistBSMatrix(shape=tuple(shape), bs=bs, coords=coords, owner=owner,
                        slot=slot, cap=cap, store=store, mesh=mesh)


def _device_index(x) -> dict[int, jax.Array]:
    """Per-device local store ``[1, cap, bs, bs]`` by worker index."""
    return {int(s.index[0].start or 0): s.data for s in x.store.addressable_shards}


@jax.jit
def _take(shard, slots):
    """Rows ``slots`` of a ``[1, cap, bs, bs]`` shard, gathered on its
    device: the shard itself is never copied."""
    return shard[0, slots]


def fetch_blocks(x, wanted: list[tuple[int, int]]) -> dict[tuple[int, int], np.ndarray]:
    """Blocks ``wanted`` of resident ``x`` as float64 host arrays, read from
    each owner's shard; a block ``x`` does not hold is left out."""
    where = {(int(i), int(j)): g for g, (i, j) in enumerate(x.coords.tolist())}
    local = _device_index(x)
    by_owner: dict[int, list[tuple[tuple[int, int], int]]] = {}
    for ij in wanted:
        g = where.get((int(ij[0]), int(ij[1])))
        if g is not None:
            by_owner.setdefault(int(x.owner[g]), []).append((ij, int(x.slot[g])))
    out = {}
    for p, items in by_owner.items():
        data = np.asarray(_take(local[p], jnp.asarray([s for _, s in items])), np.float64)
        for (ij, _), blk in zip(items, data):
            out[(int(ij[0]), int(ij[1]))] = blk
    return out

