"""Device-resident distributed block-sparse matrix.

:class:`DistBSMatrix` is the persistent distributed object the CHT runtime
keeps in worker chunk storage: the *values* live sharded across a 1-D worker
mesh as one padded per-device store ``[P, cap, bs, bs]`` and STAY there
across operations; the *structure* (Morton-sorted block coords plus the
owner / slot placement maps) lives on the host where all symbolic decisions
are made.  A matrix enters the mesh once via :func:`scatter` and leaves only
at the algorithm boundary via :meth:`DistBSMatrix.gather` — iterative
algorithms (``repro.dist.purify``) never ship operand blocks from the host
between operations.

Layout invariants (relied on by every planner in this package):

* ``owner[g]`` is the device holding global block ``g``; ``slot[g]`` is its
  row in that device's store, and slots are assigned in ascending global
  (Morton) order within each owner — exactly
  :func:`repro.core.schedule._owner_slots`.
* ``cap == max(blocks per device, 1)``; store rows past a device's last
  valid slot are padding with UNSPECIFIED content (kernel trash rows) — every
  consumer masks by validity rather than assuming zeros.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import AXIS, make_worker_mesh
from repro.core.matrix import BSMatrix, block_frobenius_norms
from repro.core.quadtree import morton_encode, structure_fingerprint
from repro.core.schedule import _owner_slots, partition_morton

__all__ = [
    "DistBSMatrix",
    "scatter",
    "dist_zeros",
    "mesh_key",
    "resident_block_norms",
]


def mesh_key(mesh: Mesh) -> tuple:
    """Device identity of a mesh — part of every plan-cache key, so a shared
    PlanCache never replays an executable jitted for a different mesh."""
    return tuple(int(d.id) for d in mesh.devices.flat)


def _store_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS))


@dataclasses.dataclass(frozen=True)
class DistBSMatrix:
    """Sharded block-sparse matrix resident on a worker mesh.

    Attributes:
      shape:  logical (rows, cols).
      bs:     leaf block size.
      coords: host [nnzb, 2] block (row, col), Morton sorted.
      owner:  host [nnzb] int32 — device holding each block.
      slot:   host [nnzb] int32 — row within the owner's store.
      cap:    store rows per device (max blocks on any device, >= 1).
      store:  device [P, cap, bs, bs], sharded over the mesh's worker axis;
              rows past a device's valid count are unspecified padding.
      mesh:   the worker mesh the store lives on.
    """

    shape: tuple[int, int]
    bs: int
    coords: np.ndarray
    owner: np.ndarray
    slot: np.ndarray
    cap: int
    store: jax.Array
    mesh: Mesh

    def __post_init__(self):
        assert self.coords.ndim == 2 and self.coords.shape[1] == 2
        assert self.owner.shape == self.slot.shape == (self.coords.shape[0],)
        assert self.store.shape == (
            self.nparts,
            self.cap,
            self.bs,
            self.bs,
        ), (self.store.shape, self.nparts, self.cap, self.bs)

    @property
    def nnzb(self) -> int:
        return int(self.coords.shape[0])

    @property
    def nparts(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def dtype(self):
        return self.store.dtype

    def codes(self) -> np.ndarray:
        return morton_encode(self.coords[:, 0], self.coords[:, 1])

    def store_maps(self) -> tuple[np.ndarray, np.ndarray]:
        """(store_idx [P, cap] global block per slot, store_valid [P, cap])."""
        idx = np.zeros((self.nparts, self.cap), dtype=np.int32)
        valid = np.zeros((self.nparts, self.cap), dtype=bool)
        idx[self.owner, self.slot] = np.arange(self.nnzb, dtype=np.int32)
        valid[self.owner, self.slot] = True
        return idx, valid

    # -- boundary conversions ----------------------------------------------
    def gather(self) -> BSMatrix:
        """Pull the matrix back to a host-structured BSMatrix (boundary op)."""
        host = np.asarray(self.store)
        data = host[self.owner, self.slot] if self.nnzb else host[:0, 0]
        return BSMatrix(
            shape=tuple(self.shape),
            bs=self.bs,
            coords=self.coords,
            data=jnp.asarray(data),
        )

    # -- device-local ops ---------------------------------------------------
    def scale(self, alpha) -> "DistBSMatrix":
        """alpha * A; elementwise on the resident store, stays sharded."""
        return dataclasses.replace(
            self, store=self.store * jnp.asarray(alpha, self.dtype)
        )

    def astype(self, dtype) -> "DistBSMatrix":
        return dataclasses.replace(self, store=self.store.astype(dtype))


def _mapped_norms_psum(store, gpos, *, nnzb: int):
    """Per-device block norms scattered to global stack positions, psum'd.

    Each stack position receives its value from exactly one device (its
    owner) plus zeros from the rest — float addition with +0.0 is exact, so
    the result is bit-identical to fetching the padded table and indexing on
    the host.  Padding rows scatter into the trash position ``nnzb``.
    """
    norms = block_frobenius_norms(store[0])  # [cap], float32
    out = jnp.zeros((nnzb + 1,), norms.dtype).at[gpos[0]].add(norms)
    return jax.lax.psum(out[:nnzb], AXIS)


class NormTableExecutable:
    """Fused device-side norm reduction + compaction for one structure.

    The legacy path fetches the padded ``[P, cap]`` norm table and compacts
    on the host; this executable scatters each device's valid block norms
    into their global stack positions and ``psum``s over the worker axis, so
    only the dense ``[nnzb]`` stack-order vector — the exact leaf bounds the
    hierarchical descents consume — ever crosses device->host.
    """

    def __init__(self, x: DistBSMatrix):
        gpos = np.full((x.nparts, x.cap), x.nnzb, dtype=np.int32)  # trash
        gpos[x.owner, x.slot] = np.arange(x.nnzb, dtype=np.int32)
        # host copy retained for repro.analysis plan-cache verification
        self._verify_plan = dict(
            kind="norm-table", gpos=gpos, owner=np.asarray(x.owner),
            slot=np.asarray(x.slot), nnzb=x.nnzb, nparts=x.nparts, cap=x.cap)
        self._gpos = jax.device_put(gpos, NamedSharding(x.mesh, P(AXIS)))
        self._mapped = jax.jit(
            jax.shard_map(
                functools.partial(_mapped_norms_psum, nnzb=x.nnzb),
                mesh=x.mesh,
                in_specs=(P(AXIS), P(AXIS)),
                out_specs=P(),
                check_vma=False,
            )
        )

    def __call__(self, store) -> np.ndarray:
        return np.asarray(self._mapped(store, self._gpos))  # [nnzb] -> host


def resident_block_norms(x: DistBSMatrix, cache=None) -> np.ndarray:
    """Per-block Frobenius norms in stack order from the resident store.

    Runs :func:`repro.core.matrix.block_frobenius_norms` — the exact kernel
    the host path uses, same accumulation dtype — on the ``[P, cap, bs, bs]``
    store, so host and resident SpAMM / hierarchical truncation make
    identical prune decisions near ``tau``.  With a
    :class:`~repro.dist.cache.PlanCache`, the reduction and the compaction
    are fused on device (:class:`NormTableExecutable`, cached per structure):
    only the ``[nnzb]`` stack-order vector crosses device->host instead of
    the padded ``[P, cap]`` table, with bit-identical values (tested).
    """
    if x.nnzb == 0:
        return np.zeros((0,), dtype=np.float64)
    from repro.obs.tracer import tracer_of

    tr = tracer_of(cache)
    with tr.span("norm_fetch", cat="collective", nnzb=x.nnzb):
        if tr.enabled:
            tr.counter("host_fetches").add()
            tr.counter("norm_fetch_bytes").add(x.nnzb * 4)
        mm = getattr(cache, "memory_meter", None) if cache is not None else None
        if mm is not None:
            # the [P, cap] norm table the fused reduction materializes
            per_worker = np.full(x.nparts, x.cap * 4, dtype=np.int64)
            mm.note_bytes("norm_table", per_worker, cache=cache)
        if cache is not None:
            key = (
                "norms",
                structure_fingerprint(x.codes(), x.owner, x.nparts, x.bs),
                mesh_key(x.mesh),
            )
            exe = cache.get_or_build(key, lambda: NormTableExecutable(x))
            return exe(x.store).astype(np.float64)
        table = np.asarray(block_frobenius_norms(x.store))  # [P, cap] -> host
        return table[x.owner, x.slot].astype(np.float64)


def dist_zeros(
    shape: tuple[int, int], bs: int, mesh: Mesh, dtype=jnp.float32
) -> DistBSMatrix:
    """Structurally-empty resident matrix (cap-1 padding store, no blocks)."""
    store = jax.device_put(
        np.zeros((int(mesh.devices.size), 1, bs, bs), dtype=dtype),
        _store_sharding(mesh),
    )
    return DistBSMatrix(
        shape=tuple(shape),
        bs=bs,
        coords=np.zeros((0, 2), dtype=np.int64),
        owner=np.zeros((0,), dtype=np.int32),
        slot=np.zeros((0,), dtype=np.int32),
        cap=1,
        store=store,
        mesh=mesh,
    )


def scatter(
    a: BSMatrix,
    mesh: Mesh | None = None,
    *,
    owner: np.ndarray | None = None,
) -> DistBSMatrix:
    """Ship a host BSMatrix onto the mesh once; default Morton placement.

    The inverse of :meth:`DistBSMatrix.gather`.  ``owner`` pins an explicit
    placement (must assign every block a device id < mesh size).
    """
    mesh = mesh or make_worker_mesh()
    nparts = int(mesh.devices.size)
    if owner is None:
        owner = partition_morton(a.nnzb, nparts)
    owner = np.asarray(owner, dtype=np.int32)
    assert owner.shape == (a.nnzb,)
    slot, stores = _owner_slots(owner, nparts)
    cap = max(max((len(s) for s in stores), default=0), 1)
    host = np.zeros((nparts, cap, a.bs, a.bs), dtype=np.asarray(a.data).dtype)
    data = np.asarray(a.data)
    for p, s in enumerate(stores):
        host[p, : len(s)] = data[s]
    # straight from host memory onto the sharding: each device receives only
    # its own [cap, bs, bs] slice (no staging of the whole stack on one chip)
    store = jax.device_put(host, _store_sharding(mesh))
    return DistBSMatrix(
        shape=tuple(a.shape),
        bs=a.bs,
        coords=a.coords,
        owner=owner,
        slot=slot,
        cap=cap,
        store=store,
        mesh=mesh,
    )
