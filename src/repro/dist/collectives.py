"""Distributed linear-algebra collectives over resident stores.

Everything here follows the same shape as the multiply schedule: a host-side
symbolic phase per *structure* (cached in :class:`~repro.dist.cache.PlanCache`)
producing small index arrays and a jitted ``shard_map`` program, and a device
phase that only ever touches the resident stores:

* :func:`dist_add` — C = alpha*A + beta*B, structure union with owner-aligned
  re-slotting: union blocks inherit A's owner where present, else B's, so
  only B-copies of overlapping blocks ever cross a device boundary (planned
  as ``ppermute`` rounds via :func:`repro.core.schedule.plan_fetch`).
* :func:`dist_trace` / :func:`dist_frobenius_norm` — local masked reductions
  followed by a ``psum`` over the worker axis.
* :func:`dist_truncate` — device-computed block norms, host symbolic
  selection (identical error control to :func:`repro.core.truncate.truncate`),
  device-side compaction gather; blocks keep their owners so no data moves.
* :func:`dist_truncate_hierarchical` — the same compaction, but the symbolic
  selection is the quadtree subtree-drop descent
  (:func:`repro.core.quadtree.hierarchical_drop_mask`) over a
  :class:`~repro.core.quadtree.QuadtreeIndex` built from the resident norm
  table: dropped subtrees' leaves are never enumerated, and only the tiny
  [P, cap] norm table ever crosses device->host.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import AXIS, _exchange_bufs
from repro.core.quadtree import (
    build_quadtree_index,
    hierarchical_drop_mask,
    morton_sort,
    quadtree_depth,
)
from repro.core.schedule import (
    _owner_slots,
    local_fetch_index,
    plan_fetch,
    structure_fingerprint,
)
from repro.obs.timing import timed_into
from repro.obs.tracer import tracer_of

from .cache import PlanCache
from .matrix import DistBSMatrix, mesh_key, resident_block_norms

__all__ = [
    "dist_add",
    "dist_scale",
    "dist_trace",
    "dist_frobenius_norm",
    "dist_transpose",
    "dist_repartition",
    "RepartitionExecutable",
    "dist_submatrix",
    "dist_assemble2x2",
    "dist_truncate",
    "dist_truncate_hierarchical",
    "transpose_permutation",
]


def _put(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P(AXIS)))


def _structure_key(a: DistBSMatrix) -> tuple:
    return (
        structure_fingerprint(a.codes(), a.owner, a.nparts, a.bs),
        mesh_key(a.mesh),
    )


# --------------------------------------------------------------------------
# add
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _AddSpec:
    nparts: int
    a_offsets: tuple
    b_offsets: tuple


def _acc_dtype(*dtypes):
    """Accumulate in at least float32, wider if the stores are wider."""
    out = jnp.float32
    for dt in dtypes:
        out = jnp.promote_types(out, dt)
    return out


def _mapped_add(
    a_store, b_store, idx_a, idx_b, val_a, val_b, alpha, beta, *sends, spec
):
    na = len(spec.a_offsets)
    acc = _acc_dtype(a_store.dtype, b_store.dtype)
    a_all = _exchange_bufs(a_store[0], spec.a_offsets, sends[:na], spec.nparts)
    b_all = _exchange_bufs(b_store[0], spec.b_offsets, sends[na:], spec.nparts)
    c = alpha.astype(acc) * a_all[idx_a[0]].astype(acc) * val_a[0][:, None, None].astype(acc)
    c += beta.astype(acc) * b_all[idx_b[0]].astype(acc) * val_b[0][:, None, None].astype(acc)
    return c[None]


class AddExecutable:
    """Planned structure-union add bound to a mesh; alpha/beta are runtime
    scalars so one executable serves every coefficient pair."""

    def __init__(self, a: DistBSMatrix, b: DistBSMatrix):
        nparts, mesh = a.nparts, a.mesh
        a_codes, b_codes = a.codes(), b.codes()
        c_codes = np.union1d(a_codes, b_codes)  # sorted == Morton order
        nc = int(c_codes.size)
        pos_a = np.searchsorted(c_codes, a_codes)
        pos_b = np.searchsorted(c_codes, b_codes)
        # owner-aligned re-slotting: A's owner wins on overlap -> A blocks
        # never move; B-only blocks inherit B's owner and never move either.
        c_owner = np.zeros(nc, dtype=np.int32)
        c_owner[pos_b] = b.owner
        c_owner[pos_a] = a.owner
        c_slot, c_stores = _owner_slots(c_owner, nparts)
        c_cap = max(max((len(s) for s in c_stores), default=0), 1)

        # which A/B blocks each device needs: the source blocks of the union
        # entries it owns (ascending by construction; plan_fetch skips the
        # ones whose source copy is already local)
        def needs(x_pos):
            dst_of = c_owner[x_pos]
            return [
                np.nonzero(dst_of == p)[0].astype(np.int64) for p in range(nparts)
            ]

        a_offsets, a_send, a_send_cnt, a_recv = plan_fetch(
            a.owner, a.slot, needs(pos_a), nparts
        )
        b_offsets, b_send, b_send_cnt, b_recv = plan_fetch(
            b.owner, b.slot, needs(pos_b), nparts
        )

        # union position -> source block index (or -1)
        from_a = -np.ones(nc, dtype=np.int64)
        from_b = -np.ones(nc, dtype=np.int64)
        from_a[pos_a] = np.arange(a.nnzb)
        from_b[pos_b] = np.arange(b.nnzb)

        idx_a = np.zeros((nparts, c_cap), dtype=np.int32)
        idx_b = np.zeros((nparts, c_cap), dtype=np.int32)
        val_a = np.zeros((nparts, c_cap), dtype=np.float32)
        val_b = np.zeros((nparts, c_cap), dtype=np.float32)
        for p, s in enumerate(c_stores):
            for local, u in enumerate(s):
                ga, gb = from_a[u], from_b[u]
                if ga >= 0:
                    idx_a[p, local] = local_fetch_index(
                        a.owner, a.slot, a_offsets, a_send, a_recv, a.cap, ga, p
                    )
                    val_a[p, local] = 1.0
                if gb >= 0:
                    idx_b[p, local] = local_fetch_index(
                        b.owner, b.slot, b_offsets, b_send, b_recv, b.cap, gb, p
                    )
                    val_b[p, local] = 1.0

        # host-side plan copy retained for static verification at plan-cache
        # admission (repro.analysis.verify, kind="add") — the device arrays
        # are unverifiable post-put
        self._verify_plan = dict(
            kind="add", nparts=nparts,
            a_owner=np.asarray(a.owner), a_slot=np.asarray(a.slot),
            a_cap=a.cap,
            b_owner=np.asarray(b.owner), b_slot=np.asarray(b.slot),
            b_cap=b.cap,
            pos_a=pos_a, pos_b=pos_b, from_a=from_a, from_b=from_b,
            c_owner=c_owner, c_slot=c_slot, c_cap=c_cap,
            a_offsets=a_offsets, a_send=a_send, a_send_cnt=a_send_cnt,
            b_offsets=b_offsets, b_send=b_send, b_send_cnt=b_send_cnt,
            idx_a=idx_a, idx_b=idx_b, val_a=val_a, val_b=val_b,
        )

        from repro.core.quadtree import morton_decode

        r, c = morton_decode(c_codes)
        self.c_coords = np.stack([r, c], axis=1)
        self.c_owner = c_owner
        self.c_slot = c_slot
        self.c_cap = c_cap
        self.mesh = mesh
        spec = _AddSpec(nparts, a_offsets, b_offsets)
        self._plan_args = [
            _put(mesh, idx_a),
            _put(mesh, idx_b),
            _put(mesh, val_a),
            _put(mesh, val_b),
        ]
        self._sends = [_put(mesh, a_send[d]) for d in a_offsets] + [
            _put(mesh, b_send[d]) for d in b_offsets
        ]
        nargs = 2 + len(self._plan_args)
        self._mapped = jax.jit(
            jax.shard_map(
                functools.partial(_mapped_add, spec=spec),
                mesh=mesh,
                in_specs=tuple(P(AXIS) for _ in range(nargs))
                + (P(), P())
                + tuple(P(AXIS) for _ in self._sends),
                out_specs=P(AXIS),
                check_vma=False,
            )
        )

    def __call__(self, a_store, b_store, alpha, beta):
        return self._mapped(
            a_store,
            b_store,
            *self._plan_args,
            jnp.float32(alpha),
            jnp.float32(beta),
            *self._sends,
        )


def dist_add(
    a: DistBSMatrix,
    b: DistBSMatrix,
    alpha=1.0,
    beta=1.0,
    cache: PlanCache | None = None,
) -> DistBSMatrix:
    """C = alpha*A + beta*B on resident stores; structure-union plan cached."""
    assert a.shape == b.shape and a.bs == b.bs, (a.shape, b.shape, a.bs, b.bs)
    tr = tracer_of(cache)
    key = ("add", _structure_key(a), _structure_key(b))
    build = lambda: AddExecutable(a, b)
    with tr.span("dist_add", cat="collective", nnzb_a=a.nnzb, nnzb_b=b.nnzb):
        exe = cache.get_or_build(key, build) if cache is not None else build()
        with tr.span("dispatch", cat="kernel", kind="add") as sp:
            store = exe(a.store, b.store, alpha, beta).astype(
                jnp.result_type(a.dtype, b.dtype)
            )
        if tr.enabled:
            sp.worker_costs = np.bincount(
                exe.c_owner, minlength=a.nparts
            ).astype(np.float64)
    return DistBSMatrix(
        shape=tuple(a.shape),
        bs=a.bs,
        coords=exe.c_coords,
        owner=exe.c_owner,
        slot=exe.c_slot,
        cap=exe.c_cap,
        store=store,
        mesh=a.mesh,
    )


def dist_scale(a: DistBSMatrix, alpha) -> DistBSMatrix:
    """alpha * A; purely local, no plan needed."""
    return a.scale(alpha)


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------


def _mapped_masked_trace(store, mask):
    acc = _acc_dtype(store.dtype)
    tr = jnp.einsum("cii->c", store[0].astype(acc))
    return jax.lax.psum(jnp.sum(tr * mask[0].astype(acc)), AXIS)


def _mapped_masked_sumsq(store, mask):
    acc = _acc_dtype(store.dtype)
    sq = jnp.sum(store[0].astype(acc) ** 2, axis=(1, 2))
    return jax.lax.psum(jnp.sum(sq * mask[0].astype(acc)), AXIS)


class _ReduceExecutable:
    def __init__(self, a: DistBSMatrix, body, mask: np.ndarray):
        self._mask = _put(a.mesh, mask)
        self._mapped = jax.jit(
            jax.shard_map(
                body,
                mesh=a.mesh,
                in_specs=(P(AXIS), P(AXIS)),
                out_specs=P(),
                check_vma=False,
            )
        )

    def __call__(self, store):
        return self._mapped(store, self._mask)


def _valid_mask(a: DistBSMatrix) -> np.ndarray:
    _, valid = a.store_maps()
    return valid.astype(np.float32)


def dist_trace(a: DistBSMatrix, cache: PlanCache | None = None) -> float:
    """trace(A): psum of masked per-device diagonal-block traces."""
    def build():
        mask = np.zeros((a.nparts, a.cap), dtype=np.float32)
        diag = a.coords[:, 0] == a.coords[:, 1]
        mask[a.owner[diag], a.slot[diag]] = 1.0
        return _ReduceExecutable(a, _mapped_masked_trace, mask)

    tr = tracer_of(cache)
    with tr.span("dist_trace", cat="collective", nnzb=a.nnzb):
        key = ("trace", _structure_key(a))
        exe = cache.get_or_build(key, build) if cache is not None else build()
        tr.counter("host_fetches").add()
        return float(exe(a.store))


def dist_frobenius_norm(a: DistBSMatrix, cache: PlanCache | None = None) -> float:
    """||A||_F: psum of per-device masked block sum-of-squares."""
    def build():
        return _ReduceExecutable(a, _mapped_masked_sumsq, _valid_mask(a))

    tr = tracer_of(cache)
    with tr.span("dist_fro", cat="collective", nnzb=a.nnzb):
        key = ("fro", _structure_key(a))
        exe = cache.get_or_build(key, build) if cache is not None else build()
        tr.counter("host_fetches").add()
        return float(np.sqrt(exe(a.store)))


# --------------------------------------------------------------------------
# truncation
# --------------------------------------------------------------------------


@jax.jit
def _block_norms_sq(store):
    return jnp.sum(store.astype(_acc_dtype(store.dtype)) ** 2, axis=(2, 3))


def _mapped_compact(store, gidx, gval):
    return (store[0][gidx[0]] * gval[0][:, None, None].astype(store.dtype))[None]


class _CompactExecutable:
    def __init__(self, a: DistBSMatrix, gidx: np.ndarray, gval: np.ndarray):
        self._args = [_put(a.mesh, gidx), _put(a.mesh, gval)]
        self._mapped = jax.jit(
            jax.shard_map(
                _mapped_compact,
                mesh=a.mesh,
                in_specs=(P(AXIS), P(AXIS), P(AXIS)),
                out_specs=P(AXIS),
                check_vma=False,
            )
        )

    def __call__(self, store):
        return self._mapped(store, *self._args)


def _compact_to_kept(
    a: DistBSMatrix,
    kept: np.ndarray,
    cache: PlanCache | None,
    *,
    coords: np.ndarray | None = None,
    shape: tuple[int, int] | None = None,
    kind: str = "truncate",
) -> DistBSMatrix:
    """Device-side compaction onto a kept subset of the block stack.

    Shared tail of both truncation variants and of the resident quadrant
    slice (:func:`dist_submatrix`): blocks keep their owners (slots just
    close ranks within each device), so compaction never moves block data
    between devices; the gather executable is cached per
    (structure, kept-set).  ``kept`` may carry any order — slots follow its
    order per owner, so slicers that re-sort shifted coordinates into Morton
    order preserve the store layout invariant.  ``coords`` / ``shape``
    override the result structure (slices shift coordinates and shrink the
    logical shape; the executable itself depends only on the kept set).
    """
    new_owner = a.owner[kept]
    new_slot, new_stores = _owner_slots(new_owner, a.nparts)
    new_cap = max(max((len(s) for s in new_stores), default=0), 1)
    gidx = np.zeros((a.nparts, new_cap), dtype=np.int32)
    gval = np.zeros((a.nparts, new_cap), dtype=np.float32)
    for p, s in enumerate(new_stores):
        old = a.slot[kept[s]]
        gidx[p, : len(s)] = old
        gval[p, : len(s)] = 1.0

    key = (kind, _structure_key(a), structure_fingerprint(kept))

    def build():
        exe = _CompactExecutable(a, gidx, gval)
        # host-side plan copy for static verification at cache admission
        # (repro.analysis.verify, kind="compact")
        exe._verify_plan = dict(
            kind="compact", label=kind, nparts=a.nparts,
            a_owner=np.asarray(a.owner), a_slot=np.asarray(a.slot),
            a_cap=a.cap, kept=np.asarray(kept, dtype=np.int64),
            new_owner=new_owner, new_slot=new_slot, new_cap=new_cap,
            gidx=gidx, gval=gval,
        )
        return exe

    exe = cache.get_or_build(key, build) if cache is not None else build()
    return DistBSMatrix(
        shape=tuple(a.shape) if shape is None else tuple(shape),
        bs=a.bs,
        coords=a.coords[kept] if coords is None else coords,
        owner=new_owner,
        slot=new_slot,
        cap=new_cap,
        store=exe(a.store),
        mesh=a.mesh,
    )


def dist_truncate(
    a: DistBSMatrix, tau: float, cache: PlanCache | None = None
) -> DistBSMatrix:
    """Drop smallest-norm blocks with sqrt(sum of dropped norms^2) <= tau.

    Block norms are computed on device (only the tiny [P, cap] norm table
    crosses to the host); the greedy global selection is the same error
    control as :func:`repro.core.truncate.truncate`; surviving blocks are
    compacted device-side and keep their owners, so truncation moves no
    block data between devices.
    """
    if a.nnzb == 0 or tau <= 0:
        return a
    tr = tracer_of(cache)
    # device fetch stays OUTSIDE the symbolic account (same rule as the
    # hierarchical path, which times only the descent)
    norms_sq = np.asarray(_block_norms_sq(a.store))  # [P, cap] -> host (small)
    if tr.enabled:
        tr.counter("host_fetches").add()
        tr.counter("norm_fetch_bytes").add(a.nnzb * 4)
    with timed_into(cache, "symbolic_s", tr, "truncate_select",
                    cat="symbolic", nnzb=a.nnzb):
        n_sq = norms_sq[a.owner, a.slot].astype(np.float64)
        order = np.argsort(n_sq)
        csum = np.sqrt(np.cumsum(n_sq[order]))
        ndrop = int(np.searchsorted(csum, tau, side="right"))
    if ndrop == 0:
        return a
    keep = np.ones(a.nnzb, dtype=bool)
    keep[order[:ndrop]] = False
    return _compact_to_kept(a, np.nonzero(keep)[0], cache)


# --------------------------------------------------------------------------
# transpose
# --------------------------------------------------------------------------


def transpose_permutation(coords: np.ndarray) -> np.ndarray:
    """``perm`` with ``perm[i]`` = source stack index of transposed block i.

    Pure structure: the transposed stack in Morton order pulls block ``i``
    from position ``perm[i]`` of the original stack.  Block Frobenius norms
    are transpose-invariant, so ``norms[perm]`` is the transposed matrix's
    norm table — callers holding a current table (the refinement loop in
    :mod:`repro.dist.inverse`) reuse it without a fresh device fetch.
    """
    return morton_sort(np.asarray(coords)[:, ::-1])


@dataclasses.dataclass(frozen=True)
class _TransposeSpec:
    nparts: int
    offsets: tuple


def _mapped_transpose(store, gidx, gval, *sends, spec):
    allb = _exchange_bufs(store[0], spec.offsets, sends, spec.nparts)
    out = allb[gidx[0]] * gval[0][:, None, None].astype(store.dtype)
    return jnp.transpose(out, (0, 2, 1))[None]


def _relayout_gather_plan(x: DistBSMatrix, out_owner: np.ndarray, src: np.ndarray):
    """Shared exchange-plan assembly of the owner re-layout collectives.

    Output stack position ``o`` lives on device ``out_owner[o]`` and pulls
    source block ``src[o]`` out of A's resident layout: blocks already local
    gather from the store, the rest travel via planned ``ppermute`` rounds
    (:func:`repro.core.schedule.plan_fetch`).  Transpose (``src`` = the
    transpose permutation) and repartition (``src`` = identity) both build
    their executables from this.  Returns ``(out_slot, out_cap, offsets,
    send, send_cnt, gidx, gval)``.
    """
    nparts = x.nparts
    out_slot, out_stores = _owner_slots(out_owner, nparts)
    out_cap = max(max((len(s) for s in out_stores), default=0), 1)
    needs = [
        np.unique(src[out_owner == p]) if np.any(out_owner == p)
        else np.zeros(0, np.int64)
        for p in range(nparts)
    ]
    offsets, send, send_cnt, recv = plan_fetch(x.owner, x.slot, needs, nparts)
    gidx = np.zeros((nparts, out_cap), dtype=np.int32)
    gval = np.zeros((nparts, out_cap), dtype=np.float32)
    for p, s in enumerate(out_stores):
        for local, o in enumerate(s):
            gidx[p, local] = local_fetch_index(
                x.owner, x.slot, offsets, send, recv, x.cap, src[o], p
            )
            gval[p, local] = 1.0
    return out_slot, out_cap, offsets, send, send_cnt, gidx, gval


def _relayout_verify_payload(x, src, out_owner, out_slot, out_cap, offsets,
                             send, send_cnt, gidx, gval, label):
    """Host-side copy of the relayout plan arrays, retained on executables
    so :func:`repro.analysis.verify.verify_value` can re-prove the gather
    at plan-cache admission (the device arrays are unverifiable post-put)."""
    return dict(
        kind="relayout", label=label, nparts=x.nparts,
        x_owner=np.asarray(x.owner), x_slot=np.asarray(x.slot), x_cap=x.cap,
        src=np.asarray(src), out_owner=np.asarray(out_owner),
        out_slot=np.asarray(out_slot), out_cap=out_cap, offsets=offsets,
        send=send, send_cnt=send_cnt, gidx=gidx, gval=gval,
    )


class TransposeExecutable:
    """Planned resident transpose bound to a mesh.

    Every transposed block *inherits its source block's owner* — the cut the
    operand currently has, uniform Morton or dynamically rebalanced, carries
    through unchanged.  That makes the transpose communication-free by
    construction (every gather is local; the planned ``ppermute`` machinery
    degenerates to zero rounds) and, after a rebalance, keeps the balancer's
    weighted cut instead of re-slotting back to the uniform Morton partition
    — which would both pay phantom migration bytes on every transpose and
    silently undo the migration the balancer just paid for.  Block data is
    transposed in the mapped body on gather.
    """

    def __init__(self, a: DistBSMatrix):
        nparts, mesh = a.nparts, a.mesh
        src = transpose_permutation(a.coords)  # out stack pos -> a stack idx
        out_owner = a.owner[src]  # inherit the operand's cut (zero movement)
        out_slot, out_cap, offsets, send, send_cnt, gidx, gval = (
            _relayout_gather_plan(a, out_owner, src)
        )
        self._verify_plan = _relayout_verify_payload(
            a, src, out_owner, out_slot, out_cap, offsets, send, send_cnt,
            gidx, gval, "transpose")
        # per-source true send counts (stats/trace attribution)
        self.sent_blocks = np.zeros(nparts, dtype=np.int64)
        for d in offsets:
            self.sent_blocks += send_cnt[d]

        self.src = src
        self.out_coords = a.coords[src][:, ::-1]
        self.out_owner = out_owner
        self.out_slot = out_slot
        self.out_cap = out_cap
        self.mesh = mesh
        spec = _TransposeSpec(nparts, offsets)
        self._args = [_put(mesh, gidx), _put(mesh, gval)]
        self._sends = [_put(mesh, send[d]) for d in offsets]
        nargs = 1 + len(self._args) + len(self._sends)
        self._mapped = jax.jit(
            jax.shard_map(
                functools.partial(_mapped_transpose, spec=spec),
                mesh=mesh,
                in_specs=tuple(P(AXIS) for _ in range(nargs)),
                out_specs=P(AXIS),
                check_vma=False,
            )
        )

    def __call__(self, store):
        return self._mapped(store, *self._args, *self._sends)


def dist_transpose(
    a: DistBSMatrix, cache: PlanCache | None = None
) -> DistBSMatrix:
    """A^T on the resident store; structure-keyed plan, no host gather.

    The result's owner layout inherits A's (each transposed block stays on
    the device that owns its source block), so the transpose is
    communication-free and a rebalanced cut survives it; downstream plan
    keys fingerprint the owner map, so plans re-key automatically.
    """
    tr = tracer_of(cache)
    key = ("transpose", _structure_key(a))
    build = lambda: TransposeExecutable(a)
    with tr.span("dist_transpose", cat="collective", nnzb=a.nnzb):
        exe = cache.get_or_build(key, build) if cache is not None else build()
        with tr.span("dispatch", cat="kernel", kind="transpose") as sp:
            store = exe(a.store)
        if tr.enabled:
            blk = a.bs * a.bs * a.store.dtype.itemsize
            shipped = int(exe.sent_blocks.sum())
            sp.args.update(sent_blocks=shipped)
            tr.counter("send_bytes").add(shipped * blk)
            tr.counter("recv_bytes").add(shipped * blk)
            # cost share: blocks each source ships, plus the local gather
            sp.worker_costs = exe.sent_blocks.astype(np.float64) + 1.0
    return DistBSMatrix(
        shape=(a.shape[1], a.shape[0]),
        bs=a.bs,
        coords=exe.out_coords,
        owner=exe.out_owner,
        slot=exe.out_slot,
        cap=exe.out_cap,
        store=store,
        mesh=a.mesh,
    )


# --------------------------------------------------------------------------
# repartition (owner re-layout)
# --------------------------------------------------------------------------


def _mapped_relayout(store, gidx, gval, *sends, spec):
    allb = _exchange_bufs(store[0], spec.offsets, sends, spec.nparts)
    return (allb[gidx[0]] * gval[0][:, None, None].astype(store.dtype))[None]


class RepartitionExecutable:
    """Planned owner re-layout bound to a mesh — the dynamic load balancer's
    data-motion primitive (:mod:`repro.dist.balance`).

    Re-slots every block to a caller-supplied new owner map using the same
    planned ``ppermute``-round machinery as :class:`TransposeExecutable`:
    blocks whose owner is unchanged are gathered from the local store, blocks
    that migrate travel device-to-device in the planned rounds — block
    payloads only, no host round-trip.  Coordinates and stack (Morton) order
    are untouched; slots are reassigned in ascending Morton order within each
    new owner, preserving the layout invariant every planner relies on.
    Downstream plans re-key automatically: every plan-cache key fingerprints
    the owner map, so the first operation after a re-layout plans fresh and
    the cache returns to all-hit once the layout stabilizes.
    """

    def __init__(self, x: DistBSMatrix, new_owner: np.ndarray):
        nparts, mesh = x.nparts, x.mesh
        new_owner = np.asarray(new_owner, dtype=np.int32)
        assert new_owner.shape == (x.nnzb,)
        assert new_owner.size == 0 or (
            new_owner.min() >= 0 and new_owner.max() < nparts
        ), "owner map must assign every block a device id < mesh size"
        src = np.arange(x.nnzb, dtype=np.int64)  # re-layout, not a permutation
        new_slot, new_cap, offsets, send, send_cnt, gidx, gval = (
            _relayout_gather_plan(x, new_owner, src)
        )
        self._verify_plan = _relayout_verify_payload(
            x, src, new_owner, new_slot, new_cap, offsets, send, send_cnt,
            gidx, gval, "repartition")

        self.new_owner = new_owner
        self.new_slot = new_slot
        self.new_cap = new_cap
        self.migrated_blocks = int(np.count_nonzero(new_owner != x.owner))
        # per-source true send counts (stats): only migrating blocks ship
        self.sent_blocks = np.zeros(nparts, dtype=np.int64)
        for d in offsets:
            self.sent_blocks += send_cnt[d]
        self.mesh = mesh
        spec = _TransposeSpec(nparts, offsets)
        self._args = [_put(mesh, gidx), _put(mesh, gval)]
        self._sends = [_put(mesh, send[d]) for d in offsets]
        nargs = 1 + len(self._args) + len(self._sends)
        self._mapped = jax.jit(
            jax.shard_map(
                functools.partial(_mapped_relayout, spec=spec),
                mesh=mesh,
                in_specs=tuple(P(AXIS) for _ in range(nargs)),
                out_specs=P(AXIS),
                check_vma=False,
            )
        )

    def __call__(self, store):
        return self._mapped(store, *self._args, *self._sends)


def dist_repartition(
    x: DistBSMatrix,
    new_owner: np.ndarray,
    cache: PlanCache | None = None,
    *,
    stats: dict | None = None,
) -> DistBSMatrix:
    """Re-slot A's blocks to ``new_owner`` entirely on device.

    The resident re-layout collective of the dynamic load-balancing
    subsystem (:mod:`repro.dist.balance`): structure, values and Morton stack
    order are preserved bit-for-bit (``gather()`` before and after are
    identical, and so is the stack-order norm table — block values never
    change, only which device holds them), so a re-layout between iterations
    is invisible to the algorithm and only visible to the schedule.  The
    executable is cached per (structure + old owner, new owner); a no-op map
    (``new_owner == x.owner``) returns ``x`` unchanged without touching the
    cache.

    ``stats``, when a dict, receives ``migrated_blocks`` / ``migrated_bytes``
    (blocks that actually changed owner — the planned rounds ship nothing
    else) and ``sent_blocks_per_worker``.
    """
    new_owner = np.asarray(new_owner, dtype=np.int32)
    if x.nnzb == 0 or np.array_equal(new_owner, x.owner):
        if stats is not None:
            stats["migrated_blocks"] = 0
            stats["migrated_bytes"] = 0
            stats["sent_blocks_per_worker"] = np.zeros(x.nparts, dtype=np.int64)
        return x
    tr = tracer_of(cache)
    key = (
        "repartition",
        _structure_key(x),
        structure_fingerprint(new_owner),
    )
    build = lambda: RepartitionExecutable(x, new_owner)
    blk = x.bs * x.bs * x.store.dtype.itemsize
    with tr.span("dist_repartition", cat="migration", nnzb=x.nnzb) as msp:
        exe = cache.get_or_build(key, build) if cache is not None else build()
        if stats is not None:
            stats["migrated_blocks"] = exe.migrated_blocks
            stats["migrated_bytes"] = exe.migrated_blocks * blk
            stats["sent_blocks_per_worker"] = exe.sent_blocks.copy()
        with tr.span("dispatch", cat="kernel", kind="repartition") as sp:
            store = exe(x.store)
        if tr.enabled:
            msp.args.update(migrated_blocks=exe.migrated_blocks)
            tr.counter("migrated_bytes").add(exe.migrated_blocks * blk)
            # cost share: blocks each source ships, plus the local gather
            sp.worker_costs = exe.sent_blocks.astype(np.float64) + 1.0
    return DistBSMatrix(
        shape=tuple(x.shape),
        bs=x.bs,
        coords=x.coords,
        owner=exe.new_owner,
        slot=exe.new_slot,
        cap=exe.new_cap,
        store=store,
        mesh=x.mesh,
    )


# --------------------------------------------------------------------------
# quadrant slice / assemble
# --------------------------------------------------------------------------


def dist_submatrix(
    a: DistBSMatrix,
    r0: int,
    r1: int,
    c0: int,
    c1: int,
    cache: PlanCache | None = None,
) -> DistBSMatrix:
    """Block-range slice a[r0:r1, c0:c1] on the resident store.

    The resident counterpart of :func:`repro.core.inverse.submatrix`: the
    kept set is an owner-local coordinate mask decided on the host, the data
    motion is the shared device-side compaction (:func:`_compact_to_kept`) —
    blocks keep their owners, so slicing moves nothing between devices.
    """
    m = (
        (a.coords[:, 0] >= r0)
        & (a.coords[:, 0] < r1)
        & (a.coords[:, 1] >= c0)
        & (a.coords[:, 1] < c1)
    )
    kept = np.nonzero(m)[0]
    new_coords = a.coords[kept] - np.array([[r0, c0]])
    # quadrant offsets strip a shared Morton prefix, which preserves relative
    # order; re-sort anyway so arbitrary ranges keep the layout invariant
    order = morton_sort(new_coords)
    kept, new_coords = kept[order], new_coords[order]
    rows = min((r1 - r0) * a.bs, max(a.shape[0] - r0 * a.bs, 0))
    cols = min((c1 - c0) * a.bs, max(a.shape[1] - c0 * a.bs, 0))
    return _compact_to_kept(
        a, kept, cache, coords=new_coords, shape=(rows, cols), kind="slice"
    )


def _mapped_assemble(s0, s1, s2, s3, gidx, gval):
    allb = jnp.concatenate([s0[0], s1[0], s2[0], s3[0]], axis=0)
    return (allb[gidx[0]] * gval[0][:, None, None].astype(allb.dtype))[None]


class AssembleExecutable:
    """Planned 2x2 quadrant glue bound to a mesh.

    Every output block is one quadrant's block on the device that already
    owns it — the local buffer is just the four quadrant stores concatenated
    — so assembly performs zero inter-device communication; only the merged
    slot maps are rebuilt on the host.
    """

    def __init__(self, quads, offsets_rc, mesh):
        nparts = int(mesh.devices.size)
        coords, owner, src_q, src_i = [], [], [], []
        for qi, (q, (dr, dc)) in enumerate(zip(quads, offsets_rc)):
            if q.nnzb:
                coords.append(q.coords + np.array([[dr, dc]]))
                owner.append(q.owner)
                src_q.append(np.full(q.nnzb, qi, dtype=np.int64))
                src_i.append(np.arange(q.nnzb, dtype=np.int64))
        if coords:
            coords = np.concatenate(coords)
            owner = np.concatenate(owner)
            src_q = np.concatenate(src_q)
            src_i = np.concatenate(src_i)
        else:
            coords = np.zeros((0, 2), dtype=np.int64)
            owner = np.zeros((0,), dtype=np.int32)
            src_q = src_i = np.zeros((0,), dtype=np.int64)
        order = morton_sort(coords)
        coords, owner = coords[order], owner[order]
        src_q, src_i = src_q[order], src_i[order]
        out_slot, out_stores = _owner_slots(owner, nparts)
        out_cap = max(max((len(s) for s in out_stores), default=0), 1)

        base = np.concatenate([[0], np.cumsum([q.cap for q in quads])])[:-1]
        gidx = np.zeros((nparts, out_cap), dtype=np.int32)
        gval = np.zeros((nparts, out_cap), dtype=np.float32)
        for p, s in enumerate(out_stores):
            for local, o in enumerate(s):
                q = quads[src_q[o]]
                gidx[p, local] = base[src_q[o]] + q.slot[src_i[o]]
                gval[p, local] = 1.0

        self.out_coords = coords
        self.out_owner = owner
        self.out_slot = out_slot
        self.out_cap = out_cap
        self._args = [_put(mesh, gidx), _put(mesh, gval)]
        self._mapped = jax.jit(
            jax.shard_map(
                _mapped_assemble,
                mesh=mesh,
                in_specs=tuple(P(AXIS) for _ in range(6)),
                out_specs=P(AXIS),
                check_vma=False,
            )
        )

    def __call__(self, *stores):
        return self._mapped(*stores, *self._args)


def dist_assemble2x2(
    a00: DistBSMatrix,
    a01: DistBSMatrix,
    a10: DistBSMatrix,
    a11: DistBSMatrix,
    split: int,
    cache: PlanCache | None = None,
) -> DistBSMatrix:
    """Glue four resident quadrants at block offset ``split``.

    Inverse of :func:`dist_submatrix` over a quadtree split; blocks keep
    their owners, so nothing moves between devices (empty quadrants — the
    zero branches of the factorization — contribute padding only).
    """
    quads = (a00, a01, a10, a11)
    bs = a00.bs
    assert all(q.bs == bs for q in quads)
    shape = (a00.shape[0] + a11.shape[0], a00.shape[1] + a11.shape[1])
    offsets_rc = ((0, 0), (0, split), (split, 0), (split, split))
    key = (
        "assemble",
        tuple(_structure_key(q) for q in quads),
        tuple(tuple(q.shape) for q in quads),
        int(split),
    )
    build = lambda: AssembleExecutable(quads, offsets_rc, a00.mesh)
    exe = cache.get_or_build(key, build) if cache is not None else build()
    dtype = jnp.result_type(*(q.dtype for q in quads))
    store = exe(*(q.store.astype(dtype) for q in quads))
    return DistBSMatrix(
        shape=shape,
        bs=bs,
        coords=exe.out_coords,
        owner=exe.out_owner,
        slot=exe.out_slot,
        cap=exe.out_cap,
        store=store,
        mesh=a00.mesh,
    )


def dist_truncate_hierarchical(
    a: DistBSMatrix,
    tau: float,
    cache: PlanCache | None = None,
    *,
    norms: np.ndarray | None = None,
    stats: dict | None = None,
) -> DistBSMatrix:
    """Truncate by dropping whole quadtree subtrees first — resident variant.

    Builds a :class:`~repro.core.quadtree.QuadtreeIndex` from the resident
    per-block norm table (one tiny [P, cap] device->host transfer, or zero
    when ``norms`` is supplied by a caller that already fetched it) and runs
    the same top-down subtree-drop descent as
    :func:`repro.core.truncate.truncate_hierarchical` — identical kept set on
    identical inputs, same global guarantee ``||A - T(A)||_F <= tau``, and a
    subtree dropped at level L is removed without its leaves ever being
    enumerated.  Survivors are compacted device-side keeping their owners, so
    no block data moves between devices.

    ``stats``, when a dict, receives ``nodes_visited`` (frontier nodes whose
    norms the descent examined) and ``kept`` (surviving stack indices) — the
    SP2 driver uses ``kept`` to carry the norm table forward to the next
    iteration's SpAMM without a fresh fetch.
    """
    if stats is not None:
        stats["nodes_visited"] = 0
        stats["kept"] = np.arange(a.nnzb, dtype=np.int64)
    if a.nnzb == 0 or tau <= 0:
        return a
    if norms is None:
        # outside the symbolic timer: a miss on the fused norm executable is
        # timed into cache.build_s by get_or_build
        norms = resident_block_norms(a, cache)
    with timed_into(cache, "symbolic_s", tracer_of(cache), "hierarchical_drop",
                    cat="symbolic", nnzb=a.nnzb):
        depth = quadtree_depth(-(-a.shape[0] // a.bs), -(-a.shape[1] // a.bs))
        qt = build_quadtree_index(a.coords, norms, depth=depth)
        keep, visited = hierarchical_drop_mask(qt, tau)
    if stats is not None:
        stats["nodes_visited"] = visited
    if keep.all():
        return a
    kept = np.nonzero(keep)[0]
    if stats is not None:
        stats["kept"] = kept
    return _compact_to_kept(a, kept, cache)
