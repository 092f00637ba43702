"""Distributed SpGEMM execution via shard_map (SPMD side of the schedule).

The host-side :class:`~repro.core.schedule.SpgemmPlan` becomes arrays sharded
over a 1-D "worker" mesh axis; inside shard_map each device sees its own task
list and exchange slots.  Two exchange modes:

* ``p2p``: one ``lax.ppermute`` round per active ring offset — only blocks
  actually referenced by remote tasks move (the paper's locality claim).
  For banded matrices under Morton placement only neighbour offsets appear,
  so the lowered HLO contains exactly the neighbour collective-permutes.
* ``allgather``: the baseline — both operands fully replicated with
  ``lax.all_gather`` (what random-permutation schemes effectively pay).

Numeric phase inside the mapped function is the fused leaf engine (Pallas
kernel on TPU, segment-sum oracle elsewhere), whose padded and masked tasks
contribute nothing in place; the staged paths send them to a trash row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.ops import FUSED_MAX_TASKS, task_chunks
from repro.kernels.precision import FP32, Precision

from .matrix import BSMatrix
from .schedule import SpgemmPlan

__all__ = [
    "make_worker_mesh",
    "dist_spgemm",
    "shard_stores",
    "unshard_result",
    "make_spgemm_executable",
    "SpgemmExecutable",
    "make_masked_spgemm_executable",
    "MaskedSpgemmExecutable",
    "make_fused_spgemm_executable",
    "FusedSpgemmExecutable",
    "make_masked_fused_spgemm_executable",
    "MaskedFusedSpgemmExecutable",
]

AXIS = "worker"


def make_worker_mesh(nworkers: int | None = None) -> Mesh:
    devs = np.array(jax.devices())
    nworkers = nworkers or devs.size
    return Mesh(devs[:nworkers].reshape(nworkers), (AXIS,))


def shard_stores(plan: SpgemmPlan, a_data: jax.Array, b_data: jax.Array):
    """Gather global block stacks into per-device padded stores [P, cap, bs, bs]."""
    av = jnp.asarray(plan.a_store_valid)[..., None, None]
    bv = jnp.asarray(plan.b_store_valid)[..., None, None]
    a_store = a_data[jnp.asarray(plan.a_store_idx)] * av.astype(a_data.dtype)
    b_store = b_data[jnp.asarray(plan.b_store_idx)] * bv.astype(b_data.dtype)
    return a_store, b_store


def _exchange_bufs(store, offsets, send_pads, nparts):
    """Run the planned ppermute rounds; return device-local operand buffer."""
    bufs = [store]
    for d, send in zip(offsets, send_pads):
        payload = store[send[0]]  # [cap_d, bs, bs]
        perm = [(p, (p + d) % nparts) for p in range(nparts)]
        recv = jax.lax.ppermute(payload, AXIS, perm=perm)
        bufs.append(recv)
    return jnp.concatenate(bufs, axis=0) if len(bufs) > 1 else store


def _assemble_operands(a_store, b_store, a_and_b_sends, plan: SpgemmPlan):
    """Device-local operand buffers per the plan's exchange mode."""
    na = len(plan.a_offsets)
    a_sends = a_and_b_sends[:na]
    b_sends = a_and_b_sends[na:]
    if plan.exchange == "p2p":
        a_all = _exchange_bufs(a_store[0], plan.a_offsets, a_sends, plan.nparts)
        # A @ A on one store: the shared exchange serves both operands
        b_all = a_all if plan.exchange_shared else _exchange_bufs(
            b_store[0], plan.b_offsets, b_sends, plan.nparts)
    else:  # allgather baseline
        a_all = jax.lax.all_gather(a_store[0], AXIS).reshape(
            -1, *a_store.shape[-2:]
        )
        b_all = jax.lax.all_gather(b_store[0], AXIS).reshape(
            -1, *b_store.shape[-2:]
        )
    return a_all, b_all


def _block_spmm_fn(impl: str):
    if impl == "kernel":
        from repro.kernels import ops as kops

        return kops.block_spmm
    from repro.kernels import ref as kref

    return kref.block_spmm_ref


def _exchange_stack(store, offsets, send_pads, nparts, keeps=None, live=None):
    """Planned ppermute rounds -> stacked receive buffers [R, capU, bs, bs].

    Unlike :func:`_exchange_bufs` the device's own store is NOT copied into
    an operand buffer — the fused kernel reads it in place — and each round's
    receive buffer is padded to the uniform ``capU`` so the stack is one
    array the kernel indexes by ``(round, row)``.  Padding happens locally,
    *after* the ppermute: the wire payload stays the round's true capacity.

    ``keeps``: optional per-round ``[1, cap_d]`` bool — send slots whose
    block no live task references ship zeros (delta-plan exchange pruning).
    ``live``: optional collection of round indices to run at all; dead
    rounds (every slot masked) produce zeros with no collective.
    """
    shape = store.shape[-2:]
    if len(offsets) == 0:
        # dummy stack: the kernel's recv branch prefetches row (0, 0) and the
        # select discards it (src is all-zero when there are no rounds)
        return jnp.zeros((1, 1) + shape, store.dtype)
    capU = max(send.shape[1] for send in send_pads)
    bufs = []
    for r, (d, send) in enumerate(zip(offsets, send_pads)):
        if live is not None and r not in live:
            bufs.append(jnp.zeros((capU,) + shape, store.dtype))
            continue
        payload = store[send[0]]  # [cap_d, bs, bs]
        if keeps is not None:
            payload = payload * keeps[r][0][:, None, None].astype(store.dtype)
        perm = [(p, (p + d) % nparts) for p in range(nparts)]
        recv = jax.lax.ppermute(payload, AXIS, perm=perm)
        pad = capU - recv.shape[0]
        if pad:
            recv = jnp.pad(recv, ((0, pad), (0, 0), (0, 0)))
        bufs.append(recv)
    return jnp.stack(bufs, axis=0)


def _own_stores(a_store, b_store, plan: SpgemmPlan, precision: Precision):
    """This device's operand stores, cast before the exchange for bf16 (which
    halves the ppermute payload too).  A shared exchange squares A, so its
    B operand is A's store."""
    a_own = a_store[0]
    b_own = a_own if plan.exchange_shared else b_store[0]
    if precision.mode == "bf16":
        a_own = a_own.astype(jnp.bfloat16)
        b_own = a_own if plan.exchange_shared else b_own.astype(jnp.bfloat16)
    return a_own, b_own


def _fused_spmm_fn(impl: str):
    from repro.kernels import ops as kops

    if impl == "fused-interpret":  # force the Pallas interpreter (tests)
        return functools.partial(kops.fused_block_spmm, interpret=True)
    assert impl == "fused", impl
    return kops.fused_block_spmm


def _run_starts(c: np.ndarray) -> np.ndarray:
    """Positions where a new run of equal ``c`` (one output row) begins."""
    if not c.size:
        return np.zeros(0, np.int64)
    return np.concatenate([[0], np.nonzero(np.diff(c))[0] + 1])


def _chunk_slots(
    cs: list[np.ndarray], max_tasks: int, lead_empty: bool = False
) -> tuple[np.ndarray, np.ndarray, tuple[int, ...]]:
    """Cut each device's ``c`` sequence into the same number ``K`` of
    kernel-call chunks at run boundaries, chunk ``k`` padded to the common
    length ``L_k``.  Returns ``(pos, real, lengths)``: ``pos [P, sum(L)]``
    is the position in ``cs[p]`` each slot repeats (``-1`` in an empty
    chunk), ``real`` marks slots that are that position and not padding.
    A device with fewer runs than ``K`` gets one run per chunk and its empty
    chunks last, or first with ``lead_empty``."""
    nparts = len(cs)
    runs = [_run_starts(c) for c in cs]
    cuts = [len(task_chunks(c, max_tasks)) if c.size else 0 for c in cs]
    K = max(max(cuts), 1)
    bounds = []
    for p in range(nparts):
        n, st = cs[p].size, runs[p]
        if st.size < K:  # one run per chunk, and empty chunks
            if lead_empty:
                b = [0] * (K - st.size) + list(st) + [n]
            else:
                b = list(st) + [n] * (K + 1 - st.size)
        else:
            # greedy cuts, held back so every later chunk keeps >= one run
            b, cur = [0], 0
            for k in range(K - 1):
                e = int(np.searchsorted(st, b[-1] + max_tasks, "right")) - 1
                e = min(e, st.size - (K - 1 - k))
                assert e > cur, (p, k)
                cur = e
                b.append(int(st[e]))
            b.append(n)
        bounds.append(np.asarray(b, np.int64))
    spans = np.stack([np.diff(b) for b in bounds])  # [P, K]
    lengths = tuple(int(x) for x in np.maximum(spans.max(axis=0), 1))
    assert max(lengths) <= max_tasks, lengths
    pos = np.full((nparts, sum(lengths)), -1, np.int64)
    real = np.zeros(pos.shape, bool)
    off = 0
    for k, L in enumerate(lengths):
        for p in range(nparts):
            lo, n = int(bounds[p][k]), int(spans[p, k])
            if n:
                pos[p, off : off + n] = np.arange(lo, lo + n)
                pos[p, off + n : off + L] = lo + n - 1
                real[p, off : off + n] = True
        off += L
    return pos, real, lengths


def _local_first_split(task_c, task_count, local):
    """Per-device ``(phase-1 tasks, phase-2 tasks)`` of the two-phase
    layout, or ``None`` when it does not apply.

    Phase 1 takes C rows whose every task reads only owned blocks, in
    ascending order, as many whole runs as fit in ``L1`` tasks: the
    smallest device's all-local task count, so every device fills about
    the same ``L1`` slots.  The rest (remote rows, and a device's surplus
    local rows) is phase 2, which keeps at least one run on every device.
    No split when no task reads a received block (no exchange to hide) or
    some device has no all-local row (``L1 == 0``).
    """
    nparts = task_c.shape[0]
    rows = []
    for p in range(nparts):
        n = int(task_count[p])
        st = _run_starts(task_c[p, :n])
        ln, loc = np.diff(np.append(st, n)), local[p, :n]
        row_local = np.logical_and.reduceat(loc, st) if n else np.zeros(0, bool)
        rows.append((ln, row_local))
    if all(ln[~rl].size == 0 for ln, rl in rows):
        return None
    L1 = min(int(ln[rl].sum()) for ln, rl in rows)
    if L1 == 0:
        return None
    split = []
    for ln, rl in rows:
        # whole local runs, ascending, while the prefix fits in L1
        take = rl & (np.cumsum(np.where(rl, ln, 0)) <= L1)
        if take.all():  # phase 2 needs a run on every device
            take[-1] = False
        first = np.repeat(take, ln)
        split.append((np.nonzero(first)[0], np.nonzero(~first)[0]))
    return split


def fused_task_layout(
    task_c: np.ndarray,
    task_count: np.ndarray,
    c_cap: int,
    max_tasks: int,
    local: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, tuple[int, ...], int]:
    """Lay each device's task list out in kernel-call chunks.

    One SMEM-bounded kernel call carries at most ``max_tasks`` tasks, and
    every device of an SPMD program runs the same calls, so each device's
    ``[task_count]`` list is cut at ``c``-run boundaries into the same number
    ``K`` of chunks, chunk ``k`` padded to the common length ``L_k``.
    Returns ``(idx, real, lengths, n_local)``: ``idx [P, sum(L)]`` is the
    device task each slot repeats, ``real`` marks slots that are that task
    and not padding, ``lengths`` is ``(L_0, ..., L_{K-1})``, and the first
    ``n_local`` chunks are phase 1 (below).

    Padding contributes nothing (its ``on`` flag is clear) and never
    changes the output row: it repeats its chunk's last task, so that row
    is neither re-zeroed nor left.  A device cut into fewer than ``K``
    chunks has fewer runs than ``K``, hence fewer valid rows than the
    device with the most chunks — so ``c_cap - 1`` is a padding row there,
    and its empty chunks (``idx == -1``) point at it.

    Two phases, from ``local`` (``[P, t_cap]``: the task reads only owned
    blocks): phase 1 holds C rows whose every task is local, so its calls
    need no received block and can run while the exchange is in flight;
    phase 2 holds the rest (:func:`_local_first_split`).  Each C row stays
    in one call, in its original task order, so C is bit-identical; an
    empty chunk points at the device's next laid-out row, which a later
    call zeroes again.  With no split ``n_local`` is 0 and the layout is the
    single-phase one above.
    """
    nparts = task_c.shape[0]
    split = None if local is None else _local_first_split(
        task_c, task_count, local)
    if split is None:
        cs = [task_c[p, : int(task_count[p])] for p in range(nparts)]
        idx, real, lengths = _chunk_slots(cs, max_tasks)
        return idx, real, lengths, 0
    idx, real, phases = [], [], []
    for phase in range(2):
        seqs = [s[phase] for s in split]
        pos, r, ln = _chunk_slots(
            [task_c[p, seqs[p]] for p in range(nparts)], max_tasks,
            lead_empty=True)
        # position -1 (an empty chunk) picks the appended -1
        idx.append(np.stack([np.append(seqs[p], -1)[pos[p]] for p in range(nparts)]))
        real.append(r)
        phases.append(ln)
    lengths, n_local = phases[0] + phases[1], len(phases[0])
    idx, real = np.concatenate(idx, axis=1), np.concatenate(real, axis=1)
    n1 = sum(lengths[:n_local])
    for p in range(nparts):
        # every device ends on a phase-2 run, so each empty chunk has a
        # later laid-out row to point at; phase 2 zeroes that row again
        laid = np.nonzero(idx[p] >= 0)[0]
        assert laid.size and real[p, n1:].any(), p
        idx[p] = idx[p, laid[np.searchsorted(laid, np.arange(idx.shape[1]))]]
        first, rest = idx[p, :n1][real[p, :n1]], idx[p, n1:][real[p, n1:]]
        # phase 1 reads only owned blocks, and no row is in both phases
        assert local[p, first].all(), p
        assert not np.intersect1d(task_c[p, first], task_c[p, rest]).size, p
    return idx, real, lengths, n_local


def _take_layout(x: np.ndarray, idx: np.ndarray, fill) -> np.ndarray:
    """``x[p, idx[p]]`` per device, ``fill`` where ``idx == -1``."""
    got = np.take_along_axis(x, np.maximum(idx, 0), axis=1)
    return np.where(idx >= 0, got, fill).astype(x.dtype)


def fused_task_arrays(
    plan: SpgemmPlan, idx: np.ndarray, n_before: int
) -> list[np.ndarray]:
    """The laid-out ``[a_src, a_off, b_src, b_off, c]`` task arrays of a
    :func:`fused_task_layout`, whose first ``n_before`` slots are phase 1.

    An empty phase-1 chunk repeats a phase-2 row, which may be remote; its
    slots read the own store's row 0 instead, as phase 1 has no receive
    buffer.  So every phase-1 slot reads ``src == 0``.
    """
    lay = lambda x, fill: _take_layout(x, idx, fill)
    arrays = [
        lay(plan.task_a_src, 0),
        lay(plan.task_a_off, 0),
        lay(plan.task_b_src, 0),
        lay(plan.task_b_off, 0),
        lay(plan.task_c, plan.c_cap - 1),
    ]
    remote = (arrays[0][:, :n_before] != 0) | (arrays[2][:, :n_before] != 0)
    for x in arrays[:4]:
        x[:, :n_before][remote] = 0
    return arrays


def _fused_phases(
    spmm, a_own, a_recv, b_own, b_recv, tasks, num_out, phases, *,
    on, low=None, adaptive=False,
):
    """Run the fused engine over the layout's phases, threading C through
    ``acc``.  With two phases the first reads only the own stores: its calls
    get dummy receive stacks, so none of its operands waits on a ppermute
    and the exchange can be in flight while it runs."""
    c, s = None, 0
    for k, chunks in enumerate(phases):
        n = sum(chunks)
        sl = lambda x: None if x is None else x[s : s + n]
        ar, br = a_recv, b_recv
        if k + 1 < len(phases):
            ar = jnp.zeros((1, 1) + a_own.shape[-2:], a_own.dtype)
            br = jnp.zeros((1, 1) + b_own.shape[-2:], b_own.dtype)
        c = spmm(
            a_own, ar, b_own, br, *map(sl, tasks), num_out,
            on=sl(on), low=sl(low), adaptive=adaptive, chunks=chunks, acc=c,
        )
        s += n
    return c


def _mapped_multiply_fused(
    a_store,
    b_store,
    a_src_t,
    a_off_t,
    b_src_t,
    b_off_t,
    task_c,
    task_on,
    *a_and_b_sends,
    plan: SpgemmPlan,
    impl: str,
    precision: Precision,
    phases: tuple[tuple[int, ...], ...],
):
    """Fused per-device body: exchange -> fused unpack+GEMM+accumulate
    dispatches over (own store | stacked receive buffers), writing the
    ``c_cap`` result rows in place.  No concatenated operand buffer and no
    trash row are materialized."""
    na = len(plan.a_offsets)
    a_sends, b_sends = a_and_b_sends[:na], a_and_b_sends[na:]
    a_own, b_own = _own_stores(a_store, b_store, plan, precision)
    a_recv = _exchange_stack(a_own, plan.a_offsets, a_sends, plan.nparts)
    b_recv = a_recv if plan.exchange_shared else _exchange_stack(
        b_own, plan.b_offsets, b_sends, plan.nparts)
    tasks = (a_src_t[0], a_off_t[0], b_src_t[0], b_off_t[0], task_c[0])
    c = _fused_phases(
        _fused_spmm_fn(impl), a_own, a_recv, b_own, b_recv, tasks,
        plan.c_cap, phases, on=task_on[0],
    )
    return c[None]


def _mapped_multiply_fused_masked(
    a_store,
    b_store,
    a_src_t,
    a_off_t,
    b_src_t,
    b_off_t,
    task_c,
    task_on,
    task_low,
    *keeps_and_sends,
    plan: SpgemmPlan,
    impl: str,
    precision: Precision,
    phases: tuple[tuple[int, ...], ...],
    live_a: tuple[int, ...],
    live_b: tuple[int, ...],
):
    """Masked fused body: off tasks contribute nothing, send slots feeding
    only off tasks ship zeros, and rounds with every slot masked skip their
    collective entirely.  ``task_low`` drives adaptive per-task rounding."""
    na, nb = len(plan.a_offsets), len(plan.b_offsets)
    a_keeps = keeps_and_sends[:na]
    b_keeps = keeps_and_sends[na : na + nb]
    a_sends = keeps_and_sends[na + nb : 2 * na + nb]
    b_sends = keeps_and_sends[2 * na + nb :]
    a_own, b_own = _own_stores(a_store, b_store, plan, precision)
    a_recv = _exchange_stack(
        a_own, plan.a_offsets, a_sends, plan.nparts, keeps=a_keeps, live=live_a
    )
    b_recv = a_recv if plan.exchange_shared else _exchange_stack(
        b_own, plan.b_offsets, b_sends, plan.nparts, keeps=b_keeps, live=live_b
    )
    adaptive = precision.mode == "adaptive"
    tasks = (a_src_t[0], a_off_t[0], b_src_t[0], b_off_t[0], task_c[0])
    c = _fused_phases(
        _fused_spmm_fn(impl), a_own, a_recv, b_own, b_recv, tasks,
        plan.c_cap, phases, on=task_on[0],
        low=task_low[0] if adaptive else None, adaptive=adaptive,
    )
    return c[None]


def _mapped_multiply(
    a_store,
    b_store,
    task_a,
    task_b,
    task_c,
    *a_and_b_sends,
    plan: SpgemmPlan,
    impl: str,
):
    """Per-device body. Leading dim of every arg is this device's slice (1)."""
    a_all, b_all = _assemble_operands(a_store, b_store, a_and_b_sends, plan)
    num_out = plan.c_cap + 1  # trash row for padded tasks
    c = _block_spmm_fn(impl)(a_all, b_all, task_a[0], task_b[0], task_c[0], num_out)
    return c[None, : plan.c_cap]


def _mapped_multiply_masked(
    a_store,
    b_store,
    task_a,
    task_b,
    task_c,
    task_on,
    *a_and_b_sends,
    plan: SpgemmPlan,
    impl: str,
):
    """Masked multiply body: tasks with ``task_on`` False are redirected to the
    trash row — the same mechanism padding already uses — so one compiled
    program serves every prune pattern over a fixed structure."""
    a_all, b_all = _assemble_operands(a_store, b_store, a_and_b_sends, plan)
    num_out = plan.c_cap + 1
    tc = jnp.where(task_on[0], task_c[0], plan.c_cap)
    c = _block_spmm_fn(impl)(a_all, b_all, task_a[0], task_b[0], tc, num_out)
    return c[None, : plan.c_cap]


class SpgemmExecutable:
    """A planned multiply bound to a mesh, with plan arrays device-resident.

    The host index arrays (task lists, send slots) are shipped to the mesh
    once at construction; every subsequent ``__call__`` only touches the
    operand stores — when those are already resident (``repro.dist``), an
    iteration moves no host data at all.  The jitted ``shard_map`` program is
    cached on this object, so repeated calls skip tracing and compilation —
    together these are the chunk-cache analogue of the paper's runtime.
    """

    # subclasses swap the mapped body and declare how many extra per-call
    # sharded arguments it takes between the plan index arrays and the sends
    _body = staticmethod(_mapped_multiply)
    _n_runtime_args = 0

    def __init__(
        self, plan: SpgemmPlan, mesh: Mesh, *, impl: str = "ref", **body_kwargs
    ):
        if mesh.devices.size != plan.nparts:
            from ..analysis.errors import PlanError

            raise PlanError(
                f"plan partitions over {plan.nparts} workers but the mesh "
                f"has {mesh.devices.size} devices")
        self.plan = plan
        self.mesh = mesh
        self.impl = impl
        self._body_kwargs = body_kwargs
        self._sh = NamedSharding(mesh, P(AXIS))
        put = lambda x: jax.device_put(x, self._sh)
        self._idx_args = [put(x) for x in self._plan_index_arrays(plan)]
        self._send_args = [put(plan.a_send[d]) for d in plan.a_offsets]
        self._send_args += [put(plan.b_send[d]) for d in plan.b_offsets]
        self._mapped = self._build_program()

    @staticmethod
    def _plan_index_arrays(plan: SpgemmPlan) -> list[np.ndarray]:
        return [plan.task_a, plan.task_b, plan.task_c]

    def _n_runtime(self, plan: SpgemmPlan) -> int:
        return self._n_runtime_args

    def _build_program(self, **extra):
        """Jit the shard_mapped body; subclasses pass per-program statics
        (e.g. the live-round sets of a pruned exchange) via ``extra``."""
        fn = functools.partial(
            type(self)._body,
            plan=self.plan,
            impl=self.impl,
            **{**self._body_kwargs, **extra},
        )
        nargs = (
            2 + len(self._idx_args) + self._n_runtime(self.plan) + len(self._send_args)
        )
        return jax.jit(
            jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=tuple(P(AXIS) for _ in range(nargs)),
                out_specs=P(AXIS),
                check_vma=False,
            )
        )

    def __call__(self, a_store: jax.Array, b_store: jax.Array) -> jax.Array:
        """Run on per-device padded stores [P, cap, bs, bs]; returns C stores."""
        return self._mapped(a_store, b_store, *self._idx_args, *self._send_args)


def make_spgemm_executable(
    plan: SpgemmPlan, mesh: Mesh | None = None, *, impl: str = "ref"
) -> SpgemmExecutable:
    return SpgemmExecutable(plan, mesh or make_worker_mesh(plan.nparts), impl=impl)


class MaskedSpgemmExecutable(SpgemmExecutable):
    """A full-structure multiply that takes a per-task on/off mask at call time.

    Built once from the *full* (unpruned) plan; each ``__call__`` additionally
    receives ``task_on`` ``[P, t_cap]`` bool — False tasks write to the trash
    row, exactly like padding, so their contribution is dropped without
    re-planning, re-tracing, or recompiling.  This is the delta-plan SpAMM
    executable: one jitted program per structure serves every fluctuating
    ``tau``-prune pattern (``repro.dist.multiply.dist_spamm``), at full-plan
    exchange cost but zero per-pattern symbolic/compile cost.
    """

    _body = staticmethod(_mapped_multiply_masked)
    _n_runtime_args = 1

    def __init__(self, plan: SpgemmPlan, mesh: Mesh, *, impl: str = "ref"):
        if impl == "kernel":
            # a masked task sent to the trash row splits its row's run, and
            # the block_spmm kernel re-zeroes a revisited row
            raise ValueError(
                "masked staged multiply needs impl='ref'; the fused engine "
                "(impl='fused') masks tasks in place")
        super().__init__(plan, mesh, impl=impl)

    def __call__(
        self, a_store: jax.Array, b_store: jax.Array, task_on: np.ndarray
    ) -> jax.Array:
        """Run with a [P, t_cap] bool task mask; returns C stores [P, c_cap, bs, bs].

        ``task_on`` is the only per-call host->device transfer — a tiny bool
        table, the delta against the cached full plan.
        """
        mask = jax.device_put(np.asarray(task_on, dtype=bool), self._sh)
        return self._mapped(a_store, b_store, *self._idx_args, mask, *self._send_args)


def make_masked_spgemm_executable(
    plan: SpgemmPlan, mesh: Mesh | None = None, *, impl: str = "ref"
) -> MaskedSpgemmExecutable:
    return MaskedSpgemmExecutable(plan, mesh or make_worker_mesh(plan.nparts), impl=impl)


class FusedSpgemmExecutable(SpgemmExecutable):
    """The planned multiply through the fused leaf engine.

    Ships the plan's ``(src, off)`` task operand decomposition instead of
    concatenated-buffer indices, laid out in SMEM-bounded kernel-call
    chunks (:func:`fused_task_layout`); the mapped body runs the exchange
    into a stacked receive buffer and the fused unpack+GEMM+accumulate
    dispatches.  ``precision`` selects the storage/exchange dtype policy
    (``fp32`` | ``bf16``); ``adaptive`` needs a per-task mask and lives on
    the masked variant.
    """

    _body = staticmethod(_mapped_multiply_fused)
    _masked = False  # task on/off flags arrive per call
    _max_tasks = FUSED_MAX_TASKS  # per kernel call; tests shrink it

    def __init__(
        self,
        plan: SpgemmPlan,
        mesh: Mesh,
        *,
        impl: str = "fused",
        precision: Precision = FP32,
        **body_kwargs,
    ):
        assert plan.task_a_src is not None, (
            "fused engine needs a p2p plan with (src, off) task decomposition"
        )
        assert precision.mode != "adaptive" or self._masked, (
            "adaptive precision needs the masked fused executable"
        )
        self.precision = precision
        local = (plan.task_a_src == 0) & (plan.task_b_src == 0)
        self._idx, self._real, self.chunks, n_local = fused_task_layout(
            plan.task_c, plan.task_count, plan.c_cap, self._max_tasks, local
        )
        # kernel calls per phase: local-first, then the rest (one phase
        # when the layout has no split)
        self.phases = (
            (self.chunks[:n_local], self.chunks[n_local:]) if n_local
            else (self.chunks,)
        )
        self._n_before = sum(self.chunks[:n_local])
        super().__init__(
            plan, mesh, impl=impl, precision=precision, phases=self.phases,
            **body_kwargs,
        )

    def _plan_index_arrays(self, plan: SpgemmPlan) -> list[np.ndarray]:
        arrays = fused_task_arrays(plan, self._idx, self._n_before)
        # the unmasked body's on flags are fixed: real tasks only
        return arrays if self._masked else arrays + [self._real]

    def tasks_before_exchange(self, task_on: np.ndarray | None = None) -> int:
        """Real tasks (``task_on`` ones, when given) in phase 1: the work
        laid out to run before any received block is needed."""
        head = self._real[:, : self._n_before]
        if task_on is not None:
            head = head & self._task_table(task_on)[:, : self._n_before]
        return int(head.sum())

    def _task_table(self, x: np.ndarray) -> np.ndarray:
        """A per-call ``[P, t_cap]`` task table in the chunked layout, with
        padding slots cleared."""
        return _take_layout(np.asarray(x), self._idx, 0) * self._real


def make_fused_spgemm_executable(
    plan: SpgemmPlan,
    mesh: Mesh | None = None,
    *,
    impl: str = "fused",
    precision: Precision = FP32,
) -> FusedSpgemmExecutable:
    return FusedSpgemmExecutable(
        plan, mesh or make_worker_mesh(plan.nparts), impl=impl, precision=precision
    )


def _send_task_spans(plan: SpgemmPlan):
    """Per (operand, round) CSR map: send slot ``(src, pos)`` -> the global
    task ids that read the delivered block.  Host-side, memoized on the plan
    (same pattern as the obs statics) — this is what lets the masked fused
    executable decide per call which send slots still matter."""
    maps = getattr(plan, "_send_task_spans", None)
    if maps is not None:
        return maps
    nparts = plan.nparts
    t_owner = plan.c_owner[plan.tasks.c_idx]
    tasks_of = [np.nonzero(t_owner == p)[0] for p in range(nparts)]
    maps = {}
    for name, offsets, send, send_cnt, store_idx, x_idx in (
        ("a", plan.a_offsets, plan.a_send, plan.a_send_count,
         plan.a_store_idx, plan.tasks.a_idx),
        ("b", plan.b_offsets, plan.b_send, plan.b_send_count,
         plan.b_store_idx, plan.tasks.b_idx),
    ):
        for d in offsets:
            cap_d = send[d].shape[1]
            starts = np.zeros(nparts * cap_d + 1, np.int64)
            cat = []
            for src in range(nparts):
                dst = (src + d) % nparts
                cnt = int(send_cnt[d][src])
                t_dst = tasks_of[dst]
                refs = x_idx[t_dst]
                order = np.argsort(refs, kind="stable")
                sorted_refs = refs[order]
                blocks = store_idx[src][send[d][src, :cnt]]
                lo = np.searchsorted(sorted_refs, blocks, "left")
                hi = np.searchsorted(sorted_refs, blocks, "right")
                base = src * cap_d
                for pos in range(cap_d):
                    if pos < cnt:
                        ids = t_dst[order[lo[pos] : hi[pos]]]
                        cat.append(ids)
                        starts[base + pos + 1] = starts[base + pos] + ids.size
                    else:
                        starts[base + pos + 1] = starts[base + pos]
            maps[(name, d)] = (
                starts,
                np.concatenate(cat) if cat else np.zeros(0, np.int64),
            )
    object.__setattr__(plan, "_send_task_spans", maps)
    return maps


def _exchange_keep_masks(plan: SpgemmPlan, keep_task: np.ndarray):
    """Per-round send keep masks + live round sets from a global kept-task
    mask.  Returns ``(a_keeps, b_keeps, live_a, live_b, stats)`` where each
    keeps entry is ``[P, cap_d]`` bool and stats counts pruned payload.

    A shared exchange is refused: its send spans hold A-side readers only,
    so a B-side read would be pruned (its masks would need both sides'
    readers)."""
    if plan.exchange_shared:
        from ..analysis.errors import PlanError

        raise PlanError(
            "exchange pruning needs per-operand rounds; this plan shares one "
            "exchange between A and B")
    maps = _send_task_spans(plan)
    nparts = plan.nparts
    keeps_by, live_by = {}, {}
    stats = {"send_blocks": 0, "kept_blocks": 0, "dropped_rounds": 0}
    for name, offsets, send, send_cnt in (
        ("a", plan.a_offsets, plan.a_send, plan.a_send_count),
        ("b", plan.b_offsets, plan.b_send, plan.b_send_count),
    ):
        keeps, live = [], []
        for r, d in enumerate(offsets):
            starts, cat = maps[(name, d)]
            kt = keep_task[cat].astype(np.int64)
            cs = np.concatenate([[0], np.cumsum(kt)])
            keep = (cs[starts[1:]] - cs[starts[:-1]]) > 0
            keep = keep.reshape(nparts, send[d].shape[1])
            stats["send_blocks"] += int(np.asarray(send_cnt[d]).sum())
            stats["kept_blocks"] += int(keep.sum())
            if keep.any():
                live.append(r)
            else:
                stats["dropped_rounds"] += 1
            keeps.append(keep)
        keeps_by[name] = keeps
        live_by[name] = tuple(live)
    return keeps_by["a"], keeps_by["b"], live_by["a"], live_by["b"], stats


class MaskedFusedSpgemmExecutable(FusedSpgemmExecutable):
    """Delta-plan SpAMM through the fused engine, with exchange pruning.

    Like :class:`MaskedSpgemmExecutable`, one full-structure program serves
    every prune pattern — but here the mask also reaches the exchange: send
    slots referenced only by masked-out tasks ship zero payload, and rounds
    whose every slot is masked skip their ppermute entirely (a distinct
    jitted program per live-round pattern, memoized — ring plans have few
    rounds, so the program set stays tiny).  ``task_low`` feeds the adaptive
    precision mask.  ``last_exchange`` records the pruning stats of the most
    recent call.
    """

    _body = staticmethod(_mapped_multiply_fused_masked)
    _masked = True

    def __init__(
        self,
        plan: SpgemmPlan,
        mesh: Mesh,
        *,
        impl: str = "fused",
        precision: Precision = FP32,
        prune_exchange: bool = True,
    ):
        self.prune_exchange = prune_exchange
        self.last_exchange: dict | None = None
        # keep-mask pair (a_keeps, b_keeps) of the most recent pruned call —
        # the locality ledger reads it to meter only the blocks that shipped
        # (None when the last call ran the full exchange)
        self.last_keeps: tuple | None = None
        all_a = tuple(range(len(plan.a_offsets)))
        all_b = tuple(range(len(plan.b_offsets)))
        self._all_keeps = None  # built lazily for the unpruned path
        super().__init__(
            plan, mesh, impl=impl, precision=precision,
            live_a=all_a, live_b=all_b,
        )
        self._programs = {(all_a, all_b): self._mapped}

    def _n_runtime(self, plan: SpgemmPlan) -> int:
        # task_on, task_low, then one keep mask per exchange round
        return 2 + len(plan.a_offsets) + len(plan.b_offsets)

    def _keep_task_from_mask(self, task_on: np.ndarray) -> np.ndarray:
        plan = self.plan
        valid = np.arange(plan.t_cap)[None, :] < plan.task_count[:, None]
        keep_task = np.zeros(max(plan.tasks.num_tasks, 1), dtype=bool)
        keep_task[plan.task_gidx[task_on & valid]] = True
        return keep_task

    def __call__(
        self,
        a_store: jax.Array,
        b_store: jax.Array,
        task_on: np.ndarray,
        task_low: np.ndarray | None = None,
    ) -> jax.Array:
        plan = self.plan
        task_on = np.asarray(task_on, dtype=bool)
        if task_low is None:
            task_low = np.zeros(task_on.shape, dtype=np.int32)
        if self.prune_exchange and (plan.a_offsets or plan.b_offsets):
            keep_task = self._keep_task_from_mask(task_on)
            a_keeps, b_keeps, live_a, live_b, stats = _exchange_keep_masks(
                plan, keep_task
            )
            self.last_exchange = stats
            self.last_keeps = (a_keeps, b_keeps)
        else:
            if self._all_keeps is None:
                self._all_keeps = (
                    [np.ones((plan.nparts, plan.a_send[d].shape[1]), bool)
                     for d in plan.a_offsets],
                    [np.ones((plan.nparts, plan.b_send[d].shape[1]), bool)
                     for d in plan.b_offsets],
                )
            a_keeps, b_keeps = self._all_keeps
            live_a = tuple(range(len(plan.a_offsets)))
            live_b = tuple(range(len(plan.b_offsets)))
            self.last_exchange = None
            self.last_keeps = None
        program = self._programs.get((live_a, live_b))
        if program is None:
            program = self._build_program(live_a=live_a, live_b=live_b)
            self._programs[(live_a, live_b)] = program
        put = lambda x: jax.device_put(x, self._sh)
        return program(
            a_store,
            b_store,
            *self._idx_args,
            put(self._task_table(task_on).astype(bool)),
            put(self._task_table(task_low).astype(np.int32)),
            *[put(k) for k in a_keeps],
            *[put(k) for k in b_keeps],
            *self._send_args,
        )


def make_masked_fused_spgemm_executable(
    plan: SpgemmPlan,
    mesh: Mesh | None = None,
    *,
    impl: str = "fused",
    precision: Precision = FP32,
    prune_exchange: bool = True,
) -> MaskedFusedSpgemmExecutable:
    return MaskedFusedSpgemmExecutable(
        plan,
        mesh or make_worker_mesh(plan.nparts),
        impl=impl,
        precision=precision,
        prune_exchange=prune_exchange,
    )


def dist_spgemm(
    plan: SpgemmPlan,
    a_data: jax.Array,
    b_data: jax.Array,
    mesh: Mesh | None = None,
    *,
    impl: str = "ref",
) -> jax.Array:
    """Execute the planned multiply. Returns sharded C stores [P, c_cap, bs, bs].

    One-shot form: ships host block stacks each call.  Iterative algorithms
    should hold a :class:`SpgemmExecutable` (via ``repro.dist``) instead.
    """
    mesh = mesh or make_worker_mesh(plan.nparts)
    exe = SpgemmExecutable(plan, mesh, impl=impl)
    a_store, b_store = shard_stores(plan, a_data, b_data)
    sh = NamedSharding(mesh, P(AXIS))
    return exe(
        jax.device_put(jnp.asarray(a_store), sh),
        jax.device_put(jnp.asarray(b_store), sh),
    )


def _mapped_outer(
    a_store,
    b_store,
    task_a,
    task_b,
    task_c,
    acc_idx,
    *sends,
    plan,
    impl: str,
):
    """Outer-product multiply body: all-local tasks -> partial C -> exchange
    partials to owners -> accumulate."""
    num_partial = plan.p_cap + 1  # trash row for padded tasks
    if impl == "kernel":
        from repro.kernels import ops as kops

        partials = kops.block_spmm(
            a_store[0], b_store[0], task_a[0], task_b[0], task_c[0], num_partial
        )
    else:
        from repro.kernels import ref as kref

        partials = kref.block_spmm_ref(
            a_store[0], b_store[0], task_a[0], task_b[0], task_c[0], num_partial
        )
    partials = partials[: plan.p_cap]
    bufs = [partials]
    for d, send in zip(plan.offsets, sends):
        payload = partials[send[0]]
        perm = [(p, (p + d) % plan.nparts) for p in range(plan.nparts)]
        bufs.append(jax.lax.ppermute(payload, AXIS, perm=perm))
    all_partials = jnp.concatenate(bufs, axis=0) if len(bufs) > 1 else partials
    c = jax.ops.segment_sum(all_partials, acc_idx[0], num_segments=plan.c_cap + 1)
    return c[None, : plan.c_cap]


def dist_spgemm_outer(plan, a_data, b_data, mesh=None, *, impl: str = "ref"):
    """Execute an OuterPlan (repro.core.outer).  Returns [P, c_cap, bs, bs]."""
    mesh = mesh or make_worker_mesh(plan.nparts)
    av = jnp.asarray(plan.a_store_valid)[..., None, None]
    bv = jnp.asarray(plan.b_store_valid)[..., None, None]
    a_store = a_data[jnp.asarray(plan.a_store_idx)] * av.astype(a_data.dtype)
    b_store = b_data[jnp.asarray(plan.b_store_idx)] * bv.astype(b_data.dtype)
    sh = NamedSharding(mesh, P(AXIS))
    put = lambda x: jax.device_put(jnp.asarray(x), sh)
    args = [
        put(a_store),
        put(b_store),
        put(plan.task_a),
        put(plan.task_b),
        put(plan.task_c),
        put(plan.acc_idx),
    ]
    sends = [put(plan.send[d]) for d in plan.offsets]
    fn = functools.partial(_mapped_outer, plan=plan, impl=impl)
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(P(AXIS) for _ in range(len(args) + len(sends))),
        out_specs=P(AXIS),
        check_vma=False,
    )
    return jax.jit(mapped)(*args, *sends)


def unshard_result(plan: SpgemmPlan, c_stores: jax.Array, shape, bs) -> BSMatrix:
    """Reassemble the global BSMatrix from per-device C stores."""
    c_stores = np.asarray(c_stores)
    nc = plan.c_coords.shape[0]
    data = np.zeros((nc, bs, bs), dtype=c_stores.dtype)
    for p in range(plan.nparts):
        valid = plan.c_store_valid[p]
        data[plan.c_store_idx[p][valid]] = c_stores[p][valid]
    return BSMatrix(shape=tuple(shape), bs=bs, coords=plan.c_coords, data=jnp.asarray(data))
