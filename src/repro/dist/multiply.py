"""Distributed multiply on resident operands, planned through the cache.

``dist_multiply`` is the hot-path operation the subsystem exists for: both
operands are :class:`~repro.dist.matrix.DistBSMatrix` stores already living
on the mesh, the schedule comes from the structure-keyed
:class:`~repro.dist.cache.PlanCache` (symbolic phase + shard_map executable
+ device-resident plan arrays, built once per distinct structure), and the
result store is produced sharded — it never visits the host.

``dist_spamm`` adds error-controlled approximate multiply in two modes:

* ``method="delta"`` (default) — the *delta-plan* path: the full-multiply
  plan and a :class:`~repro.core.distributed.MaskedSpgemmExecutable` are
  cached once per structure; each call runs the hierarchical SpAMM descent
  on the host and ships only a tiny per-task on/off mask (``gval``-style
  zeroing via trash-row redirect).  A fluctuating ``tau``-prune pattern
  therefore never causes a plan-cache miss — the SP2 inner loop stays pure
  device work.
* ``method="replan"`` — the pruned task list is threaded into
  :func:`make_spgemm_plan(tasks=...)` and the plan is keyed by the pruned
  structure: cheaper flops/exchange per call, but any wiggle in the prune
  pattern re-plans and re-jits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import (
    make_fused_spgemm_executable,
    make_masked_fused_spgemm_executable,
    make_masked_spgemm_executable,
    make_spgemm_executable,
)
from repro.core.quadtree import build_quadtree_index, quadtree_depth
from repro.core.schedule import (
    make_spgemm_plan,
    plan_worker_bytes,
    structure_fingerprint,
)
from repro.core.spgemm import spamm_symbolic
from repro.kernels.precision import FP32, Precision, low_precision_task_mask
from repro.obs.timing import timed_into
from repro.obs.tracer import tracer_of

from .balance import RebalancePolicy, relayout_for_product, worker_load
from .cache import PlanCache
from .matrix import (
    DistBSMatrix,
    _store_sharding,
    mesh_key,
    resident_block_norms,
)

__all__ = [
    "dist_multiply",
    "dist_spamm",
    "multiply_plan_key",
    "spamm_delta_plan_key",
]

# backward-compatible private name; the implementation now lives next to the
# store layout it reads (repro.dist.matrix)
_resident_block_norms = resident_block_norms


_FUSED_IMPLS = ("fused", "fused-interpret")


def _shares_exchange(a: DistBSMatrix, b: DistBSMatrix, exchange: str) -> bool:
    """A @ A on one resident store ships each block once
    (``make_spgemm_plan(shared_exchange=True)``)."""
    return b is a and exchange == "p2p"


def multiply_plan_key(
    a: DistBSMatrix,
    b: DistBSMatrix,
    *,
    exchange: str,
    impl: str,
    precision: Precision = FP32,
) -> tuple:
    """Cache key: A/B Morton codes + owner maps + mesh + mode knobs.

    Operand dtypes and the precision policy are part of the key — a bf16 or
    adaptive program is a different compiled artifact than the fp32 one.
    So is a shared exchange (``b is a``): its plan squares A's store, and a
    B with A's structure and owners but another store must not reuse it.
    """
    return (
        "spgemm",
        structure_fingerprint(
            a.codes(), b.codes(), a.owner, b.owner, a.nparts, a.bs
        ),
        mesh_key(a.mesh),
        exchange,
        impl,
        str(a.dtype),
        str(b.dtype),
        precision.key(),
        _shares_exchange(a, b, exchange),
    )


def spamm_delta_plan_key(
    a: DistBSMatrix,
    b: DistBSMatrix,
    *,
    exchange: str,
    impl: str,
    precision: Precision = FP32,
) -> tuple:
    """Delta-plan SpAMM cache key — structure only, independent of the per-call
    prune pattern, so every call on a stable structure is a hit."""
    return (
        "spamm-delta",
        structure_fingerprint(
            a.codes(), b.codes(), a.owner, b.owner, a.nparts, a.bs
        ),
        mesh_key(a.mesh),
        exchange,
        impl,
        str(a.dtype),
        str(b.dtype),
        precision.key(),
    )


def _plan_obs_static(plan) -> dict:
    """Per-plan static annotation payload, memoized on the plan object.

    Everything here depends only on the plan (exchange bytes, ownership
    terms of the cost model, per-round byte totals) — a warm-cache run
    replays the same plan hundreds of times, so recomputing it per dispatch
    is what pushed tracing overhead past the benchmark cap.
    """
    st = getattr(plan, "_obs_static", None)
    if st is None:
        load = worker_load(plan)
        _, _, recv_padded = plan_worker_bytes(plan)
        pol = RebalancePolicy()
        blk = plan.bs * plan.bs * 4
        rounds = []
        if plan.exchange != "allgather":
            for operand, offs, cnts in (
                ("a", plan.a_offsets, plan.a_send_count),
                ("b", plan.b_offsets, plan.b_send_count),
            ):
                for rnd, d in enumerate(offs):
                    rounds.append((operand, rnd, int(d),
                                   float(np.asarray(cnts[d]).sum()) * blk))
        base = (pol.recv_cost * load.recv_bytes / blk
                + pol.send_cost * load.send_bytes / blk
                + pol.block_cost * load.blocks)
        st = dict(
            # the task-independent terms of the rebalancer's combined cost
            base=base,
            # full (unmasked) dispatch cost vector, precomputed: most warm
            # dispatches run the whole task list
            full_costs=np.asarray(plan.task_count, np.float64) + base,
            full_tasks=int(np.asarray(plan.task_count).sum()),
            recv_sum=float(load.recv_bytes.sum()),
            # what the SPMD program receives, padding included, over chips
            recv_padded_sum=float(recv_padded.sum()),
            send_sum=float(load.send_bytes.sum()),
            rounds=rounds,
            tiles={},            # per-dtype pick_tiles memo
            rounds_tracer=None,  # exchange_round instants once per tracer
        )
        st["full_costs"].setflags(write=False)  # shared across spans
        object.__setattr__(plan, "_obs_static", st)  # plan is frozen
    return st


def _annotate_spgemm_dispatch(
    tr, sp, plan, task_count, precision: Precision | None = None, exe=None,
    task_on=None,
) -> None:
    """Per-worker attribution + byte/task counters on an executed multiply
    dispatch span.  Callers guard on ``tr.enabled`` — this does real work
    (plan byte accounting, cost-model evaluation) that must cost nothing
    with tracing off.  ``task_on`` is a masked dispatch's task mask.
    """
    st = _plan_obs_static(plan)
    if precision is not None:
        dtype = "bfloat16" if precision.mode == "bf16" else "float32"
        tiles = st["tiles"].get(dtype)
        if tiles is None:
            from repro.kernels.autotune import pick_tiles

            tiles = st["tiles"][dtype] = list(
                pick_tiles(plan.bs, plan.bs, plan.bs, dtype))
        sp.args.update(precision=precision.mode, dtype=dtype, tiles=tiles)
    ex = getattr(exe, "last_exchange", None)
    if ex is not None:
        sp.args.update(
            send_blocks=ex["send_blocks"],
            kept_send_blocks=ex["kept_blocks"],
            dropped_rounds=ex["dropped_rounds"],
        )
        tr.counter("pruned_send_blocks").add(
            float(ex["send_blocks"] - ex["kept_blocks"])
        )
    # the same combined task-equivalent cost the rebalancer weighs, so the
    # trace's utilization tracks match BENCH_balance's imbalance numbers
    if task_count is None or task_count is plan.task_count:
        sp.worker_costs = st["full_costs"]
        tasks = st["full_tasks"]
    else:
        tc = np.asarray(task_count)
        sp.worker_costs = tc.astype(np.float64) + st["base"]
        tasks = int(tc.sum())
    sp.args.update(tasks=tasks, recv_bytes=st["recv_sum"],
                   recv_padded_bytes=st["recv_padded_sum"],
                   send_bytes=st["send_sum"],
                   exchange_shared=plan.exchange_shared)
    tr.counter("tasks_executed").add(float(tasks))
    if plan.exchange_shared:
        tr.counter("shared_exchanges").add(1.0)
    before = getattr(exe, "tasks_before_exchange", None)
    if before is not None:
        # fused layouts: tasks laid out ahead of the first received block
        n = before(task_on)
        sp.args.update(tasks_before_exchange=n)
        tr.counter("tasks_before_exchange").add(float(n))
    tr.counter("recv_bytes").add(st["recv_sum"])
    tr.counter("recv_padded_bytes").add(st["recv_padded_sum"])
    tr.counter("send_bytes").add(st["send_sum"])
    # exchange rounds run fused inside the jitted dispatch — emit honest
    # per-round markers carrying planned bytes, not fabricated durations.
    # They are plan-static, so each plan emits them on its first dispatch
    # observed by a given tracer; warm replays add no duplicate markers.
    if st["rounds_tracer"] is not tr:
        st["rounds_tracer"] = tr
        for operand, rnd, d, nbytes in st["rounds"]:
            tr.instant("exchange_round", cat="exchange", operand=operand,
                       round=rnd, offset=d, bytes=nbytes)


def _note_dispatch_memory(cache, plan, precision, c) -> None:
    """Account an executed multiply against the installed
    :class:`~repro.obs.memory.MemoryMeter` (no-op when none is installed):
    the plan's receive buffers at wire precision plus the result store.

    A repeat dispatch of the same cached plan over the same owner layout
    yields byte-identical account vectors, so those are deduped by token —
    peak watermarks cannot move and warm iteration loops pay one set
    lookup instead of recomputing the per-worker bincounts."""
    mm = getattr(cache, "memory_meter", None) if cache is not None else None
    if mm is None:
        return
    tok = (id(plan), id(c.owner), c.nnzb, c.cap,
           getattr(precision, "mode", None))
    seen = getattr(mm, "_dispatch_seen", None)
    if seen is None:
        seen = mm._dispatch_seen = set()
    if tok in seen:
        return
    seen.add(tok)
    mm.note_plan(plan, precision, cache=cache)
    mm.note_matrix(c, "store", cache=cache)


def _note_dispatch_locality(
    cache, tr, plan, precision, a, b, *, task_on=None, exe=None
) -> None:
    """Meter an executed multiply against the installed
    :class:`~repro.obs.locality.LocalityLedger` (no-op when none is
    installed, costing one getattr): static local/shipped residency split,
    wire bytes with delta-mask pruning and the wire itemsize applied, and
    per-block movement lineage keyed by the operands' Morton codes.
    Independent of the tracer — the ledger meters even with tracing off —
    but feeds the locality counters when a tracer listens."""
    lld = getattr(cache, "locality_ledger", None) if cache is not None else None
    if lld is None:
        return
    wire = 2 if getattr(precision, "mode", "fp32") != "fp32" else 4
    out = lld.note_dispatch(
        plan,
        wire_itemsize=wire,
        task_on=task_on,
        keeps=getattr(exe, "last_keeps", None),
        a_codes=a.codes(),
        b_codes=b.codes(),
    )
    if tr.enabled:
        tr.counter("local_bytes").add(out["local_bytes"])
        tr.counter("shipped_bytes").add(out["shipped_bytes"])
        tr.counter("wire_recv_bytes").add(out["wire_recv_bytes"])
        tr.counter("local_flops").add(out["local_flops"])


def _check_operands(a: DistBSMatrix, b: DistBSMatrix) -> None:
    assert a.mesh is b.mesh or list(a.mesh.devices.flat) == list(
        b.mesh.devices.flat
    ), "operands must live on the same worker mesh"
    assert a.shape[1] == b.shape[0] and a.bs == b.bs, (a.shape, b.shape)


def _precision_of(precision, impl: str, exchange: str = "p2p") -> Precision:
    precision = FP32 if precision is None else precision
    if precision.is_mixed:
        assert impl in _FUSED_IMPLS, (
            "mixed precision needs the fused leaf engine (impl='fused')"
        )
        assert exchange == "p2p", (
            "mixed precision needs the p2p exchange (allgather plans have no "
            "(src, off) task decomposition)"
        )
    return precision


def _use_fused(impl: str, exchange: str) -> bool:
    """Fused engine needs the p2p (src, off) decomposition; an allgather
    plan falls back to the staged reference path."""
    return impl in _FUSED_IMPLS and exchange == "p2p"


def _valid_task_slots(plan) -> np.ndarray:
    return (
        np.arange(plan.task_gidx.shape[1])[None, :] < plan.task_count[:, None]
    )


def _adaptive_low_table(plan, low_task: np.ndarray) -> np.ndarray:
    """Map a global per-task low-precision mask onto [P, t_cap] int32."""
    if low_task.shape[0] == 0:  # no tasks: gidx pads with 0, don't index
        return np.zeros(plan.task_gidx.shape, np.int32)
    valid = _valid_task_slots(plan)
    return (low_task[plan.task_gidx] & valid).astype(np.int32)


def dist_multiply(
    a: DistBSMatrix,
    b: DistBSMatrix,
    cache: PlanCache | None = None,
    *,
    exchange: str = "p2p",
    impl: str = "fused",
    precision: Precision | None = None,
    rebalance=None,
) -> DistBSMatrix:
    """C = A @ B with A, B, C device-resident.  Plan + executable cached.

    ``impl="fused"`` (the default) routes through the fused leaf engine
    (one unpack+GEMM+accumulate dispatch, no concatenated operand buffer);
    ``precision`` selects its dtype policy (:class:`Precision` — ``fp32`` |
    ``bf16`` | ``adaptive``; adaptive spends a rounding-error budget of
    ``precision.tau`` using the resident norm tables).  Staged impls
    (``ref`` / ``kernel``) are fp32-only.

    ``rebalance`` (a :class:`repro.dist.balance.RebalancePolicy`) re-slots
    skewed operand layouts on device before planning, on every call — see
    :func:`repro.dist.balance.relayout_for_product`, whose handles a caller
    that repeats the product should hold instead.
    """
    _check_operands(a, b)
    precision = _precision_of(precision, impl, exchange)
    fused = _use_fused(impl, exchange)
    adaptive = precision.mode == "adaptive"
    tr = tracer_of(cache)
    with tr.span("dist_multiply", cat="collective",
                 nnzb_a=a.nnzb, nnzb_b=b.nnzb):
        if rebalance is not None:
            a, b = relayout_for_product(a, b, cache, policy=rebalance)

        def build():
            plan = make_spgemm_plan(
                a.coords,
                b.coords,
                a.nparts,
                a.bs,
                exchange=exchange,
                a_owner=a.owner,
                b_owner=b.owner,
                shared_exchange=_shares_exchange(a, b, exchange),
            )
            # the pinned placements must reproduce the operands' resident
            # layout
            assert plan.a_cap == a.cap and plan.b_cap == b.cap, (
                plan.a_cap,
                a.cap,
                plan.b_cap,
                b.cap,
            )
            if fused and adaptive:
                # adaptive needs the per-task low mask -> masked executable;
                # no pruning here (all tasks run), so keep the exchange full
                exe = make_masked_fused_spgemm_executable(
                    plan, a.mesh, impl=impl, precision=precision,
                    prune_exchange=False,
                )
            elif fused:
                exe = make_fused_spgemm_executable(
                    plan, a.mesh, impl=impl, precision=precision
                )
            else:
                staged = "ref" if impl in _FUSED_IMPLS else impl
                exe = make_spgemm_executable(plan, a.mesh, impl=staged)
            return plan, exe

        with tr.span("plan_key", cat="plan"):
            key = multiply_plan_key(
                a, b, exchange=exchange, impl=impl, precision=precision
            )
        if cache is None:
            plan, exe = build()
        else:
            plan, exe = cache.get_or_build(key, build)
            cache.last_plan_key = key
            cache.last_task_count = plan.task_count
        if adaptive:
            a_norms = resident_block_norms(a, cache)
            b_norms = a_norms if b is a else resident_block_norms(b, cache)
            full = plan.tasks
            low_task, _ = low_precision_task_mask(
                a_norms, b_norms, full.a_idx, full.b_idx, precision.tau
            )
            task_on = _valid_task_slots(plan)
            task_low = _adaptive_low_table(plan, low_task)
        with tr.span("dispatch", cat="kernel", kind="spgemm") as sp:
            if adaptive:
                c_store = exe(a.store, b.store, task_on, task_low)
            else:
                c_store = exe(a.store, b.store)
        if tr.enabled:
            _annotate_spgemm_dispatch(
                tr, sp, plan, plan.task_count, precision, exe
            )
        c = DistBSMatrix(
            shape=(a.shape[0], b.shape[1]),
            bs=a.bs,
            coords=plan.c_coords,
            owner=np.asarray(plan.c_owner, dtype=np.int32),
            slot=np.asarray(plan.c_slot, dtype=np.int32),
            cap=plan.c_cap,
            store=c_store,
            mesh=a.mesh,
        )
        _note_dispatch_memory(cache, plan, precision, c)
        _note_dispatch_locality(cache, tr, plan, precision, a, b, exe=exe)
    return c


def _spamm_pruned_tasks(
    a: DistBSMatrix,
    b: DistBSMatrix,
    tau: float,
    a_norms: np.ndarray,
    b_norms: np.ndarray,
):
    """Hierarchical SpAMM descent on the resident structures.

    ``a_norms`` / ``b_norms`` are stack-order per-block norms the caller
    already holds — :func:`dist_spamm` prefetches them through the fused
    psum path (:func:`resident_block_norms` with the cache) outside the
    symbolic timer, or reuses a table carried over from truncation.
    Returns ``(tasks, err_bound)``.
    """
    depth = max(
        quadtree_depth(-(-a.shape[0] // a.bs), -(-a.shape[1] // a.bs)),
        quadtree_depth(-(-b.shape[0] // b.bs), -(-b.shape[1] // b.bs)),
    )
    na, nb = a_norms, b_norms
    ia = build_quadtree_index(a.coords, na, depth=depth)
    ib = ia if b is a else build_quadtree_index(b.coords, nb, depth=depth)
    tasks, err, _ = spamm_symbolic(ia, ib, tau)
    return tasks, err


def _empty_dist_result(a: DistBSMatrix, b: DistBSMatrix) -> DistBSMatrix:
    store = jax.device_put(
        np.zeros((a.nparts, 1, a.bs, a.bs), dtype=a.dtype),
        _store_sharding(a.mesh),
    )
    return DistBSMatrix(
        shape=(a.shape[0], b.shape[1]),
        bs=a.bs,
        coords=np.zeros((0, 2), dtype=np.int64),
        owner=np.zeros((0,), dtype=np.int32),
        slot=np.zeros((0,), dtype=np.int32),
        cap=1,
        store=store,
        mesh=a.mesh,
    )


def dist_spamm(
    a: DistBSMatrix,
    b: DistBSMatrix,
    tau: float,
    cache: PlanCache | None = None,
    *,
    exchange: str = "p2p",
    impl: str = "fused",
    method: str = "delta",
    precision: Precision | None = None,
    a_norms: np.ndarray | None = None,
    b_norms: np.ndarray | None = None,
    rebalance=None,
) -> tuple[DistBSMatrix, float]:
    """Sparse approximate multiply on resident operands: C ~= A @ B.

    The hierarchical SpAMM symbolic phase (:func:`repro.core.spgemm.spamm_symbolic`)
    runs on the host against quadtree indexes carrying subtree norms — norms
    depend on current values, so it runs every call, but it is cheap and
    shrinks with the pruned work.  ``a_norms`` / ``b_norms`` (stack-order
    per-block norms, as returned by :func:`resident_block_norms`) let callers
    share one norm-table fetch across operations.

    ``method="delta"`` applies the prune pattern as a task mask against the
    cached full-multiply plan (see module docstring): the plan cache is keyed
    by structure alone, so prune-pattern fluctuation never misses.
    ``method="replan"`` threads the pruned task list into a per-pattern plan.

    ``rebalance`` (a :class:`repro.dist.balance.RebalancePolicy`) re-slots
    skewed operand layouts on device before planning
    (:func:`repro.dist.balance.relayout_for_product`); note the stack-order
    norm tables are layout-invariant, so prefetched ``a_norms`` /
    ``b_norms`` stay valid across the re-layout.

    ``precision`` (fused impl only) selects the leaf engine's dtype policy;
    ``adaptive`` rounds the smallest-bound kept tasks to bf16 under a budget
    of ``precision.budget(tau)`` — the returned bound then includes the
    rounding spend, so ``||A@B - C||_F <= err_bound`` still holds.

    Returns ``(C, err_bound)`` with ``||A@B - C||_F <= err_bound``; for pure
    pruning (fp32/bf16 storage aside) the bound is ``<= tau``.
    """
    _check_operands(a, b)
    precision = _precision_of(precision, impl, exchange)
    if precision.mode == "adaptive":
        assert method == "delta", "adaptive precision rides the delta plan"
    tr = tracer_of(cache)
    with tr.span("dist_spamm", cat="collective",
                 nnzb_a=a.nnzb, nnzb_b=b.nnzb, tau=float(tau)):
        return _dist_spamm_impl(
            a, b, tau, cache, tr,
            exchange=exchange, impl=impl, method=method, precision=precision,
            a_norms=a_norms, b_norms=b_norms, rebalance=rebalance,
        )


def _dist_spamm_impl(
    a, b, tau, cache, tr, *, exchange, impl, method, precision, a_norms,
    b_norms, rebalance
):
    fused = _use_fused(impl, exchange)
    if rebalance is not None:
        a, b = relayout_for_product(a, b, cache, policy=rebalance)
    # norm fetches stay outside the symbolic timer: a miss on the fused norm
    # executable is timed into cache.build_s by get_or_build
    if a_norms is None:
        a_norms = resident_block_norms(a, cache)
    if b_norms is None:
        b_norms = a_norms if b is a else resident_block_norms(b, cache)
    # descent time only — miss builders are timed into cache.build_s by
    # get_or_build, and must not be double-counted as symbolic work
    with timed_into(cache, "symbolic_s", tr, "spamm_descent",
                    cat="symbolic", tau=float(tau)):
        tasks, err = _spamm_pruned_tasks(a, b, tau, a_norms, b_norms)

    if method == "delta":
        with tr.span("plan_key", cat="plan"):
            key = spamm_delta_plan_key(
                a, b, exchange=exchange, impl=impl, precision=precision
            )

        def build():
            # the delta plan IS the exact-multiply plan; reuse one already
            # cached for dist_multiply on this structure instead of redoing
            # the symbolic phase (only the executable differs) — unless it
            # shares its exchange, which the masked exchange cannot prune
            exact = (
                cache.peek(multiply_plan_key(
                    a, b, exchange=exchange, impl=impl, precision=precision
                ))
                if cache is not None
                else None
            )
            if exact is not None and exact[0].exchange_shared:
                exact = None
            plan = exact[0] if exact is not None else make_spgemm_plan(
                a.coords,
                b.coords,
                a.nparts,
                a.bs,
                exchange=exchange,
                a_owner=a.owner,
                b_owner=b.owner,
            )
            assert plan.a_cap == a.cap and plan.b_cap == b.cap, (
                plan.a_cap, a.cap, plan.b_cap, b.cap,
            )
            if fused:
                exe = make_masked_fused_spgemm_executable(
                    plan, a.mesh, impl=impl, precision=precision
                )
            else:
                staged = "ref" if impl in _FUSED_IMPLS else impl
                exe = make_masked_spgemm_executable(plan, a.mesh, impl=staged)
            return plan, exe

        if cache is None:
            plan, exe = build()
        else:
            plan, exe = cache.get_or_build(key, build)
            cache.last_plan_key = key
        # relay the kept (a, b) pairs onto the full task list: a task is
        # uniquely (a_idx, b_idx) — the output block is determined by the pair
        with timed_into(cache, "symbolic_s", tr, "delta_mask", cat="symbolic"):
            full = plan.tasks
            if full.num_tasks == 0:
                # no structural overlap: every padded slot is already masked
                # off (task_gidx pads with 0, which must not index an empty
                # task list)
                task_on = np.zeros(plan.task_gidx.shape, dtype=bool)
            else:
                keep_task = np.zeros(full.num_tasks, dtype=bool)
                if tasks.num_tasks:
                    nb_blocks = np.int64(max(b.nnzb, 1))
                    keep_task = np.isin(
                        full.a_idx * nb_blocks + full.b_idx,
                        tasks.a_idx * nb_blocks + tasks.b_idx,
                    )
                valid = (
                    np.arange(plan.task_gidx.shape[1])[None, :]
                    < plan.task_count[:, None]
                )
                task_on = keep_task[plan.task_gidx] & valid
        # adaptive mixed precision: spend the rounding budget on the kept
        # tasks with the smallest ||A_t||·||B_t|| bound (a pruned task
        # contributes no error and must not consume budget)
        task_low = None
        if precision.mode == "adaptive":
            full = plan.tasks
            keep_task_g = np.zeros(max(full.num_tasks, 1), dtype=bool)
            if full.num_tasks:
                keep_task_g[plan.task_gidx[task_on]] = True
            low_task, spent = low_precision_task_mask(
                a_norms, b_norms, full.a_idx, full.b_idx,
                precision.budget(tau), eligible=keep_task_g[: full.num_tasks],
            )
            task_low = _adaptive_low_table(plan, low_task)
            err = float(err) + spent
        # measured per-worker flop load: only unmasked tasks cost work
        masked_count = task_on.sum(axis=1).astype(np.int64)
        if cache is not None:
            cache.last_task_count = masked_count
        with tr.span("dispatch", cat="kernel", kind="spamm-delta") as sp:
            if fused:
                c_store = exe(a.store, b.store, task_on, task_low)
            else:
                c_store = exe(a.store, b.store, task_on)
        if tr.enabled:
            _annotate_spgemm_dispatch(
                tr, sp, plan, masked_count, precision, exe, task_on
            )
        c = DistBSMatrix(
            shape=(a.shape[0], b.shape[1]),
            bs=a.bs,
            coords=plan.c_coords,
            owner=np.asarray(plan.c_owner, dtype=np.int32),
            slot=np.asarray(plan.c_slot, dtype=np.int32),
            cap=plan.c_cap,
            store=c_store,
            mesh=a.mesh,
        )
        _note_dispatch_memory(cache, plan, precision, c)
        _note_dispatch_locality(
            cache, tr, plan, precision, a, b, task_on=task_on, exe=exe
        )
        return c, err

    assert method == "replan", method
    if tasks.num_tasks == 0:
        if cache is not None:
            cache.last_plan_key = None  # no plan ran; nothing to peek
            cache.last_task_count = None
        return _empty_dist_result(a, b), err

    key = (
        "spamm",
        structure_fingerprint(
            a.codes(), b.codes(), a.owner, b.owner, a.nparts, a.bs,
            tasks.a_idx, tasks.b_idx, tasks.c_idx,
        ),
        mesh_key(a.mesh),
        exchange,
        impl,
        str(a.dtype),
        str(b.dtype),
        precision.key(),
    )

    def build():
        plan = make_spgemm_plan(
            a.coords,
            b.coords,
            a.nparts,
            a.bs,
            exchange=exchange,
            tasks=tasks,
            a_owner=a.owner,
            b_owner=b.owner,
        )
        assert plan.a_cap == a.cap and plan.b_cap == b.cap, (
            plan.a_cap, a.cap, plan.b_cap, b.cap,
        )
        if fused:
            exe = make_fused_spgemm_executable(
                plan, a.mesh, impl=impl, precision=precision
            )
        else:
            staged = "ref" if impl in _FUSED_IMPLS else impl
            exe = make_spgemm_executable(plan, a.mesh, impl=staged)
        return plan, exe

    if cache is None:
        plan, exe = build()
    else:
        plan, exe = cache.get_or_build(key, build)
        cache.last_plan_key = key
        cache.last_task_count = plan.task_count
    with tr.span("dispatch", cat="kernel", kind="spamm") as sp:
        c_store = exe(a.store, b.store)
    if tr.enabled:
        _annotate_spgemm_dispatch(
            tr, sp, plan, plan.task_count, precision, exe
        )
    c = DistBSMatrix(
        shape=(a.shape[0], b.shape[1]),
        bs=a.bs,
        coords=plan.c_coords,
        owner=np.asarray(plan.c_owner, dtype=np.int32),
        slot=np.asarray(plan.c_slot, dtype=np.int32),
        cap=plan.c_cap,
        store=c_store,
        mesh=a.mesh,
    )
    _note_dispatch_memory(cache, plan, precision, c)
    _note_dispatch_locality(cache, tr, plan, precision, a, b, exe=exe)
    return c, err
