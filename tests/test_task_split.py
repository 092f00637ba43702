"""Task lists split into SMEM-bounded kernel calls, and in-place task masks.

A list longer than one call's bound runs as several calls cut at ``c``-run
boundaries, accumulating into one output; a forced small bound exercises
the split here on the CPU, in the Pallas interpreter and in the reference.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.distributed import fused_task_layout  # noqa: E402
from repro.kernels.fused_leaf import first_accumulation_hazard  # noqa: E402
from repro.kernels.ops import (  # noqa: E402
    FUSED_MAX_TASKS,
    BLOCK_SPMM_MAX_TASKS,
    block_spmm,
    fused_block_spmm,
    task_chunks,
)

# runs of c: [0 0 0 | 1 1 | 2 2 2 2 | 3 | 4 4 4 | 5 5]
C = np.array([0, 0, 0, 1, 1, 2, 2, 2, 2, 3, 4, 4, 4, 5, 5], np.int32)
RUN_STARTS = {0, 3, 5, 9, 10, 13}
# bounds whose cuts land inside runs (4: at 4 and 7; 7: at 7) and on run
# starts (4: at 9; 5: at 5 and 10)
BOUNDS = [4, 5, 7]


def _problem(c, bs=16, n_store=6, rounds=2, cap_u=4, seed=0):
    rng = np.random.default_rng(seed)
    T = c.shape[0]
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    a_src = rng.integers(0, rounds + 1, T).astype(np.int32)
    b_src = rng.integers(0, rounds + 1, T).astype(np.int32)
    off = lambda src: np.where(
        src == 0, rng.integers(0, n_store, T), rng.integers(0, cap_u, T)
    ).astype(np.int32)
    return dict(
        a_store=f32(n_store, bs, bs), a_recv=f32(rounds, cap_u, bs, bs),
        b_store=f32(n_store, bs, bs), b_recv=f32(rounds, cap_u, bs, bs),
        a_src=a_src, a_off=off(a_src), b_src=b_src, b_off=off(b_src),
        c_idx=c, num_out=int(c.max()) + 1,
    )


def _fused(p, **kw):
    return np.asarray(fused_block_spmm(
        p["a_store"], p["a_recv"], p["b_store"], p["b_recv"],
        p["a_src"], p["a_off"], p["b_src"], p["b_off"], p["c_idx"],
        p["num_out"], **kw,
    ))


def test_smem_bounds():
    # six (fused) and three (block_spmm) int32 words per task in 3/4 MiB
    assert FUSED_MAX_TASKS == 32_768
    assert BLOCK_SPMM_MAX_TASKS == 65_536


@pytest.mark.parametrize("bound", BOUNDS)
def test_task_chunks_cut_at_run_boundaries(bound):
    chunks = task_chunks(C, bound)
    assert sum(chunks) == C.size and max(chunks) <= bound
    cuts = np.cumsum((0,) + chunks)
    assert set(cuts[:-1].tolist()) <= RUN_STARTS
    # greedy: no chunk could have taken the next run as well
    for s, e in zip(cuts[:-2], cuts[1:-1]):
        nxt = min(r for r in RUN_STARTS | {C.size} if r > e)
        assert nxt - s > bound
    # the kernel's zero-on-slot-change contract holds in every call
    for s, e in zip(cuts[:-1], cuts[1:]):
        assert first_accumulation_hazard(C[s:e]) is None
        assert s == 0 or C[s] != C[s - 1]


def test_task_chunks_refuses_run_longer_than_bound():
    with pytest.raises(ValueError, match="more than 3 tasks"):
        task_chunks(np.array([0, 1, 1, 1, 1, 2]), 3)
    with pytest.raises(ValueError, match="more than 3 tasks"):
        task_chunks(C, 3)


@pytest.mark.parametrize("bound", BOUNDS)
@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_split_call_equals_unsplit(mode, bound):
    p = _problem(C)
    kw = dict(interpret=True) if mode == "interpret" else {}
    whole = _fused(p, **kw)
    split = _fused(p, _max_tasks=bound, **kw)
    assert len(task_chunks(C, bound)) > 1
    np.testing.assert_array_equal(split, whole)


@pytest.mark.parametrize("bound", [4, 7])
def test_block_spmm_split_equals_unsplit(bound):
    p = _problem(C)
    args = (p["a_store"], p["b_store"],
            np.minimum(p["a_off"], 5), np.minimum(p["b_off"], 5), C, p["num_out"])
    whole = np.asarray(block_spmm(*args, interpret=True))
    split = np.asarray(block_spmm(*args, interpret=True, _max_tasks=bound))
    np.testing.assert_array_equal(split, whole)


def test_traced_long_list_needs_chunks():
    p = _problem(C)

    def step(c):
        return fused_block_spmm(
            p["a_store"], p["a_recv"], p["b_store"], p["b_recv"],
            p["a_src"], p["a_off"], p["b_src"], p["b_off"], c, p["num_out"],
            _max_tasks=4,
        )

    with pytest.raises(ValueError, match="chunks="):
        jax.jit(step)(C)
    # with the host-computed split it runs; one jitted program fuses the
    # reference differently from the eager call, hence allclose
    got = jax.jit(lambda c: fused_block_spmm(
        p["a_store"], p["a_recv"], p["b_store"], p["b_recv"],
        p["a_src"], p["a_off"], p["b_src"], p["b_off"], c, p["num_out"],
        _max_tasks=4, chunks=task_chunks(C, 4),
    ))(C)
    np.testing.assert_allclose(np.asarray(got), _fused(p), rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_masked_tasks_keep_their_run(mode):
    # off tasks mid-run, at a run's start, and every task of row 3
    p = _problem(C)
    on = np.ones(C.size, bool)
    on[[1, 5, 9, 12]] = False
    kw = dict(interpret=True) if mode == "interpret" else {}
    got = _fused(p, on=on, **kw)
    q = {k: (v[on] if isinstance(v, np.ndarray) and v.shape == C.shape else v)
         for k, v in p.items()}
    want = _fused(q)  # the kept tasks alone, row count unchanged
    np.testing.assert_array_equal(got, want)
    assert (got[3] == 0).all()


def test_fused_task_layout_multi_device():
    # four devices, uneven lists; bound 5 gives the longest list three calls
    lists = [
        C,                                   # 15 tasks, 6 runs
        np.array([0, 0, 1, 2, 2, 2], np.int32),  # 6 tasks, 3 runs
        np.array([0, 1], np.int32),          # 2 runs: an empty chunk
        np.zeros(0, np.int32),               # no tasks at all
    ]
    c_cap = 6
    t_cap = max(x.size for x in lists)
    task_c = np.full((4, t_cap), c_cap, np.int32)  # plan padding: trash row
    for p, x in enumerate(lists):
        task_c[p, : x.size] = x
    count = np.array([x.size for x in lists])
    idx, real, lengths, n_local = fused_task_layout(task_c, count, c_cap, 5)
    K = len(lengths)
    assert n_local == 0  # no locality given: one phase
    assert K == len(task_chunks(C, 5)) and max(lengths) <= 5
    offs = np.cumsum((0,) + lengths)
    for p, x in enumerate(lists):
        # every real task appears once, in order
        assert idx[p][real[p]].tolist() == list(range(x.size))
        c_lay = np.where(idx[p] >= 0, task_c[p, np.maximum(idx[p], 0)], c_cap - 1)
        for k in range(K):
            seg = c_lay[offs[k] : offs[k + 1]]
            assert first_accumulation_hazard(seg) is None
            n_real = int(real[p, offs[k] : offs[k + 1]].sum())
            if n_real == 0:
                # an empty chunk writes only a row no task of p uses
                assert (seg == c_cap - 1).all() and c_cap - 1 not in x
            else:
                # a chunk starts a run, and padding stays on its last row
                first = int(idx[p, offs[k]])
                assert first == 0 or x[first] != x[first - 1]
                assert (seg[n_real:] == seg[n_real - 1]).all()


def test_fused_task_layout_padding_contributes_nothing():
    # the laid-out device lists, flagged, give each device's unsplit result
    lists = [C, np.array([0, 1, 1, 2], np.int32)]
    c_cap, t_cap = 6, C.size
    task_c = np.full((2, t_cap), c_cap, np.int32)
    for p, x in enumerate(lists):
        task_c[p, : x.size] = x
    count = np.array([x.size for x in lists])
    idx, real, lengths, _ = fused_task_layout(task_c, count, c_cap, 5)
    for p, x in enumerate(lists):
        q = _problem(x, seed=p)
        want = np.zeros((c_cap, 16, 16), np.float32)
        want[: q["num_out"]] = _fused(q, interpret=True)
        lay = lambda v: v[np.maximum(idx[p], 0)] * (idx[p] >= 0)
        got = np.asarray(fused_block_spmm(
            q["a_store"], q["a_recv"], q["b_store"], q["b_recv"],
            lay(q["a_src"]), lay(q["a_off"]), lay(q["b_src"]), lay(q["b_off"]),
            np.where(idx[p] >= 0, x[np.maximum(idx[p], 0)] if x.size else 0,
                     c_cap - 1).astype(np.int32),
            c_cap, on=real[p], chunks=lengths, interpret=True,
        ))
        used = np.unique(x)
        np.testing.assert_array_equal(got[used], want[used])


def test_executable_split_bit_identical():
    # the SPMD executables with a tiny per-call bound: same C, bit for bit
    from repro.core.distributed import (
        FusedSpgemmExecutable,
        MaskedFusedSpgemmExecutable,
        make_worker_mesh,
    )
    from repro.core.families import band_block_coords
    from repro.core.schedule import make_spgemm_plan

    class Tiny(FusedSpgemmExecutable):
        _max_tasks = 8

    class TinyMasked(MaskedFusedSpgemmExecutable):
        _max_tasks = 8

    coords = band_block_coords(12, 2)
    plan = make_spgemm_plan(coords, coords, 1, 8, exchange="p2p")
    mesh = make_worker_mesh(1)
    rng = np.random.default_rng(5)
    store = jax.device_put(
        rng.standard_normal((1, plan.a_cap, 8, 8)).astype(np.float32))
    whole = FusedSpgemmExecutable(plan, mesh)
    tiny = Tiny(plan, mesh)
    assert whole.chunks == (plan.t_cap,) and len(tiny.chunks) > 1
    np.testing.assert_array_equal(
        np.asarray(tiny(store, store)), np.asarray(whole(store, store)))
    valid = np.arange(plan.t_cap)[None, :] < plan.task_count[:, None]
    on = valid & (rng.random(valid.shape) < 0.6)
    got = np.asarray(TinyMasked(plan, mesh, prune_exchange=False)(store, store, on))
    want = np.asarray(
        MaskedFusedSpgemmExecutable(plan, mesh, prune_exchange=False)(store, store, on))
    np.testing.assert_array_equal(got, want)


# -- two-phase layout: rows that need no received block first ---------------

def _plan(structure: str, nparts: int):
    from repro.core.families import band_block_coords, morton_sort, random_block_starts
    from repro.core.schedule import make_spgemm_plan

    coords = band_block_coords(24, 3)
    if structure == "random":
        # the band plus one dense diagonal block at a seeded position
        coords = band_block_coords(24, 2)
        have = {tuple(x) for x in coords.tolist()}
        (b0,) = random_block_starts(24, 6, 1, seed=0)
        extra = [(i, j) for i in range(b0, b0 + 6) for j in range(b0, b0 + 6)
                 if (i, j) not in have]
        coords = np.concatenate([coords, np.array(extra, np.int64)])
        coords = coords[morton_sort(coords)]
    return make_spgemm_plan(coords, coords, nparts, 8, exchange="p2p")


def _local(plan):
    return (plan.task_a_src == 0) & (plan.task_b_src == 0)


def _accumulation_ok(c, real, lengths):
    """Replay the kernel's zeroing on one device's laid-out rows: a row is
    zeroed at each call's first slot and where ``c`` changes.  Every row a
    real task feeds must be zeroed at or before its first contribution and
    never after it, and every call's rows ascend."""
    zeros, feeds = {}, {}
    offs = np.cumsum((0,) + tuple(lengths))
    for lo, hi in zip(offs[:-1], offs[1:]):
        seg = c[lo:hi]
        if (np.diff(seg) < 0).any():
            return False
        for s in range(lo, hi):
            if s == lo or c[s] != c[s - 1]:
                zeros.setdefault(int(c[s]), []).append(s)
            if real[s]:
                feeds.setdefault(int(c[s]), []).append(s)
    # a slot zeroes its row before it adds to it
    return all(r in zeros and max(zeros[r]) <= min(f) for r, f in feeds.items())


@pytest.mark.parametrize("max_tasks", [FUSED_MAX_TASKS, 40])
@pytest.mark.parametrize("structure", ["banded", "random"])
def test_two_phase_layout(structure, max_tasks):
    from repro.core.distributed import fused_task_arrays

    plan = _plan(structure, 4)
    idx, real, lengths, n_local = fused_task_layout(
        plan.task_c, plan.task_count, plan.c_cap, max_tasks, _local(plan))
    _, _, today, _ = fused_task_layout(
        plan.task_c, plan.task_count, plan.c_cap, max_tasks)
    assert 1 <= n_local < len(lengths)
    n1 = sum(lengths[:n_local])
    a_src, _, b_src, _, c = fused_task_arrays(plan, idx, n1)
    # every phase-1 slot, padding included, reads the own store alone
    assert (a_src[:, :n1] == 0).all() and (b_src[:, :n1] == 0).all()
    offs = np.cumsum((0,) + lengths)
    for p in range(plan.nparts):
        n = int(plan.task_count[p])
        order = idx[p][real[p]]
        assert sorted(order.tolist()) == list(range(n))  # each task once
        calls = [set(c[p, lo:hi][real[p, lo:hi]].tolist())
                 for lo, hi in zip(offs[:-1], offs[1:])]
        # no row in two calls, and each row keeps its tasks in plan order
        assert sum(map(len, calls)) == len(set().union(*calls))
        for row in set().union(*calls):
            mine = order[plan.task_c[p, order] == row]
            assert (np.diff(mine) == 1).all()
        assert _accumulation_ok(c[p], real[p], lengths)
    if max_tasks == FUSED_MAX_TASKS:
        # one run of padding more than today's single call, at most
        c_runs = [np.diff(np.nonzero(np.diff(
            np.r_[-1, plan.task_c[p, : plan.task_count[p]], -2]))[0])
            for p in range(plan.nparts)]
        assert sum(lengths) <= today[0] + max(r.max() for r in c_runs)


@pytest.mark.parametrize("structure", ["banded", "random"])
def test_two_phase_layout_one_device_is_today(structure):
    # no exchange rounds: every task is local and the layout is unchanged
    plan = _plan(structure, 1)
    assert not plan.a_offsets and _local(plan)[0, : plan.task_count[0]].all()
    for max_tasks in (FUSED_MAX_TASKS, 40):
        got = fused_task_layout(
            plan.task_c, plan.task_count, plan.c_cap, max_tasks, _local(plan))
        want = fused_task_layout(plan.task_c, plan.task_count, plan.c_cap, max_tasks)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        assert got[2:] == want[2:] and got[3] == 0


def _hand_built(lists):
    """``(task_c, task_count, local, c_cap)`` from per-device
    ``(rows, remote rows)``."""
    c_cap = max(int(x.max()) + 1 for x, _ in lists)
    t_cap = max(x.size for x, _ in lists)
    task_c = np.full((len(lists), t_cap), c_cap, np.int32)
    local = np.ones(task_c.shape, bool)
    for p, (x, remote) in enumerate(lists):
        task_c[p, : x.size] = x
        local[p, : x.size] = ~np.isin(x, remote)
    return task_c, np.array([x.size for x, _ in lists]), local, c_cap


def _phase_problem(x, loc, seed, bs=16, n_store=6, rounds=2, cap_u=4):
    """A device problem whose remote tasks read a receive buffer."""
    rng = np.random.default_rng(seed)
    T = x.size
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    a_src = np.where(loc, 0, rng.integers(1, rounds + 1, T)).astype(np.int32)
    b_src = np.where(loc | (rng.random(T) < 0.5), 0,
                     rng.integers(1, rounds + 1, T)).astype(np.int32)
    off = lambda src: np.where(
        src == 0, rng.integers(0, n_store, T), rng.integers(0, cap_u, T)
    ).astype(np.int32)
    return dict(
        a_store=f32(n_store, bs, bs), a_recv=f32(rounds, cap_u, bs, bs),
        b_store=f32(n_store, bs, bs), b_recv=f32(rounds, cap_u, bs, bs),
        a_src=a_src, a_off=off(a_src), b_src=b_src, b_off=off(b_src),
        c_idx=x, num_out=int(x.max()) + 1,
    )


@pytest.mark.parametrize("case", ["no_remote_row", "first_run_too_long", "no_local_row"])
def test_two_phase_layout_hazards(case):
    import functools
    from types import SimpleNamespace

    from repro.core.distributed import _fused_phases, fused_task_arrays

    if case == "no_remote_row":
        # device 0 is all local and the smallest (L1 = 3): its last run moves
        # to phase 2, which every device needs; device 1's local rows 2 and
        # 4 are surplus and go to phase 2 too
        lists = [(np.array([0, 0, 1], np.int32), []),
                 (np.array([0, 0, 0, 1, 2, 2, 3, 4, 4], np.int32), [1, 3])]
        want_ph1 = [{0}, {0}]
    elif case == "first_run_too_long":
        # device 0's one local row has more tasks than L1 = 2: its phase-1
        # call is empty and points at its first phase-2 row, a remote one
        lists = [(np.array([0, 1, 1, 1, 1], np.int32), [0]),
                 (np.array([0, 0, 1], np.int32), [1])]
        want_ph1 = [set(), {0}]
    else:
        # device 1 has no all-local row: L1 == 0, today's layout
        lists = [(np.array([0, 0, 1, 2, 2], np.int32), [1]),
                 (np.array([0, 1, 1], np.int32), [0, 1])]
        want_ph1 = [set(), set()]
    task_c, count, local, c_cap = _hand_built(lists)
    idx, real, lengths, n_local = fused_task_layout(task_c, count, c_cap, 5, local)
    today = fused_task_layout(task_c, count, c_cap, 5)
    n1 = sum(lengths[:n_local])
    ph1 = [set(task_c[p, idx[p, :n1][real[p, :n1]]].tolist()) for p in range(2)]
    assert ph1 == want_ph1
    if case == "no_local_row":
        assert n_local == 0
        np.testing.assert_array_equal(idx, today[0])
        np.testing.assert_array_equal(real, today[1])
        assert lengths == today[2]
    else:
        assert n_local >= 1 and sum(lengths) <= sum(today[2]) + 4
    qs = [_phase_problem(x, local[p, : x.size], seed=p)
          for p, (x, _) in enumerate(lists)]
    pad = lambda k: np.stack([np.pad(q[k], (0, task_c.shape[1] - q[k].size))
                              for q in qs])
    plan = SimpleNamespace(
        task_a_src=pad("a_src"), task_a_off=pad("a_off"), task_b_src=pad("b_src"),
        task_b_off=pad("b_off"), task_c=task_c, c_cap=c_cap)
    laid = fused_task_arrays(plan, idx, n1)
    # every phase-1 slot, padding included, reads the own store alone
    assert (laid[0][:, :n1] == 0).all() and (laid[2][:, :n1] == 0).all()
    spmm = functools.partial(fused_block_spmm, interpret=True)
    phases = (lengths[:n_local], lengths[n_local:]) if n_local else (lengths,)
    for p, q in enumerate(qs):
        assert _accumulation_ok(laid[4][p], real[p], lengths)
        got = np.asarray(_fused_phases(
            spmm, q["a_store"], q["a_recv"], q["b_store"], q["b_recv"],
            [x[p] for x in laid], c_cap, phases, on=real[p]))
        used = np.unique(q["c_idx"])
        np.testing.assert_array_equal(got[used], _fused(q, interpret=True)[used])
