"""Static-analysis layer: plan verifier, mutation suite, lint, cache policy.

The mutation suite is the verifier's proof of detection: every seeded
corruption must be caught by its named check, with provenance, while the
clean plan it was derived from verifies empty.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from helpers import banded_matrix, random_block_matrix

from repro.analysis import PlanError, Violation
from repro.analysis.lint import (
    Finding,
    fix_perf_counter_source,
    lint_paths,
    load_baseline,
)
from repro.analysis.mutate import (
    CORRUPTIONS,
    SHARED_CORRUPTIONS,
    NotApplicable,
    clone_plan,
)
from repro.analysis.verify import (
    verify_add_plan,
    verify_compact_plan,
    verify_payload,
    verify_spgemm_plan,
    verify_task_mask,
    verify_value,
)
from repro.core.cache import SymbolicCache
from repro.core.schedule import (
    make_spgemm_plan,
    plan_byte_provenance,
    plan_worker_bytes,
)

BS = 16


def _plan(matrix=None, nparts=4, exchange="p2p", **kw):
    m = matrix if matrix is not None else random_block_matrix(256, BS, 0.25, seed=3)
    return make_spgemm_plan(m.coords, m.coords, nparts, BS,
                            exchange=exchange, **kw)


# ---------------------------------------------------------------------------
# clean plans verify; seeded corruptions are caught
# ---------------------------------------------------------------------------


def test_clean_plan_verifies():
    plan = _plan()
    assert plan.tasks.num_tasks > 0
    assert verify_spgemm_plan(plan) == []


@pytest.mark.parametrize("name", sorted(CORRUPTIONS))
def test_mutation_caught_with_provenance(name):
    plan = _plan()
    fn, expected = CORRUPTIONS[name]
    bad, kwargs = fn(plan)
    report = verify_spgemm_plan(bad, **kwargs)
    checks = {v.check for v in report}
    assert expected in checks, (name, sorted(checks))
    caught = [v for v in report if v.check == expected]
    assert all(isinstance(v, Violation) and v.provenance for v in caught)
    assert all(v.message for v in caught)
    # the corruption never leaked into the original plan
    assert verify_spgemm_plan(plan) == []


@pytest.mark.parametrize("name", sorted(CORRUPTIONS) + sorted(SHARED_CORRUPTIONS))
def test_shared_plan_mutation_caught_with_provenance(name):
    # A·A with one shared exchange: the same invariants hold for its rounds,
    # and a block only the B side reads must still arrive
    plan = _plan(shared_exchange=True)
    assert plan.exchange_shared and plan.b_offsets == () and plan.a_offsets
    assert verify_spgemm_plan(plan) == []
    fn, expected = {**CORRUPTIONS, **SHARED_CORRUPTIONS}[name]
    bad, kwargs = fn(plan)
    caught = [v for v in verify_spgemm_plan(bad, **kwargs) if v.check == expected]
    assert caught and all(v.provenance and v.message for v in caught)
    assert verify_spgemm_plan(plan) == []


def test_shared_corruption_needs_a_shared_plan():
    with pytest.raises(NotApplicable):
        SHARED_CORRUPTIONS["shared_b_only"][0](_plan())


def test_shared_layout_violation_reported():
    # B keeping rounds of its own beside a shared exchange is refused
    plan = _plan(shared_exchange=True)
    two = _plan()
    bad = clone_plan(plan)
    for attr in ("b_offsets", "b_send", "b_send_count"):
        object.__setattr__(bad, attr, getattr(two, attr))
    assert "shared-exchange" in {v.check for v in verify_spgemm_plan(bad)}


def test_shared_exchange_refuses_two_stores_and_exchange_pruning():
    from repro.core.distributed import _exchange_keep_masks

    m = random_block_matrix(256, BS, 0.25, seed=3)
    owner = np.arange(m.coords.shape[0], dtype=np.int32) % 4
    with pytest.raises(PlanError, match="shared exchange"):
        make_spgemm_plan(m.coords, m.coords, 4, BS, shared_exchange=True,
                         a_owner=owner, b_owner=owner[::-1].copy())
    with pytest.raises(PlanError, match="shared exchange"):
        make_spgemm_plan(m.coords, m.coords, 4, BS, shared_exchange=True,
                         exchange="allgather")
    plan = _plan(shared_exchange=True)
    with pytest.raises(PlanError, match="shares one exchange"):
        _exchange_keep_masks(plan, np.ones(plan.tasks.num_tasks, bool))


def test_mutation_suite_covers_required_corruptions():
    # the acceptance list from the issue, each a distinct corruption
    assert len(CORRUPTIONS) >= 8
    required = {"send-conflict", "src-off-oob", "round-permutation",
                "use-before-receive", "c-slot-race", "owner-fingerprint",
                "mask-redirect", "capacity-mismatch"}
    assert required <= {exp for _, exp in CORRUPTIONS.values()}


# ---------------------------------------------------------------------------
# edge cases: each clean and mutated
# ---------------------------------------------------------------------------


def test_edge_empty_task_list():
    # A is diagonal, B is empty: the symbolic phase yields zero tasks
    nb = 8
    a_coords = np.stack([np.arange(nb), np.arange(nb)], axis=1)
    plan = make_spgemm_plan(a_coords, np.zeros((0, 2), np.int64), 4, BS)
    assert plan.tasks.num_tasks == 0
    assert verify_spgemm_plan(plan) == []
    bad = clone_plan(plan)
    bad.task_c[0, 0] = 0  # padded slot aimed at a live row, not the trash
    assert {"mask-redirect"} <= {v.check for v in verify_spgemm_plan(bad)}


def test_edge_single_worker_zero_rounds():
    plan = _plan(nparts=1)
    assert plan.a_offsets == () and plan.b_offsets == ()
    assert verify_spgemm_plan(plan) == []
    fn, expected = CORRUPTIONS["accumulation_order"]
    bad, kwargs = fn(plan)
    assert expected in {v.check for v in verify_spgemm_plan(bad, **kwargs)}
    # exchange corruptions are structurally inapplicable here
    with pytest.raises(NotApplicable):
        CORRUPTIONS["send_conflict"][0](plan)


def test_edge_more_parts_than_blocks():
    m = banded_matrix(64, 2, BS)  # 4x4 block rows, few blocks
    plan = _plan(matrix=m, nparts=8)
    assert plan.a_owner.shape[0] < 8 * 2  # some devices own nothing
    assert verify_spgemm_plan(plan) == []
    fn, expected = CORRUPTIONS["owner_fingerprint"]
    bad, kwargs = fn(plan)
    assert expected in {v.check for v in verify_spgemm_plan(bad, **kwargs)}


def test_edge_non_power_of_two_blocks():
    m = random_block_matrix(120, 24, 0.4, seed=5)  # 5x5 blocks of 24
    plan = make_spgemm_plan(m.coords, m.coords, 3, 24)
    assert verify_spgemm_plan(plan) == []
    fn, expected = CORRUPTIONS["capacity_mismatch"]
    bad, kwargs = fn(plan)
    assert expected in {v.check for v in verify_spgemm_plan(bad, **kwargs)}


def test_edge_fully_masked_delta_all_rounds_dropped():
    from repro.core.distributed import _exchange_keep_masks

    plan = _plan()
    nrounds = len(plan.a_offsets) + len(plan.b_offsets)
    assert nrounds > 0
    off = np.zeros(plan.tasks.num_tasks, bool)
    _, _, live_a, live_b, stats = _exchange_keep_masks(plan, off)
    assert live_a == () and live_b == ()
    assert stats["dropped_rounds"] == nrounds and stats["kept_blocks"] == 0
    assert verify_task_mask(plan, off) == []  # no kept task starves
    # a partial mask over a corrupted span memo is caught
    on = np.ones(plan.tasks.num_tasks, bool)
    assert verify_task_mask(plan, on) == []
    from repro.core.distributed import _send_task_spans

    bad = clone_plan(plan)
    maps = {k: (s.copy(), c.copy()) for k, (s, c) in
            _send_task_spans(bad).items()}
    (name, d) = next(iter(maps))
    starts, cat = maps[(name, d)]
    maps[(name, d)] = (np.zeros_like(starts), cat)  # every span empty
    object.__setattr__(bad, "_send_task_spans", maps)
    assert {"exchange-starvation"} <= {v.check for v in verify_task_mask(bad, on)}
    assert {"exchange-starvation"} <= {v.check for v in verify_spgemm_plan(bad)}


# ---------------------------------------------------------------------------
# planner guards survive -O (typed PlanError, not assert)
# ---------------------------------------------------------------------------


def test_mismatched_owner_shapes_raise_plan_error():
    m = random_block_matrix(128, BS, 0.3)
    with pytest.raises(PlanError, match="owner maps do not match"):
        make_spgemm_plan(m.coords, m.coords, 4, BS,
                         a_owner=np.zeros(m.coords.shape[0] + 1, np.int32))
    with pytest.raises(PlanError, match="outside the mesh"):
        make_spgemm_plan(m.coords, m.coords, 4, BS,
                         b_owner=np.full(m.coords.shape[0], 7, np.int32))


# ---------------------------------------------------------------------------
# cache admission policy
# ---------------------------------------------------------------------------


def test_cache_rejects_corrupt_plan_and_traces_violations():
    from repro.obs.tracer import Tracer

    plan = _plan()
    bad, _ = CORRUPTIONS["send_conflict"][0](plan)
    tr = Tracer()
    cache = SymbolicCache(tracer=tr)
    with pytest.raises(PlanError) as exc:
        cache.get_or_build(("spgemm", "k1"), lambda: (bad, None))
    assert exc.value.violations and exc.value.violations[0].provenance
    assert ("spgemm", "k1") not in cache  # bad plans are never admitted
    events = tr.instants_of("plan_verify_violation", "analysis")
    assert events and events[0]["check"] == "send-conflict"
    assert cache.verify_violations >= 1
    assert tr.counter("verify_violations").value >= 1


def test_cached_once_pays_nothing_on_hits():
    plan = _plan()
    cache = SymbolicCache()  # default verify="cached-once"
    cache.get_or_build(("spgemm", "k"), lambda: (plan, None))
    assert cache.plans_verified == 1 and cache.verify_s > 0.0
    verified, spent = cache.plans_verified, cache.verify_s
    for _ in range(5):  # zero-miss replay: no verification work at all
        cache.get_or_build(("spgemm", "k"), lambda: (plan, None))
    assert cache.hits == 5
    assert cache.plans_verified == verified
    assert cache.verify_s == spent  # exact: the hook never ran

    always = SymbolicCache(verify="always")
    always.get_or_build(("spgemm", "k"), lambda: (plan, None))
    always.get_or_build(("spgemm", "k"), lambda: (plan, None))
    assert always.plans_verified == 2  # re-proved on the hit too

    off = SymbolicCache(verify="off")
    off.get_or_build(("spgemm", "k"), lambda: (plan, None))
    assert off.plans_verified == 0 and off.verify_s == 0.0

    with pytest.raises(ValueError):
        SymbolicCache(verify="sometimes")


def test_unverifiable_values_pass_through():
    cache = SymbolicCache()
    assert cache.get_or_build(("trace", "k"), lambda: 42.0) == 42.0
    assert cache.plans_verified == 0  # nothing verifiable: no counter tick
    assert verify_value(("trace", "k"), 42.0) is None


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------


def test_lint_repo_clean():
    findings, waived = lint_paths()
    assert findings == [], "\n".join(str(f) for f in findings)
    # the baseline waives exactly the tracer's default clock
    assert {f.key for f in waived} <= load_baseline()
    assert any(f.key == "obs/tracer.py::perf-counter" for f in waived)


def test_lint_rules_fire(tmp_path):
    bad = tmp_path / "offender.py"
    bad.write_text(textwrap.dedent("""
        import time
        from time import perf_counter

        def slow():
            return time.perf_counter()

        class Exe:
            def _build_program(self):
                import numpy as np
                x = np.asarray(self.dev)
                x.block_until_ready()
                return jax.device_get(x)

        def _mapped_body(store):
            return np.asarray(store)

        def key(a, b, mesh, precision):
            return ("spamm-delta", mesh_key(mesh), str(a.dtype))
    """))
    findings, _ = lint_paths([bad], baseline=set())
    rules = sorted({f.rule for f in findings})
    assert rules == ["host-sync", "perf-counter", "plan-key-fields"]
    sync = [f for f in findings if f.rule == "host-sync"]
    assert len(sync) == 4  # asarray + block_until_ready + device_get + mapped
    assert all(isinstance(f, Finding) and f.line > 0 for f in findings)
    # the baseline waives by path::rule key
    waiveall = {f.key for f in findings}
    clean, waived = lint_paths([bad], baseline=waiveall)
    assert clean == [] and len(waived) == len(findings)


def test_lint_allows_clean_key_and_timing_home(tmp_path):
    home = tmp_path / "obs"
    home.mkdir()
    (home / "timing.py").write_text("from time import perf_counter\n")
    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent("""
        def key(a, b, mesh, precision):
            return ("spamm", mesh_key(mesh), str(a.dtype), str(b.dtype),
                    precision.key())

        def host_key(a, b):
            return ("spgemm", a.structure_key, b.structure_key)
    """))
    findings, _ = lint_paths([tmp_path], baseline=set())
    assert findings == []


# ---------------------------------------------------------------------------
# CLI + verification on real executables over a multi-device mesh
# ---------------------------------------------------------------------------


def test_cli_selftest_clean():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--selftest"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "analysis: clean" in proc.stdout


_MESH_SCRIPT = """
import json
import numpy as np
from repro.core import BSMatrix
from repro.core.distributed import make_worker_mesh
from repro.dist import PlanCache, scatter
from repro.dist.multiply import dist_multiply
from repro.dist.collectives import dist_transpose
from repro.dist.matrix import resident_block_norms

rng = np.random.default_rng(0)
nb, bs = 12, 16
mask = (np.abs(np.arange(nb)[:, None] - np.arange(nb)[None]) <= 2)
a = np.zeros((nb * bs, nb * bs), np.float32)
for i, j in zip(*np.nonzero(mask)):
    a[i*bs:(i+1)*bs, j*bs:(j+1)*bs] = rng.standard_normal((bs, bs))
A = BSMatrix.from_dense(a, bs)
mesh = make_worker_mesh(4)
cache = PlanCache(verify="always")
dA = scatter(A, mesh)
c1 = dist_multiply(dA, dA, cache=cache)
c2 = dist_multiply(dA, dA, cache=cache)  # hit path re-verifies
t = dist_transpose(dA, cache=cache)
norms = resident_block_norms(dA, cache=cache)
st = cache.stats()
print("RESULT " + json.dumps(dict(
    verified=st["plans_verified"], violations=st["verify_violations"],
    verify_s=st["verify_s"], hits=st["hits"])))
"""


def test_verify_always_on_real_mesh_executables():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    # multiply plan (miss + re-verified hit), transpose, norm table all proved
    assert out["verified"] >= 4
    assert out["violations"] == 0
    assert out["verify_s"] > 0.0
    assert out["hits"] >= 1

# ---------------------------------------------------------------------------
# property-based plan fuzzing: random structures x random owner pins
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 10**6),
    nparts=st.sampled_from([1, 2, 3, 4, 8]),
    density=st.floats(0.05, 0.6),
    pin=st.sampled_from(["morton", "skew", "random"]),
    exchange=st.sampled_from(["p2p", "allgather"]),
)
def test_fuzz_pinned_plans_verify_and_ledger_matches_worker_bytes(
        seed, nparts, density, pin, exchange):
    m = random_block_matrix(128, BS, density, seed=seed)
    nb = m.coords.shape[0]
    if pin == "skew":
        owner = np.zeros(nb, np.int32)
    elif pin == "random":
        owner = np.random.default_rng(seed).integers(
            0, nparts, nb).astype(np.int32)
    else:
        owner = None
    plan = make_spgemm_plan(m.coords, m.coords, nparts, BS,
                            exchange=exchange, a_owner=owner, b_owner=owner)
    assert verify_spgemm_plan(plan) == []
    # the ledger's per-task byte decomposition conserves and sums to the
    # load balancer's plan_worker_bytes totals exactly
    prov = plan_byte_provenance(plan)
    assert np.array_equal(prov["local"] + prov["shipped"], prov["referenced"])
    recv, send, _ = plan_worker_bytes(plan)
    assert np.array_equal(prov["wire_recv"], recv)
    assert np.array_equal(prov["wire_send"], send)
    if exchange == "p2p":
        assert np.array_equal(prov["shipped"], recv)
    assert prov["task_local"].shape == (nparts, plan.task_gidx.shape[1])
    assert np.array_equal(prov["task_local"].sum(axis=1), prov["local_tasks"])


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 10**6),
    nparts=st.sampled_from([1, 2, 3, 4, 8]),
    density=st.floats(0.05, 0.6),
    pin=st.sampled_from(["morton", "skew", "random"]),
)
def test_fuzz_shared_plans_verify_and_ship_the_union(seed, nparts, density, pin):
    m = random_block_matrix(128, BS, density, seed=seed)
    nb = m.coords.shape[0]
    owner = {"skew": np.zeros(nb, np.int32),
             "random": np.random.default_rng(seed).integers(
                 0, nparts, nb).astype(np.int32),
             "morton": None}[pin]
    kw = dict(a_owner=owner, b_owner=owner)
    plan = make_spgemm_plan(m.coords, m.coords, nparts, BS,
                            shared_exchange=True, **kw)
    two = make_spgemm_plan(m.coords, m.coords, nparts, BS, **kw)
    assert verify_spgemm_plan(plan) == []
    # one set of rounds, the union of both sides' needs: never more than
    # the two exchanges receive, and the ledger still conserves
    recv, send, padded = plan_worker_bytes(plan)
    recv2, _, padded2 = plan_worker_bytes(two)
    assert (recv <= recv2).all() and (padded <= padded2).all()
    prov = plan_byte_provenance(plan)
    assert np.array_equal(prov["local"] + prov["shipped"], prov["referenced"])
    assert np.array_equal(prov["shipped"], recv)
    assert np.array_equal(prov["wire_send"], send)
    # the same tasks in the same order: C's bits cannot change
    assert np.array_equal(plan.task_gidx, two.task_gidx)


# ---------------------------------------------------------------------------
# add / compact verifiers
# ---------------------------------------------------------------------------


def _add_payload():
    """A real AddExecutable's host-side plan copy (single-device mesh)."""
    from repro.core import BSMatrix
    from repro.core.distributed import make_worker_mesh
    from repro.dist import scatter
    from repro.dist.collectives import AddExecutable

    rng = np.random.default_rng(0)
    n, bs = 32, 8
    da = np.zeros((n, n), np.float32)
    da[:16, :16] = rng.standard_normal((16, 16))
    db = np.zeros((n, n), np.float32)
    db[8:24, 8:24] = rng.standard_normal((16, 16))
    mesh = make_worker_mesh(1)
    exe = AddExecutable(scatter(BSMatrix.from_dense(da, bs), mesh),
                        scatter(BSMatrix.from_dense(db, bs), mesh))
    return exe._verify_plan


def test_add_plan_clean_and_dispatched():
    payload = _add_payload()
    assert payload["kind"] == "add"
    assert verify_add_plan(payload) == []
    assert verify_payload(payload) == []  # kind-dispatch reaches it


def test_add_plan_catches_union_and_gather_corruption():
    payload = _add_payload()
    live = np.nonzero(payload["from_a"] >= 0)[0]
    assert live.size >= 2

    # duplicate a source: one A block dropped, another double-counted
    bad = dict(payload)
    bad["from_a"] = payload["from_a"].copy()
    bad["from_a"][live[1]] = bad["from_a"][live[0]]
    assert "add-union" in {v.check for v in verify_add_plan(bad)}

    # zero the gather weight of a live operand: contribution silently lost
    bad = dict(payload)
    bad["val_a"] = payload["val_a"].copy()
    p, slot = np.argwhere(bad["val_a"] == 1.0)[0]
    bad["val_a"][p, slot] = 0.0
    assert "operand-mismatch" in {v.check for v in verify_add_plan(bad)}

    # weight on a padding slot: garbage accumulated into a live block
    bad = dict(payload)
    bad["val_b"] = payload["val_b"].copy()
    pad = np.argwhere(payload["val_b"] == 0.0)
    if pad.size:
        bad["val_b"][pad[0][0], pad[0][1]] = 1.0
        assert "mask-redirect" in {v.check for v in verify_add_plan(bad)}


def _compact_payload():
    a_owner = np.array([0, 1, 0, 1], np.int32)
    a_slot = np.array([0, 0, 1, 1], np.int32)
    kept = np.array([0, 3], np.int64)
    return dict(
        kind="compact", label="truncate", nparts=2,
        a_owner=a_owner, a_slot=a_slot, a_cap=2, kept=kept,
        new_owner=a_owner[kept], new_slot=np.array([0, 0], np.int32),
        new_cap=1,
        gidx=np.array([[0], [1]], np.int32),
        gval=np.ones((2, 1), np.float32),
    )


def test_compact_plan_clean_and_dispatched():
    payload = _compact_payload()
    assert verify_compact_plan(payload) == []
    assert verify_payload(payload) == []


def test_compact_plan_catches_corruption():
    # a kept block changing owners: compaction must be communication-free
    bad = _compact_payload()
    bad["new_owner"] = np.array([1, 0], np.int32)
    bad["new_slot"] = np.array([0, 0], np.int32)
    assert "owner-fingerprint" in {v.check for v in verify_compact_plan(bad)}

    # gather aimed at the wrong source slot
    bad = _compact_payload()
    bad["gidx"] = np.array([[1], [1]], np.int32)
    assert "operand-mismatch" in {v.check for v in verify_compact_plan(bad)}

    # kept index outside the block stack
    bad = _compact_payload()
    bad["kept"] = np.array([0, 9], np.int64)
    assert "owner-map" in {v.check for v in verify_compact_plan(bad)}


# ---------------------------------------------------------------------------
# lint --fix: mechanical perf-counter rewrites, idempotent
# ---------------------------------------------------------------------------

_FIXABLE = textwrap.dedent("""\
    import time
    from time import perf_counter

    def work(busy):
        t0 = perf_counter()
        now = time.perf_counter()
        busy(now)
        dt = time.perf_counter() - t0
        return dt
""")


def test_lint_fix_rewrites_and_is_idempotent(tmp_path):
    import ast

    fixed, n = fix_perf_counter_source(_FIXABLE)
    assert n > 0
    ast.parse(fixed)  # still valid python
    # paired names become stopwatches, unpaired reads become wall clock
    assert "t0 = Stopwatch()" in fixed
    assert "dt = t0.elapsed()" in fixed
    assert "now = wall_clock()" in fixed
    assert "perf_counter" not in fixed
    assert "from repro.obs.timing import Stopwatch, wall_clock" in fixed
    # idempotent: a second pass finds nothing to do
    again, n2 = fix_perf_counter_source(fixed)
    assert n2 == 0 and again == fixed
    # and the fixed module lints clean of the perf-counter rule
    mod = tmp_path / "mod.py"
    mod.write_text(fixed)
    findings, _ = lint_paths([mod], baseline=set())
    assert not [f for f in findings if f.rule == "perf-counter"]


def test_lint_fix_cli_flag(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(_FIXABLE)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--lint-only", "--fix",
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FIX" in proc.stdout, proc.stdout + proc.stderr
    fixed = mod.read_text()
    assert "perf_counter" not in fixed and "Stopwatch()" in fixed
