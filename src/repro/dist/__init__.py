"""Device-resident distributed matrix runtime — the CHT worker-storage layer.

The paper's CHT-MPI runtime keeps chunks resident in worker storage and
caches the chunks tasks touch, so iterative algorithms never re-ship
operands between operations.  This package is that layer for the XLA mesh:

* :class:`DistBSMatrix` (:mod:`repro.dist.matrix`) — a sharded block-sparse
  matrix whose padded per-device stores live on the worker mesh *across*
  operations; host-side structure (coords, owner, slot maps); enters via
  :func:`scatter`, leaves via :meth:`DistBSMatrix.gather`.
* :class:`PlanCache` (:mod:`repro.dist.cache`) — structure-keyed cache of
  symbolic plans, device-resident plan arrays, and jitted shard_map
  executables, with hit/miss metrics.
* resident collectives (:mod:`repro.dist.collectives`) — ``dist_add``
  (structure union, owner-aligned re-slotting), ``dist_scale``,
  ``dist_trace`` / ``dist_frobenius_norm`` (psum reductions),
  ``dist_transpose`` (re-slot to the transposed owner layout via planned
  ppermute rounds, no host gather), ``dist_submatrix`` /
  ``dist_assemble2x2`` (quadrant slice and glue — owner-local, zero
  inter-device motion), ``dist_truncate`` / ``dist_truncate_hierarchical``
  (host symbolic selection — flat greedy or quadtree subtree-drop over the
  resident norm table — then device compaction).
* :func:`dist_multiply` / :func:`dist_spamm` (:mod:`repro.dist.multiply`) —
  C = A @ B on resident operands through the cached schedule; SpAMM prunes
  hierarchically with an error bound <= tau, by default as a *delta plan*:
  a task mask against the cached full-multiply executable, so fluctuating
  prune patterns never miss the plan cache.
* :func:`dist_inv_chol` / :func:`dist_localized_inverse_factorization`
  (:mod:`repro.dist.inverse`) — inverse factorization (Z^T A Z = I) with
  the refinement loop running entirely through delta-plan SpAMM and
  hierarchical truncation, sharing one norm-table fetch per iteration.
* :func:`dist_sp2_purify` / :func:`dist_sqrt_inv_pipeline`
  (:mod:`repro.dist.purify`) — the full SP2 loop on resident matrices with
  per-iteration cache/comm stats, and the end-to-end SPD pipeline
  S -> Z -> Z^T H Z -> SP2 -> Z D Z^T that never leaves the devices.
* dynamic load balancing (:mod:`repro.dist.balance`) — a measured
  per-worker cost model (:class:`WorkerLoad`: executed tasks, exchange
  bytes, owned leaves), a :class:`RebalancePolicy` / :class:`LoadMonitor`
  feedback loop, and the resident re-layout collective
  :func:`dist_repartition` (planned ``ppermute`` rounds, block payloads
  only); the iterative drivers take ``rebalance=`` and re-lay iterates out
  between iterations when the measured imbalance crosses the threshold, and
  :func:`relayout_for_product` lays operands out for one product and
  returns the handles.
"""

from .balance import (
    LoadMonitor,
    RebalancePolicy,
    WorkerLoad,
    owner_imbalance,
    rebalanced_owner,
    relayout_for_product,
    worker_load,
)
from .cache import PlanCache
from .collectives import (
    dist_add,
    dist_assemble2x2,
    dist_frobenius_norm,
    dist_repartition,
    dist_scale,
    dist_submatrix,
    dist_trace,
    dist_transpose,
    dist_truncate,
    dist_truncate_hierarchical,
    transpose_permutation,
)
from .inverse import (
    DistInverseStats,
    dist_inv_chol,
    dist_localized_inverse_factorization,
)
from .matrix import DistBSMatrix, dist_zeros, resident_block_norms, scatter
from .multiply import (
    dist_multiply,
    dist_spamm,
    multiply_plan_key,
    spamm_delta_plan_key,
)
from .purify import (
    DistPurifyStats,
    SqrtInvPipelineStats,
    dist_lanczos_bounds,
    dist_sp2_purify,
    dist_sqrt_inv_pipeline,
)

__all__ = [
    "DistBSMatrix",
    "scatter",
    "dist_zeros",
    "resident_block_norms",
    "PlanCache",
    "dist_add",
    "dist_scale",
    "dist_trace",
    "dist_frobenius_norm",
    "dist_transpose",
    "dist_repartition",
    "dist_submatrix",
    "dist_assemble2x2",
    "transpose_permutation",
    "dist_truncate",
    "dist_truncate_hierarchical",
    "dist_multiply",
    "dist_spamm",
    "multiply_plan_key",
    "spamm_delta_plan_key",
    "dist_inv_chol",
    "dist_localized_inverse_factorization",
    "DistInverseStats",
    "dist_sp2_purify",
    "DistPurifyStats",
    "dist_lanczos_bounds",
    "dist_sqrt_inv_pipeline",
    "SqrtInvPipelineStats",
    "RebalancePolicy",
    "LoadMonitor",
    "WorkerLoad",
    "worker_load",
    "owner_imbalance",
    "rebalanced_owner",
    "relayout_for_product",
]
