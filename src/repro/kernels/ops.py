"""Jit'd public wrappers around the Pallas kernels.

On the TPU the kernels compile to Mosaic; elsewhere the block-sparse ops run
their jnp references (pass ``interpret=True`` to run the Pallas interpreter
instead, as tests do).  On the TPU there is no silent fallback: a block shape
the kernel cannot take is an error.

Task lists longer than one call's SMEM bound are split into several calls
at ``c``-run boundaries, each accumulating into the same output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .block_spmm import block_spmm_kernel_call
from .fused_leaf import (
    FLAG_LOW,
    FLAG_ON,
    fused_block_spmm_kernel_call,
    fused_block_spmm_ref,
)

__all__ = [
    "block_spmm",
    "fused_block_spmm",
    "flash_attention",
    "task_chunks",
    "max_tasks_per_call",
    "FUSED_MAX_TASKS",
    "BLOCK_SPMM_MAX_TASKS",
]

# Scalar-prefetch operands live in SMEM, 1 MiB on a v5e core, at 4 bytes
# per int32 task index array.  A v5e compile of the fused kernel measured
# exactly 24 bytes per task (1.50 MiB at 65,536 tasks, refused), so a quarter
# of SMEM is left as margin for the compiler's own scalars.
SMEM_BYTES = 1 << 20


def max_tasks_per_call(n_prefetch: int) -> int:
    """Tasks one kernel call may carry with ``n_prefetch`` int32 arrays."""
    return (SMEM_BYTES * 3 // 4) // (4 * n_prefetch)


FUSED_MAX_TASKS = max_tasks_per_call(6)  # 32,768
BLOCK_SPMM_MAX_TASKS = max_tasks_per_call(3)  # 65,536


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_takes(bm: int, bk: int, bn: int) -> bool:
    return min(bm, bk, bn) >= 8 and not (bm % 8 or bk % 8 or bn % 8)


def _check_tpu_shape(name: str, bm: int, bk: int, bn: int) -> None:
    if not _kernel_takes(bm, bk, bn):
        raise ValueError(
            f"{name}: the TPU kernel cannot take blocks of {bm}x{bk} @ "
            f"{bk}x{bn}; every block dimension must be a multiple of 8")


def task_chunks(c_idx, max_tasks: int) -> tuple[int, ...]:
    """Lengths of consecutive task-list chunks of at most ``max_tasks``
    tasks, each ending at a boundary between runs of equal ``c_idx``.

    Every chunk then starts a new output row, so the kernel's
    zero-on-slot-change contract holds in each call on its own.  Host-side;
    ``c_idx`` must be concrete.
    """
    c = np.asarray(c_idx).reshape(-1)
    T = c.shape[0]
    if T <= max_tasks:
        return (T,)
    starts = np.concatenate([[0], np.nonzero(np.diff(c))[0] + 1])
    lengths, s = [], 0
    while T - s > max_tasks:
        # last run start that keeps this chunk within the bound
        e = int(starts[np.searchsorted(starts, s + max_tasks, "right") - 1])
        if e <= s:
            raise ValueError(
                f"output row {int(c[s])} takes more than {max_tasks} tasks, "
                "more than one kernel call can carry")
        lengths.append(e - s)
        s = e
    lengths.append(T - s)
    return tuple(lengths)


def _chunks_of(c_idx, chunks, max_tasks: int) -> tuple[int, ...]:
    T = c_idx.shape[0]
    if chunks is not None:
        assert sum(chunks) == T and max(chunks) <= max_tasks, (chunks, T)
        return tuple(chunks)
    if T <= max_tasks:
        return (T,)
    if isinstance(c_idx, jax.core.Tracer):
        raise ValueError(
            f"{T} tasks exceed one kernel call's {max_tasks}; a traced task "
            "list must come with chunks= (see task_chunks)")
    return task_chunks(c_idx, max_tasks)


def _run_chunks(call, per_task, chunks, acc=None):
    """Run ``call(*slices, acc)`` over consecutive chunks of the per-task
    arrays, threading the accumulated output through (from ``acc``, if
    given)."""
    out, s = acc, 0
    for n in chunks:
        out = call(*(x[s : s + n] for x in per_task), out)
        s += n
    return out


def block_spmm(
    a_data: jax.Array,
    b_data: jax.Array,
    a_idx: jax.Array,
    b_idx: jax.Array,
    c_idx: jax.Array,
    num_out: int,
    *,
    interpret: bool | None = None,
    _max_tasks: int = BLOCK_SPMM_MAX_TASKS,
) -> jax.Array:
    """Grouped block matmul: C[c[t]] += A[a[t]] @ B[b[t]], c sorted ascending.

    Contract: every output row in [0, num_out) must receive at least one
    task, except an optional TRAILING trash region (padded tasks), whose
    content is unspecified — callers slice it off.  The symbolic phase and
    the distributed scheduler both satisfy this by construction.

    A concrete task list longer than one call can carry runs as several
    calls (see :func:`task_chunks`).  Returns fp32 [num_out, bm, bn].
    """
    if a_idx.shape[0] == 0:
        return jnp.zeros((num_out, a_data.shape[1], b_data.shape[2]), jnp.float32)
    bm, bk, bn = a_data.shape[1], a_data.shape[2], b_data.shape[2]
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tpu_shape("block_spmm", bm, bk, bn)
    elif not _kernel_takes(bm, bk, bn):
        # tiny/odd blocks (tests, partial leaves) go through the oracle
        return ref.block_spmm_ref(a_data, b_data, a_idx, b_idx, c_idx, num_out)
    interpret = (not on_tpu) if interpret is None else interpret
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return _run_chunks(
        lambda a, b, c, acc: block_spmm_kernel_call(
            a_data, b_data, a, b, c, acc, num_out=num_out, interpret=interpret,
        ),
        (i32(a_idx), i32(b_idx), i32(c_idx)),
        _chunks_of(c_idx, None, _max_tasks),
    )


def fused_block_spmm(
    a_store: jax.Array,
    a_recv: jax.Array,
    b_store: jax.Array,
    b_recv: jax.Array,
    a_src: jax.Array,
    a_off: jax.Array,
    b_src: jax.Array,
    b_off: jax.Array,
    c_idx: jax.Array,
    num_out: int,
    *,
    on: jax.Array | None = None,
    low: jax.Array | None = None,
    adaptive: bool = False,
    interpret: bool | None = None,
    chunks: tuple[int, ...] | None = None,
    acc: jax.Array | None = None,
    _max_tasks: int = FUSED_MAX_TASKS,
) -> jax.Array:
    """Fused unpack + grouped block matmul + accumulate (the leaf engine).

    Task operands are addressed as ``(src, off)`` pairs over the device's
    own store and the stacked receive buffers — see
    :mod:`repro.kernels.fused_leaf` for the layout and the accumulation
    contract (same as :func:`block_spmm`, trailing trash row included).
    ``on`` (bool per task) masks tasks out in place: they keep their ``c``
    and contribute nothing, so a masked list stays sorted and a row whose
    tasks are all off reads zero.  ``low`` (per task, ``adaptive`` only)
    rounds that task's operands to bf16.

    Dispatch: compiled Mosaic on TPU, the fused jnp/segment-sum reference
    elsewhere (pass ``interpret=True`` to force the Pallas interpreter —
    tests do, production CPU paths should not: interpret mode is orders of
    magnitude slower than the reference).  On the CPU, block sizes the
    kernel cannot take run the reference; on the TPU they raise.  A task
    list longer than one call's SMEM bound runs as several calls split at
    ``c``-run boundaries (``chunks``, or :func:`task_chunks` of a concrete
    ``c_idx``).  ``acc`` continues an earlier call's output: rows this
    list does not visit keep their values.  Returns fp32
    ``[num_out, bm, bn]``.
    """
    bm, bk, bn = a_store.shape[1], a_store.shape[2], b_store.shape[2]
    if a_src.shape[0] == 0:
        return jnp.zeros((num_out, bm, bn), jnp.float32) if acc is None else acc
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    flags = jnp.full(a_src.shape, FLAG_ON, jnp.int32)
    if on is not None:
        flags = jnp.where(jnp.asarray(on, bool), flags, 0)
    if adaptive and low is not None:
        flags = flags | jnp.where(jnp.asarray(low) != 0, FLAG_LOW, 0)
    on_tpu = _on_tpu()
    if on_tpu:
        _check_tpu_shape("fused_block_spmm", bm, bk, bn)
    use_kernel = on_tpu if interpret is None else True
    per_task = tuple(i32(x) for x in (a_src, a_off, b_src, b_off, c_idx)) + (flags,)
    chunks = _chunks_of(c_idx, chunks, _max_tasks)
    if not use_kernel or not _kernel_takes(bm, bk, bn):
        # the reference has no SMEM bound; it runs the same chunks so the
        # split is tested on the CPU (chunks write disjoint rows, so adding
        # their results is exact)
        def call(*xs):
            part = fused_block_spmm_ref(
                a_store, a_recv, b_store, b_recv, *xs[:-1],
                num_out=num_out, adaptive=adaptive,
            )
            return part if xs[-1] is None else xs[-1] + part
    else:
        def call(*xs):
            return fused_block_spmm_kernel_call(
                a_store, a_recv, b_store, b_recv, *xs,
                num_out=num_out, adaptive=adaptive, interpret=bool(interpret),
            )
    return _run_chunks(call, per_task, chunks, acc)


def grouped_gemm_varsize(
    x: jax.Array,
    group_sizes,
    w: jax.Array,
    *,
    tile_m: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """MegaBlocks-style dropless grouped GEMM through the paper's kernel.

    x: [T, K] rows sorted by group (tokens sorted by expert);
    group_sizes: host list/array, sum == T; w: [G, K, N] per-group weights.
    Returns [T, N] with row t multiplied by its group's weight.

    The variable group boundaries become a *block-sparse task list*: x is
    tiled into [T/tile_m, tile_m, K] row blocks and each tile is paired with
    the weight(s) of the group(s) it spans — exactly the symbolic/numeric
    split of the sparse matrix library, with tokens as block rows.  Tiles
    spanning a group boundary are handled by masking each (tile, group) pair
    to the rows owned by that group — so no token is ever dropped and no
    capacity padding is computed (vs the capacity-factor path in
    repro.models.moe).
    """
    import numpy as np

    group_sizes = np.asarray(group_sizes)
    T, K = x.shape
    G, _, N = w.shape
    assert group_sizes.sum() == T, (group_sizes.sum(), T)
    pad = (-T) % tile_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nt = (T + pad) // tile_m
    # host symbolic phase: one task per (row-tile, group) pair it overlaps
    starts = np.concatenate([[0], np.cumsum(group_sizes)])
    row_group = np.repeat(np.arange(G), group_sizes)
    row_group = np.concatenate([row_group, np.full(pad, G - 1)])
    a_idx, b_idx, c_idx, mask_lo, mask_hi = [], [], [], [], []
    for t in range(nt):
        lo, hi = t * tile_m, (t + 1) * tile_m
        for g in np.unique(row_group[lo:hi]):
            a_idx.append(t)
            b_idx.append(int(g))
            c_idx.append(t)
            g_lo = int(starts[g])
            g_hi = int(starts[g + 1]) if g < G - 1 else T + pad
            mask_lo.append(max(g_lo - lo, 0))
            mask_hi.append(min(g_hi - lo, tile_m))
    xt = x.reshape(nt, tile_m, K)
    # mask each task's tile to its group's rows (numeric phase stays a pure
    # grouped block matmul; boundary tiles appear once per group)
    rows = jnp.arange(tile_m)
    sel = (rows[None, :] >= jnp.asarray(mask_lo)[:, None]) & (
        rows[None, :] < jnp.asarray(mask_hi)[:, None]
    )
    a_data = xt[jnp.asarray(a_idx)] * sel[:, :, None].astype(x.dtype)
    out = block_spmm(
        a_data,
        w.astype(x.dtype),
        jnp.arange(len(a_idx), dtype=jnp.int32),
        jnp.asarray(b_idx, jnp.int32),
        jnp.asarray(c_idx, jnp.int32),
        nt,
        interpret=interpret,
    )
    return out.reshape(nt * tile_m, N)[:T].astype(x.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Online-softmax attention (Pallas on TPU, oracle fallback elsewhere)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    from .flash_attention import flash_attention_call

    return flash_attention_call(q, k, v, causal=causal, window=window, interpret=interpret)
