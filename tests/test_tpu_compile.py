"""Compile the leaf kernels for a described TPU v5e chip, no chip attached.

The TPU compiler refuses what the Pallas interpreter accepts: tilings,
VMEM and SMEM overruns.  These compiles use the paper's Table-1 task lists
at n = 100,000, bs 512 (banded: 32,214 tasks; random blocks: 62,444, past
one call's SMEM bound, so the split into calls is compiled too).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import functools
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core.families import structure_coords  # noqa: E402
from repro.core.spgemm import spgemm_symbolic  # noqa: E402
from repro.kernels.block_spmm import block_spmm_kernel_call  # noqa: E402
from repro.kernels.ops import FUSED_MAX_TASKS, fused_block_spmm, task_chunks  # noqa: E402

BS = 512


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@functools.lru_cache(maxsize=None)
def _tasks(family: str):
    coords = structure_coords(family, 100_000, 0, BS)
    return coords.shape[0], spgemm_symbolic(coords, coords)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fused(one_chip, family, dtype=jnp.float32, adaptive=False):
    nnzb, tasks = _tasks(family)
    T = tasks.num_tasks
    chunks = task_chunks(tasks.c_idx, FUSED_MAX_TASKS)
    store = _sds((nnzb, BS, BS), dtype, one_chip)
    recv = _sds((1, 1, BS, BS), dtype, one_chip)
    idx = [_sds((T,), jnp.int32, one_chip) for _ in range(5)]
    low = _sds((T,), jnp.int32, one_chip)

    def step(a, ar, b, br, a_src, a_off, b_src, b_off, c, low):
        return fused_block_spmm(
            a, ar, b, br, a_src, a_off, b_src, b_off, c, tasks.num_out,
            low=low if adaptive else None, adaptive=adaptive,
            interpret=False, chunks=chunks,
        )

    compiled = jax.jit(step).lower(store, recv, store, recv, *idx, low).compile()
    return compiled, chunks


def _assert_kernel(compiled, calls=1):
    assert compiled.as_text().count("tpu_custom_call") >= calls


def test_fused_kernel_banded_fp32(one_chip):
    compiled, chunks = _compile_fused(one_chip, "banded")
    assert chunks == (32_214,)
    _assert_kernel(compiled)


def test_fused_kernel_banded_bf16(one_chip):
    compiled, _ = _compile_fused(one_chip, "banded", dtype=jnp.bfloat16)
    _assert_kernel(compiled)


def test_fused_kernel_banded_adaptive(one_chip):
    compiled, _ = _compile_fused(one_chip, "banded", adaptive=True)
    _assert_kernel(compiled)


def test_fused_kernel_random_family_split(one_chip):
    # 62,444 tasks need more SMEM than one call has: two calls, one output
    compiled, chunks = _compile_fused(one_chip, "random")
    assert len(chunks) == 2 and sum(chunks) == 62_444
    _assert_kernel(compiled, calls=2)


def test_block_spmm_kernel_bs512(one_chip):
    nnzb, tasks = _tasks("banded")
    T = tasks.num_tasks
    data = _sds((nnzb, BS, BS), jnp.float32, one_chip)
    idx = [_sds((T,), jnp.int32, one_chip) for _ in range(3)]
    step = functools.partial(block_spmm_kernel_call, num_out=tasks.num_out + 1)
    compiled = jax.jit(step).lower(data, data, *idx).compile()
    _assert_kernel(compiled)


def _four_chip_entry(v5e_2x2, monkeypatch, **plan_kw):
    """The four-chip fused multiply of a band, compiled: (plan, ENTRY lines)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import repro.core.distributed as D
    import repro.kernels.ops as kops
    from repro.core.families import band_block_coords
    from repro.core.schedule import make_spgemm_plan

    bs = 128
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)  # compile the kernel
    # index arrays stay on the host: the chips are described, not attached
    monkeypatch.setattr(jax, "device_put", lambda x, sharding=None: x)
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(4), (D.AXIS,))
    coords = band_block_coords(24, 3)
    plan = make_spgemm_plan(coords, coords, 4, bs, exchange="p2p", **plan_kw)
    exe = D.FusedSpgemmExecutable(plan, mesh)
    assert len(exe.phases) == 2
    sh = NamedSharding(mesh, PartitionSpec(D.AXIS))
    store = _sds((4, plan.a_cap, bs, bs), jnp.float32, sh)
    host = [_sds(x.shape, x.dtype, sh) for x in exe._idx_args + exe._send_args]
    text = exe._mapped.lower(store, store, *host).compile().as_text()
    return plan, text[text.index("\nENTRY"):].splitlines()


def _assert_overlaps_phase_one(lines):
    # XLA issues the ppermute rounds before the phase-1 kernel call and
    # waits after it
    at = lambda key: [i for i, l in enumerate(lines) if key in l]
    kernels = at('custom_call_target="tpu_custom_call"')
    starts, dones = at("collective-permute-start("), at("collective-permute-done(")
    assert len(kernels) == 2 and starts and len(dones) == len(starts)
    assert max(starts) < kernels[0] < min(dones) and max(dones) < kernels[1]
    return starts


def test_four_chip_exchange_overlaps_phase_one(v5e_2x2, monkeypatch):
    # the four-chip fused multiply, two-phase layout
    _, lines = _four_chip_entry(v5e_2x2, monkeypatch)
    _assert_overlaps_phase_one(lines)


def test_four_chip_shared_exchange_runs_once(v5e_2x2, monkeypatch):
    # A·A with one shared exchange: one ppermute per round, not two, and
    # still behind the phase-1 kernel call
    plan, lines = _four_chip_entry(v5e_2x2, monkeypatch, shared_exchange=True)
    assert plan.exchange_shared and plan.b_offsets == ()
    starts = _assert_overlaps_phase_one(lines)
    assert len(starts) == len(plan.a_offsets)
