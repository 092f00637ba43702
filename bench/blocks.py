"""Block values made on the device from ``--seed``, keyed by block position.

Every block's values come from a key folded from the run's seed and the
block's (row, col), so the same seed gives the same matrix whatever the
placement, and a reference can regenerate any block on its own.  Stores are
made in one jitted call per operand, each device making only its own rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative whole ``seed``: ``jax.random.key`` keeps only
    the low 32 bits of a larger one, so the high bits are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def normal_block(key, i, j, *, bs: int):
    """N(0, 1) float32 block for block (i, j)."""
    return jax.random.normal(jax.random.fold_in(jax.random.fold_in(key, i), j),
                             (bs, bs), jnp.float32)


def make_store(fn, seed: int, rows: np.ndarray, cols: np.ndarray,
               valid: np.ndarray, mesh, axis: str) -> jax.Array:
    """``[P, cap, bs, bs]`` store sharded over ``mesh``'s ``axis``: slot
    ``(p, s)`` holds block ``(rows[p, s], cols[p, s])`` where ``valid``, zeros
    elsewhere.  One jitted call; each device generates only its own slots."""
    spec = NamedSharding(mesh, P(axis))
    tables = [jax.device_put(np.asarray(x, dtype), spec)
              for x, dtype in ((rows, np.int32), (cols, np.int32), (valid, bool))]

    def local(key, r, c, v):
        blocks = jax.vmap(lambda i, j: fn(key, i, j))(r[0], c[0])
        return jnp.where(v[0][:, None, None], blocks, 0.0)[None]

    gen = jax.jit(jax.shard_map(local, mesh=mesh,
                                in_specs=(P(), P(axis), P(axis), P(axis)),
                                out_specs=P(axis), check_vma=False))
    return gen(seed_key(seed), *tables)


@functools.partial(jax.jit, static_argnums=(0,))
def _blocks(fn, key, rows, cols):
    return jax.vmap(lambda i, j: fn(key, i, j))(rows, cols)


def host_blocks(fn, seed: int, coords: np.ndarray) -> np.ndarray:
    """The blocks at ``coords`` ([m, 2]) as float64 on the host, for the
    reference.  Padded to a power of two so a few shapes serve every call."""
    coords = np.asarray(coords, np.int32).reshape(-1, 2)
    m = coords.shape[0]
    pad = 1 << max(m - 1, 0).bit_length()
    c = np.zeros((pad, 2), np.int32)
    c[:m] = coords
    out = _blocks(fn, seed_key(seed), jnp.asarray(c[:, 0]), jnp.asarray(c[:, 1]))
    return np.asarray(out[:m], dtype=np.float64)
