"""The paper's Table-1 growing dense block: C = A·A on A laid out for it.

The structure is the band of :func:`bench.structure.band_coords` plus a
dense ``growing_block_size``-element block in the top-left corner, which
covers the first ``ceil(growing_block_size / leaf_size)`` block rows and
columns.  Values are N(0, 1), keyed by block position, as in ``table1``.

A is made on the device with the library's default Morton owners, as a
user's ``scatter`` gives them; set-up then lays it out for A·A once with
``repro.dist.relayout_for_product`` and holds the handle.  The timed call,
the check and its float64 reference are ``table1``'s: half of the sampled
blocks are built from operands that came through the exchange.

The cell's plan cache carries a ``repro.obs.Tracer``.  ``counters()``
returns its counters at the start of the window (``setup``: the re-layout's
``migrated_bytes`` among them) and what the window added (``window``: the
products' ``recv_padded_bytes`` among them).
"""

from __future__ import annotations

import functools

import numpy as np

from ..blocks import normal_block
from ..resident import resident_operand
from ..structure import band_coords, morton_sorted, product_terms
from .table1 import Table1


class Growing(Table1):
    def __init__(self, cfg: dict, mesh, seed: int, *, impl: str = "fused"):
        import repro.dist as rd
        from repro.obs import Tracer

        # looked up first: a program without it fails before any device work
        relayout = rd.relayout_for_product
        self.cfg, self.seed, self.impl = cfg, seed, impl
        self.bs, n = cfg["leaf_size"], cfg["n"]
        self.nb = -(-n // self.bs)
        self.coords = growing_coords(cfg)
        self.fn = functools.partial(normal_block, bs=self.bs)
        self.tracer = Tracer()
        self.cache = rd.PlanCache(tracer=self.tracer)
        a = resident_operand(self.fn, seed, self.coords, (n, n), self.bs, mesh)
        self.a, _ = relayout(a, a, self.cache)
        del a  # the uniform layout is dropped once the handle is held
        self.c = None
        self.terms = product_terms(self.coords, self.coords)
        self._at_start: dict = {}

    def start_window(self) -> None:
        self._at_start = self.tracer.metrics_flat()

    def counters(self) -> dict:
        now = self.tracer.metrics_flat()
        return {"setup": dict(self._at_start),
                "window": {k: v - self._at_start.get(k, 0) for k, v in now.items()}}

    def release(self) -> None:
        self.tracer.close()
        super().release()


def growing_coords(cfg: dict) -> np.ndarray:
    """Morton-sorted block coordinates of the band plus the dense corner."""
    bs, n = cfg["leaf_size"], cfg["n"]
    nb, hw = -(-n // bs), -(-cfg["half_width"] // bs)
    sb = min(-(-cfg["growing_block_size"] // bs), nb)
    keys = {tuple(x) for x in band_coords(nb, hw).tolist()}
    keys.update((i, j) for i in range(sb) for j in range(sb))
    return morton_sorted(np.array(sorted(keys), dtype=np.int64))


def build(cfg, mesh, seed, *, impl="fused"):
    return Growing(cfg, mesh, seed, impl=impl)
