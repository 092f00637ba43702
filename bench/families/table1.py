"""The paper's Table-1 weak-scaling families: C = A·A on a resident A.

The configuration's ``structure`` names the family: ``banded`` has every
block within ``ceil(half_width / leaf_size)`` blocks of the diagonal (the
element band of half-width 3000 at block granularity); ``random`` adds
``random_blocks`` dense diagonal blocks of ``random_block_size`` elements at
the paper's fixed random positions.  Values are N(0, 1), keyed by block
position.  Each timed call is one
``repro.dist.dist_multiply(a, a, cache, impl="fused", exchange="p2p")``
ending in ``block_until_ready``; the previous C is dropped first, so the
footprint is A + C.

The check compares C's whole block structure with the symbolic product of
A's coordinates, and sampled blocks of the window's last C with float64
sums of A-block products.  Half of the sample is drawn where faults hide
best: on several chips, blocks whose products read an A block that lives on
another chip, so they were built from operands that came through the
exchange; on one chip, blocks with the longest runs of products.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from ..blocks import host_blocks, normal_block
from ..resident import fetch_blocks, resident_operand
from ..structure import band_coords, product_terms, random_coords
from ..work import block_product_work

U32 = 2.0**-24  # float32 unit roundoff
LAMBDA = 8.0  # probabilistic rounding-bound multiplier (Higham & Mary 2019)


class Table1:
    def __init__(self, cfg: dict, mesh, seed: int, *, impl: str = "fused"):
        import repro.dist as rd

        self.cfg, self.seed, self.impl = cfg, seed, impl
        self.bs, n = cfg["leaf_size"], cfg["n"]
        self.nb = -(-n // self.bs)
        self.coords = coords_of(cfg)
        self.fn = functools.partial(normal_block, bs=self.bs)
        self.a = resident_operand(self.fn, seed, self.coords, (n, n), self.bs, mesh)
        self.cache = rd.PlanCache()
        self.c = None
        self.terms = product_terms(self.coords, self.coords)

    # -- the timed path ------------------------------------------------------
    def call(self) -> None:
        import repro.dist as rd

        self.c = None  # drop the previous product before making the next
        self.c = rd.dist_multiply(self.a, self.a, self.cache, impl=self.impl,
                                  exchange="p2p")
        self.c.store.block_until_ready()

    def work(self) -> dict:
        return block_product_work(self.coords, self.coords, len(self.terms),
                                  self.bs, 4)

    def start_window(self) -> None:
        pass

    def counters(self) -> dict:
        return {}

    def failed(self) -> int:
        return 0

    # -- after the window ----------------------------------------------------
    def collect(self) -> dict:
        """What the check needs from the last product, fetched to the host."""
        rng = np.random.default_rng(self.seed)
        expect = sorted(self.terms)
        n_check = min(self.cfg["check_blocks"], len(expect))
        owner_a = self.a.owner
        if np.unique(owner_a).size > 1:  # blocks built from exchanged operands
            c_owner = {tuple(ij): int(o) for ij, o in zip(self.c.coords.tolist(), self.c.owner)}
            hard = [ij for ij in expect if ij in c_owner and any(
                owner_a[ga] != c_owner[ij] or owner_a[gb] != c_owner[ij]
                for ga, gb in self.terms[ij])]
        else:  # the tenth of the blocks with the longest runs of products
            cut = np.percentile([len(self.terms[ij]) for ij in expect], 90)
            hard = [ij for ij in expect if len(self.terms[ij]) >= cut]
        pick = []
        if hard:
            half = min(n_check // 2, len(hard))
            pick += [hard[k] for k in rng.choice(len(hard), half, replace=False)]
        rest = sorted(set(expect) - set(pick))
        pick += [rest[k] for k in rng.choice(len(rest), n_check - len(pick), replace=False)]
        return dict(c_coords=np.asarray(self.c.coords), pick=pick,
                    blocks=fetch_blocks(self.c, pick))

    def release(self) -> None:
        self.a = self.c = self.cache = None

    def check(self, got: dict) -> list:
        return reference(self.cfg, self.seed, got, self.terms, self.coords, self.fn)


def reference(cfg: dict, seed: int, got: dict, terms: dict, coords: np.ndarray,
              fn) -> list:
    """(name, value, limit) of every number compared, from float64 sums of
    A-block products regenerated from the seed."""
    bs = cfg["leaf_size"]
    limits = cfg["limits"]
    expect = set(terms)
    have = set(map(tuple, got["c_coords"].tolist()))
    wrong = len(expect ^ have) + (len(got["c_coords"]) - len(have))
    err_u, ratio = 0.0, 0.0
    for ij in got["pick"]:
        c = got["blocks"].get(ij)
        pairs = terms[ij]
        need = sorted({g for pair in pairs for g in pair})
        a = dict(zip(need, host_blocks(fn, seed, coords[need])))
        ref = np.zeros((bs, bs))
        absprod = np.zeros((bs, bs))
        for ga, gb in pairs:
            ref += a[ga] @ a[gb]
            absprod += np.abs(a[ga]) @ np.abs(a[gb])
        if c is None or not np.isfinite(c).all():
            err_u = ratio = math.inf
            continue
        diff = c - ref
        err_u = max(err_u, float(np.linalg.norm(diff) / np.linalg.norm(ref) / U32))
        bound = LAMBDA * math.sqrt(bs * len(pairs)) * U32 * absprod
        ratio = max(ratio, float((np.abs(diff) / bound).max()))
    return [("c_blocks_wrong", float(wrong), limits["c_blocks_wrong"]),
            ("c_err_u", err_u, limits["c_err_u"]),
            ("c_bound_ratio", ratio, limits["c_bound_ratio"])]


def coords_of(cfg: dict) -> np.ndarray:
    """A's block coordinates for the configuration's ``structure``."""
    bs, n = cfg["leaf_size"], cfg["n"]
    nb, hw = -(-n // bs), -(-cfg["half_width"] // bs)
    if cfg["structure"] == "banded":
        return band_coords(nb, hw)
    if cfg["structure"] == "random":
        return random_coords(nb, hw, n, bs, cfg["random_block_size"], cfg["random_blocks"])
    raise ValueError(f"unknown structure {cfg['structure']!r}")


def build(cfg, mesh, seed, *, impl="fused"):
    return Table1(cfg, mesh, seed, impl=impl)
