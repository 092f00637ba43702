"""Work counts and block structure of the benchmark against the library's own
planner and the paper's Table 1."""

import numpy as np
import pytest

from bench.structure import (band_coords, morton_codes, product_coords, product_tasks,
                             random_coords)
from bench.work import banded_element_flops, block_product_work

from repro.core.families import (BANDW, RANDOM_BLOCK_NUM, RANDOM_BLOCK_SIZE, band_block_coords,
                                 structure_coords)
from repro.core.quadtree import morton_encode
from repro.core.schedule import make_spgemm_plan
from repro.core.spgemm import spgemm_symbolic


@pytest.mark.parametrize("family,n,bs", [("banded", 20_000, 512),
                                         ("banded", 6_000, 256),
                                         ("random", 20_000, 1024)])
def test_block_tasks_match_the_plan(family, n, bs):
    coords = structure_coords(family, n, 0, bs)
    plan = make_spgemm_plan(coords, coords, 2, bs)
    assert product_tasks(coords, coords) == plan.tasks.num_tasks
    # the symbolic product's blocks, in the library's (Morton) order
    np.testing.assert_array_equal(product_coords(coords, coords),
                                  spgemm_symbolic(coords, coords).c_coords)


def test_band_coords_match_the_library():
    for nb, hw in [(1, 0), (7, 2), (196, 6), (33, 1)]:
        np.testing.assert_array_equal(band_coords(nb, hw), band_block_coords(nb, hw))


@pytest.mark.parametrize("n,bs", [(100_000, 512), (40_000, 1024), (20_000, 256)])
def test_random_coords_match_the_library(n, bs):
    size = RANDOM_BLOCK_SIZE[0]
    got = random_coords(-(-n // bs), -(-BANDW // bs), n, bs, size, RANDOM_BLOCK_NUM[0])
    np.testing.assert_array_equal(got, structure_coords("random", n, 0, bs))


def test_morton_codes_match_the_library():
    rng = np.random.default_rng(0)
    c = rng.integers(0, 2**20, size=(500, 2))
    np.testing.assert_array_equal(morton_codes(c), morton_encode(c[:, 0], c[:, 1]))


def test_table1_banded_count():
    # the paper's Table 1, banded family, n = 1e5: 7.022 TFLOP
    assert round(banded_element_flops(100_000) / 1e12, 3) == 7.022
    assert round(banded_element_flops(200_000) / 1e12, 2) == 14.22


def test_block_work_of_the_100k_cell():
    coords = band_coords(196, 6)
    w = block_product_work(coords, coords, len(product_coords(coords, coords)), 512, 4)
    assert w["tasks"] == 32_214
    assert w["flops"] == 2.0 * 32_214 * 512**3
    assert w["bytes"] == (2 * len(coords) + 4_744) * 512 * 512 * 4


def test_block_work_of_the_random_100k_cell():
    coords = random_coords(196, 6, 100_000, 512, 15_716, 1)
    w = block_product_work(coords, coords, len(product_coords(coords, coords)), 512, 4)
    assert len(coords) == 3_156
    assert w["tasks"] == 62_444
    assert w["bytes"] == (2 * 3_156 + 5_664) * 512 * 512 * 4
