"""The check against the control and against planted faults: each must come
out as not correct, while the same toy run of the sound program is correct.
The harness's look for a chip is skipped; the rest of a run is driven as
the command drives it."""

import dataclasses

import jax
import numpy as np
import pytest

import repro.dist as rd
from bench import calibrate
from bench_toy import one_chip_cells, run_toy


def _alter(x, fn):
    return dataclasses.replace(x, store=fn(x.store))


def _bump(store):  # one entry of every block altered where it is produced
    return store.at[:, :, 0, 0].add(1.0)


def _half(store):  # every other block left out (zero)
    keep = (np.arange(store.shape[1]) % 2 == 0)[None, :, None, None]
    return store * keep


@pytest.fixture
def planted(monkeypatch):
    def plant(name, wrap, module=rd):
        monkeypatch.setattr(module, name, wrap(getattr(module, name)))
    return plant


@pytest.mark.parametrize("cell", one_chip_cells())
def test_sound_runs_are_correct(cell):
    r = run_toy(cell)
    assert r["correct"], (cell, r["checks"])


@pytest.mark.parametrize("cell", one_chip_cells())
@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out", "state_unchanged"])
def test_product_faults_fail(planted, fault, cell):
    if fault == "answer_altered":
        planted("dist_multiply", lambda f: lambda *a, **k: _alter(f(*a, **k), _bump))
    elif fault == "half_left_out":
        planted("dist_multiply", lambda f: lambda *a, **k: _alter(f(*a, **k), _half))
    else:  # the step returns its operand unchanged
        planted("dist_multiply", lambda f: lambda a, b, *_, **__: a)
    r = run_toy(cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", one_chip_cells())
def test_control_fails(cell):
    """Leaf products in three bf16 passes, a step below HIGHEST."""
    with calibrate.control_precision():
        r = run_toy(cell, impl="fused-interpret")
    assert not r["correct"], r["checks"]
    assert r["checks"]["c_err_u"]["value"] > r["checks"]["c_err_u"]["limit"]
    jax.clear_caches()
