"""A run with the timed path broken underneath reads ``correct`` false.

Each test plants one fault in the program, then drives the rest of a run
(set-up, window, check against the plain reference) at a small size on the
CPU, skipping only the look for a chip. The sound run reads true. No cell
runs on more than one chip, so the fault "the exchange between chips left
out" has no cell to break.
"""
from __future__ import annotations

import io
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from conftest import small_cell

CELLS = [w["name"] for w in harness.benchmark()["workloads"]]


def _run(name):
    from repro.core import runner

    runner._EXECUTOR_CACHE.clear()  # trace afresh, with any planted fault
    jax.clear_caches()
    cell = small_cell(name)
    return harness.run(name, 20261017, 1.0, False, t_start=time.perf_counter(),
                       cell=cell, log=io.StringIO())


def _frozen_fedavg(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.core.algorithms import fedavg

    monkeypatch.setattr(fedavg.FedAvg, "round",
                        lambda self, problem, state, key: state)


def _half_clients(monkeypatch):
    """Half of the round's clients left out, the mean over the rest."""
    import repro.comm
    from repro.comm import config

    def scale(mask, cids):
        m = mask[cids].astype(jnp.float32)
        m = m * (jnp.arange(m.shape[0]) < m.shape[0] // 2)
        return m * (jnp.float32(m.shape[0]) / jnp.maximum(jnp.sum(m), 1.0))

    monkeypatch.setattr(config, "participation_scale", scale)
    monkeypatch.setattr(repro.comm, "participation_scale", scale)


def _altered_answer(monkeypatch):
    """The answer altered where it is produced: SGD's output (the iterate
    each round reports and the grid returns) scaled by 1.01."""
    from repro.core.algorithms import sgd

    orig = sgd.SGD.output
    monkeypatch.setattr(sgd.SGD, "output", lambda self, state: jax.tree.map(
        lambda leaf: 1.01 * leaf, orig(self, state)))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_frozen_fedavg, _half_clients,
                                   _altered_answer])
@pytest.mark.parametrize("name", CELLS)
def test_fault_reads_incorrect(name, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]
