"""Shared fixtures: small copies of the benchmark's cells for CPU tests.

Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q`` from
the repository root (the tier-1 run collects ``tests/`` only).
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_cell(name: str, rounds: int = 6):
    """Cell ``name`` at a size a CPU test holds: 20 clients x 60 samples of
    8 x 8 images, 2 seeds x 2 multipliers, ``rounds`` rounds."""
    from chipbench import harness

    bench = harness.benchmark()
    cell = harness.Cell(name, bench)
    c = dict(cell.config, num_clients=20, per_client=60, side=8)
    if "dim" in c:
        c["dim"] = 64
    if "arch" in c:
        c["arch"] = [64, 32, 32, 10]
        c["params"] = 64 * 32 + 32 + 32 * 32 + 32 + 32 * 10 + 10
    t = dict(cell.traffic, rounds=rounds, seeds_per_call=2,
             multipliers=[0.5, 1.0])
    return harness.Cell(name, bench, config=c, traffic=t)


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def lookup_with_test_data(found):
    """``harness._path`` that finds the test-only files under
    ``data/<kind>/`` (populations, references, families, kernels, metrics)
    as if they lay in ``chipbench/<kind>/``, and the rest with ``found``."""
    def path(kind, name, ext):
        extra = os.path.join(DATA, kind, name + ext)
        return extra if os.path.isfile(extra) else found(kind, name, ext)

    return path


@pytest.fixture
def test_files(monkeypatch):
    """Let lookups by name find the test-only files: what a later
    configuration brings as new files."""
    from chipbench import harness

    monkeypatch.setattr(harness, "_path", lookup_with_test_data(harness._path))


@pytest.fixture(scope="session")
def bench():
    from chipbench import harness

    return harness.benchmark()
