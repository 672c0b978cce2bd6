"""The control, kept at a test size: the reference in a lower precision,
put in the program's place, fails the cell's limits, while the program
passes them; so does the planted half-clients fault.

On the CPU a matrix product's precision setting changes nothing, so the
precision controls themselves (``high``, and the program at the TPU's
default precision) are read on the chip by ``chipbench/calibrate.py``; here
the reference in bfloat16 stands in for them."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from chipbench import harness
from conftest import small_cell


@pytest.mark.parametrize(
    "name", [w["name"] for w in harness.benchmark()["workloads"]])
def test_control_fails_program_passes(name):
    cell = small_cell(name, rounds=8)
    cell.setup(77)
    call = cell.grid_call(1)

    def passes(numbers):
        return all(v <= cell.limits[k] for k, v in numbers.items()
                   if k in cell.limits)

    refs = {"program": cell.reference(),
            "control": cell.reference(dtype=jnp.bfloat16),
            "half_clients": cell.reference(fault="half_clients")}
    got = {k: [] for k in refs}
    for _, c in cell.sample([call], 77):
        want = cell.replayed(cell.replay(refs["program"], call, c))
        got["program"].append(cell.compare(cell.outcome(call, c), want))
        for k in ("control", "half_clients"):
            got[k].append(cell.compare(
                cell.replayed(cell.replay(refs[k], call, c)), want))
    got = {k: cell.worst(v) for k, v in got.items()}
    assert passes(got["program"]), got["program"]
    assert not passes(got["control"]), got["control"]
    assert not passes(got["half_clients"]), got["half_clients"]
