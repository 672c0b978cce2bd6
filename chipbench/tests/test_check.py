"""The check's aggregation over sampled cells, on per-cell readings taken
on a TPU v5e at the logreg cell's own size.

One cell whose QSGD level flipped on a last-ulp difference moves the
worst-cell gaps and not the least-cell ones; the program at the TPU's
default one-pass bf16 precision parts every cell and fails."""
from __future__ import annotations

from chipbench import harness

# seed 534782116, the 4 cells its run sampled: the last one flipped
SOUND_WITH_A_FLIP = [
    (2.706908409227526e-07, 0.0),
    (2.7560800273770616e-07, 8.968978277313992e-08),
    (2.1810519584374376e-07, 1.1189701356941514e-07),
    (0.002796914222381797, 0.0015110074448030162),
]
# the program at default precision, seed 804
DEFAULT_PRECISION = [
    (0.0017721820632253637, 0.0005618743470540667),
    (0.0033830984571220904, 0.002274364254365266),
    (0.004793557914920968, 0.002464642376107996),
    (0.005689876307036804, 0.00025974062474529494),
]


def _numbers(cells):
    return harness.Cell.worst(
        {"loss_gap": lg, "change_gap": cg, "bits_gap": 0.0,
         "selection_mismatch": 0} for lg, cg in cells)


def _passes(numbers, limits):
    return all(numbers[k] <= v for k, v in limits.items())


def test_one_flipped_cell_moves_only_the_worst():
    out = _numbers(SOUND_WITH_A_FLIP)
    assert out["loss_gap"] == SOUND_WITH_A_FLIP[3][0]
    assert out["least_cell_loss_gap"] == SOUND_WITH_A_FLIP[2][0]
    assert out["least_cell_change_gap"] == 0.0


def test_limits_pass_the_flip_and_fail_the_control():
    limits = harness.load_json("limits", "logreg.c01-qsgd4ef")
    assert _passes(_numbers(SOUND_WITH_A_FLIP), limits)
    assert not _passes(_numbers(DEFAULT_PRECISION), limits)
