"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
interconnect. JAX reports the chip as "TPU v5 lite".

The FLOP peak is the matrix unit's bf16 rate. The configurations state
float32, and a float32 matrix product at the TPU's default precision runs on
the matrix unit in bf16 passes with float32 accumulation, so this is the
peak those products can reach. The elementwise work of the Pallas kernels
runs on the vector unit, whose rate is far lower; against this peak their
compute bound is loose and their roofline is set by the bytes they move.

A kind outside the table is an error, never a default.
"""
from __future__ import annotations

_V5E = {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no published peaks for device kind "
                          f"{device_kind!r}; known: {sorted(PEAKS)}") from None
