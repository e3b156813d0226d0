"""Operations and bytes the particle kernels' work requires, and the peaks
of the chip they run on.

Required work is counted from alive particles and the grid, never from
lanes, blocks or tiles, so it reads the same whatever implements it.  Per
alive particle and step, the particle phase (gather + push + move +
deposit) has to:

- read its position, momentum and weight (``z, x, ux, uy, uz, w``: 6
  float32) and write its new position and momentum (5 float32): 44 bytes;
- evaluate the order-3 B-spline weights: 4 weight sets (``z`` and ``x`` at
  the two staggerings) of 4 points at 8 operations each, before the push
  for the gather and again after the move for the deposit: 256 operations;
- gather 6 field components over the 4x4 stencil: 16 weight products plus
  a multiply-add per point and component: 208 operations;
- the relativistic Boris push: 60 operations; the move with its Lorentz
  factor: 15 operations;
- deposit 3 current components over the 4x4 stencil: 16 weight products,
  the coefficient (5) and a multiply-add per point and component: 117
  operations.

So 656 operations and 44 bytes per alive particle and step.  Per box that
holds alive particles of a species, the kernels also have to read the 6
field components over the gather's stencil reach (``box + 4`` cells a
side) and write the 3 current components over the deposit's reach after
the move (``box + 6`` a side), in float32.

The roofline is the larger of operations over the peak rate and bytes
over the peak bandwidth.  At 15 operations a byte, far under the chip's
ridge point (about 240 on TPU v5e), the bound is memory bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

import numpy as np

BYTES_PER_PARTICLE = 4 * (6 + 5)
OPS_PER_PARTICLE = 256 + 208 + 60 + 15 + 117
FIELD_COMPONENTS, CURRENT_COMPONENTS = 6, 3
GATHER_REACH, DEPOSIT_REACH = 4, 6

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def particle_phase_work(alive_per_box: Sequence[np.ndarray], box: int) -> Tuple[float, float]:
    """``(operations, bytes)`` one step of the particle kernels requires,
    from per-species alive counts per box."""
    ops = bytes_ = 0.0
    for counts in alive_per_box:
        counts = np.asarray(counts, np.float64)
        live_boxes = int((counts > 0).sum())
        ops += OPS_PER_PARTICLE * counts.sum()
        bytes_ += BYTES_PER_PARTICLE * counts.sum()
        bytes_ += 4 * live_boxes * (
            FIELD_COMPONENTS * (box + GATHER_REACH) ** 2
            + CURRENT_COMPONENTS * (box + DEPOSIT_REACH) ** 2
        )
    return ops, bytes_


def peaks(device_kind: str) -> Dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(ops: float, bytes_: float, device_kind: str) -> Tuple[float, str]:
    """Least time for ``ops`` and ``bytes_`` on one chip, and which bound
    sets it."""
    p = peaks(device_kind)
    t_ops, t_mem = ops / p["peak_flops_per_s"], bytes_ / p["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
