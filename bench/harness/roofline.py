"""Operations and bytes of the kernels, and the chip's peaks.

The ``synray`` kernel (``repro.kernels.synray``) computes, per emulated
chip and launch, i[b, c] = sum_r ev[b, r] * w[r, c] * (addr[r, c] ==
ev_addr[b, r]) for B time steps, R rows (one Dale half) and C columns.
Its useful work is 2 * B * R * C operations (a multiply and an add per
event and synapse). The least bytes it has to move are its operands and
result, once each: events f32 [B, R], event addresses as int32 [B, R]
(the wrapper widens them), weights and stored addresses int8 [R, C], and
currents f32 [B, C]. Events of 0 or 1 times 6-bit weights are exact in
bfloat16, so the bf16 peak is the compute bound.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def synray_cost(instances: int, steps: int, rows: int, cols: int):
    """(operations, bytes) of one ``synray`` launch over ``instances``
    chips, ``steps`` time steps, ``rows`` rows and ``cols`` columns."""
    ops = 2 * steps * rows * cols
    nbytes = (steps * rows * 4 + steps * rows * 4 + 2 * rows * cols
              + steps * cols * 4)
    return instances * ops, instances * nbytes


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peaks of a device kind; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple:
    """(share of the roofline in %, the bound: "compute" or "memory"):
    the least time the chip could take over the time measured."""
    p = peaks(device_kind)
    t_ops = ops / p["bf16_flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    return 100.0 * max(t_ops, t_mem) / seconds, \
        "compute" if t_ops >= t_mem else "memory"
