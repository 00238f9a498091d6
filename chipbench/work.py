"""Operations and bytes that each kernel call requires, computed from
shapes alone, and the chip's peaks.  A model step's counts belong to its
block and live in its reference module (``references/``).

The counts are what the work needs, whatever implements it: no padding, no
whole-pool copies, no recomputation.  A roofline share is the least time
the chip could take, ``max(flops / peak FLOP/s, bytes / peak bytes/s)``,
over the measured time; with required work in the numerator it cannot pass
100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import json
from pathlib import Path


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def least_seconds(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# -- page kernels -------------------------------------------------------------


def page_move_bytes(pages: int, page_bytes: int) -> int:
    """page_gather or cow_scatter over ``pages`` pages: each page read once
    and written once.  gather_assemble needs no more: its pages are read
    once and land once in the tensor's layout; the trim and reshape after
    the gather are copies the work does not require."""
    return 2 * pages * page_bytes
