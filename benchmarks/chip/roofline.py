"""A kernel's share of its roofline: the least time the chip could take
for the kernel's operations and bytes, the larger of FLOPs over the peak
FLOP/s and bytes over the peak bandwidth, over the time it took.

A `<kernel>_roofline` reader takes the kernel's device seconds from
`run["device"]["ops"]`, its FLOPs and bytes from functions of the model
module (computed from `run["cfg"]` and `run["seqs"]`), and the peaks
from `run["peak"]` (a row of `peaks.json`), and reports 100 x `share`.
"""
from __future__ import annotations

from typing import Dict


def share(flops: float, nbytes: float, seconds: float, peak: Dict) -> float:
    """Share (0-1 when the counts are right) of the roofline reached by
    `flops` operations and `nbytes` bytes of HBM traffic done in
    `seconds` of device time."""
    if seconds <= 0:
        raise ValueError(f"a kernel time of {seconds} s has no roofline")
    return max(flops / peak["bf16_flops"],
               nbytes / peak["hbm_bytes_per_s"]) / seconds
