"""The arithmetic of a roofline, whatever the model: a family
(``perfbench/families/<builder>.py``) counts the operations and bytes
its algorithm needs, this sets them against a chip's peaks."""
from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) on a chip with these peaks."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
