"""The least time a piece of work needs on the chips, from its operation
and byte counts (which each configuration's reference module gives, in
``chipbench/references/``) and the device's peaks."""

from __future__ import annotations


def least_time(flops: float, nbytes: float, peaks: dict,
               chips: int = 1) -> float:
    """Seconds the chips need at best, split evenly: the larger of the
    compute bound and the memory bound."""
    return max(flops / (chips * peaks["bf16_flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))
