"""Operations and bytes that the served work needs, from the shapes alone.

These are the numerators of the roofline metrics.  They are worked out from
sizes and never read from the program, so a change to the program cannot
change what "the least time the chip could take" means.  A model's forward
FLOPs are counted by its reference module (``reference/<model>.py``,
``forward_flops``), beside the forward they count.
"""
from __future__ import annotations

PAGE_BYTES = 4096


def page_gather_bytes(n_pages: int, page_bytes: int = PAGE_BYTES) -> int:
    """HBM bytes one gather of ``n_pages`` pages must move: each page read
    once and written once.  It does no arithmetic."""
    return 2 * n_pages * page_bytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops_s"], nbytes / peak["hbm_bytes_s"])
