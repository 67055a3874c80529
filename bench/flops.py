"""The roofline: the least time the chip could take for a count of
operations and bytes.

The counts themselves depend on the model, and come from its family's
``request_flops`` and ``kernel_calls`` (``bench/families/<family>.py``).
"""

from __future__ import annotations

from typing import Any, Dict


def roofline_s(flops: float, nbytes: float, peak: Dict[str, Any]) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
