"""Operations and bytes of the benchmark's programs, from shapes alone.

These count what the model's mathematics needs, whatever implements it:
a change of kernel, fusion or recomputation leaves them unchanged.  One
multiply-add is 2 operations.  Element-wise work (norms, rotary, softmax,
biases) is left out: it is under 1% of the matmul work at these widths.

Each architecture's counts live with its reference
(``bench/references/<reference>.py``: ``decode_token_flops`` and
``decode_step_bytes``, on the configuration's ``model`` dict); this
module keeps what holds for any of them.
"""

from __future__ import annotations


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The larger of operations over peak and bytes over bandwidth, and
    which of the two bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
