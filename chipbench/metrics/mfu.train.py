"""mfu.train: the whole train step's share of the chip's bf16 peak, in %.

Model FLOPs per token (``flops.py``: 6 N plus causal attention or the SSD
mixer, recomputation not counted) times the window's tokens per second,
over the peak for the run's ``device_kind`` (``peaks.py``).
"""

from chipbench import peaks


def read(out):
    if "flops_per_token" not in out:
        return None
    peak = peaks.peak(out["device_kind"])["bf16_flops"]
    return 100.0 * out["flops_per_token"] * out["tokens_per_s"] / peak
