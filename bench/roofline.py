"""A roofline share: the least time some work could take on this chip,
the larger of its operations over the bf16 peak and its bytes over the
HBM bandwidth, over the device time it took. Operations and bytes come
from shapes (``bench/flops/<kind>.py``), so the share outlives a change
of the code that does the work."""
from __future__ import annotations

from typing import Tuple


def bounds(ops: float, moved: float, peaks: dict) -> Tuple[float, float]:
    """(seconds of operations, seconds of bytes) at the chip's peaks."""
    return ops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]


def share(ops: float, moved: float, peaks: dict, seconds: float) -> float:
    """The least time over ``seconds``, in per cent."""
    return 100.0 * max(bounds(ops, moved, peaks)) / seconds


def note(ops: float, moved: float, peaks: dict) -> str:
    """Which of the two bounds applies, with both in milliseconds."""
    compute, memory = bounds(ops, moved, peaks)
    return (f"compute-bound ({compute * 1e3:.1f} ms of operations, "
            f"{memory * 1e3:.1f} ms of bytes)" if compute >= memory else
            f"memory-bound ({memory * 1e3:.1f} ms of bytes, "
            f"{compute * 1e3:.1f} ms of operations)")
