"""The roofline arithmetic every architecture shares: the least time of a
piece of work from its operations and bytes, against a device's peaks
(``bench/peaks.json``). Each architecture counts its own operations and
bytes (``bench/arch/<family>.py``)."""
from __future__ import annotations

F32 = 4


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, which bound binds): the larger of operations over peak
    and bytes over HBM bandwidth."""
    tf = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tb, "bytes") if tb >= tf else (tf, "flops")
