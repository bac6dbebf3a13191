"""The chip's side of a roofline: ``peaks.json`` holds the published peaks
(a ``device_kind`` that is not in it is an error, never a default), and
``roofline_seconds`` is the least time the chip could take for a count of
operations and bytes.  The counts are the model's and come from its family
file (``families/<name>.py``: ``train_flops_per_token``, ``flash_calls``).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in peaks.json "
            f"({sorted(table)}): add it with its source, do not guess"
        )
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
