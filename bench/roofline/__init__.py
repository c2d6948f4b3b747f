"""Peaks by device kind, and the operations and bytes each kernel's work
requires, computed from the configuration and the call's shapes alone."""

from __future__ import annotations

import json
import pathlib

PEAKS = json.loads((pathlib.Path(__file__).with_name("peaks.json")
                    ).read_text())


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       "to roofline/peaks.json with their source")
    return PEAKS[device_kind]


def bound_s(ops: float, nbytes: float, flops_per_s: float,
            bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / flops_per_s, nbytes / bytes_per_s)
