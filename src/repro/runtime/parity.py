"""Tolerance checks for comparing two executions of the same computation.

Each comparison is a scaled max error — ``max|got - want| / max|want|`` —
so one tolerance reads the same at any output scale.  Callers state the
tolerance and the reason for it where they call :func:`assert_close`;
``chip_smoke.py`` and the serving tests share these helpers.
"""

from __future__ import annotations

import numpy as np

__all__ = ["scaled_max_error", "assert_close", "code_mismatch"]


def scaled_max_error(got, want) -> float:
    """``max|got - want| / max|want|`` in float64 (0 when both are 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    err = float(np.max(np.abs(got - want))) if want.size else 0.0
    if not np.isfinite(err):
        return float("inf")
    return err / scale if scale > 0.0 else err


def assert_close(name: str, got, want, tol: float, why: str) -> float:
    """Raise AssertionError unless ``scaled_max_error(got, want) <= tol``;
    returns the error.  ``why`` is the tolerance's reason, quoted in the
    failure message."""
    err = scaled_max_error(got, want)
    if not err <= tol:
        raise AssertionError(
            f"{name}: scaled max error {err:.3e} > {tol:.1e} ({why})")
    return err


def code_mismatch(got, want) -> tuple:
    """(largest |code difference|, fraction of codes that differ) between
    two integer code arrays of the same shape."""
    got = np.asarray(got, np.int64)
    want = np.asarray(want, np.int64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    diff = np.abs(got - want)
    if not diff.size:
        return 0, 0.0
    return int(diff.max()), float(np.mean(diff != 0))
