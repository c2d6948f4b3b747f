"""Pallas TPU kernels: the fused KAN datapath (``kan_spline``) and flash
attention (``attention``)."""

import jax


def default_interpret() -> bool:
    """Run Pallas kernels in interpret mode off-TPU (CPU tests and CI).

    The one place the default is decided.  On a TPU every kernel compiles
    with Mosaic; interpret mode there only runs when a caller asks for it.
    """
    return jax.default_backend() != "tpu"
