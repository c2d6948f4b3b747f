"""Quickstart: build a KAN, quantize it with ASP-KAN-HAQ, run all three
execution paths (float / quantized-LUT / Pallas kernel) and compare.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core.asp_quant import ASPQuantSpec, quantize_input
from repro.core.kan_layer import (
    KANSpec, init_kan_network, kan_network_apply, quantize_kan_layer,
)
from repro.kernels.kan_spline.ops import kan_spline_from_qparams


def main():
    # the paper's edge KAN: 17 -> 1 -> 14, G=5 (KAN1 design point)
    kspec = KANSpec(dims=(17, 1, 14), grid_size=5, n_bits=8)
    spec = kspec.layer_spec()
    print(f"KAN {kspec.dims}, G={kspec.grid_size}, K={kspec.order}")
    print(f"ASP bit split: LD={spec.ld} -> global={spec.global_bits} bits "
          f"(knot interval), local={spec.ld} bits (intra-interval)")
    print(f"code range [0, {spec.num_codes - 1}] (eq. (6): G*2^LD <= 2^n)")

    key = jax.random.PRNGKey(0)
    params = init_kan_network(key, kspec)
    x = jax.random.uniform(key, (8, 17), minval=-1.0, maxval=1.0)

    # 1) float path (training path)
    y_float = kan_network_apply(params, x, kspec)

    # 2) ASP-quantized path (shared SH-LUT + banded matmul)
    qparams = [quantize_kan_layer(p, spec) for p in params]
    y_quant = kan_network_apply(None, x, kspec, quantized=True,
                                qparams_list=qparams)

    # 3) the Pallas TPU kernel (interpret mode off-TPU), layer by layer
    h = x
    for qp in qparams:
        codes = quantize_input(h, spec)
        h = kan_spline_from_qparams(codes, qp, spec)
        if qp is not qparams[-1]:
            h = jnp.tanh(h)
    y_kernel = h

    # 4) the fused multi-layer pipeline: every layer in the Pallas kernel,
    #    inter-layer requantization fused, activations stay int codes
    y_fused = kan_network_apply(None, x, kspec, quantized=True,
                                qparams_list=qparams, backend="pallas")

    print("\nfloat    ", y_float[0, :5])
    print("quantized", y_quant[0, :5])
    print("kernel   ", y_kernel[0, :5])
    print("fused    ", y_fused[0, :5])
    print("\nmax |float - quantized| =", float(jnp.abs(y_float - y_quant).max()))
    print("max |quantized - kernel| =", float(jnp.abs(y_quant - y_kernel).max()))
    print("max |quantized - fused|  =", float(jnp.abs(y_quant - y_fused).max()))
    e = quantize_kan_layer(params[0], spec)
    print(f"\nSH-LUT: {len(e['hemi'])} stored entries "
          f"(vs {(spec.order + 1) * spec.codes_per_interval} unfolded, "
          f"vs {(spec.num_basis) * 2**spec.n_bits} for per-B_i tables)")


if __name__ == "__main__":
    main()
