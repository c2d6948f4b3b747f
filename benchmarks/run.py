"""Benchmark harness: one entry per paper table/figure + kernel microbench +
fused-pipeline/runtime-backend bench + roofline aggregation.
``python -m benchmarks.run [--fast]``.

Prints ``name,us_per_call,derived`` CSV blocks per benchmark.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def _bench_kernel(print_fn=print):
    """Microbenchmark the two Pallas kernels (interpret mode on CPU: the
    numbers validate plumbing, not TPU perf — TPU perf comes from §Roofline)."""
    from repro.core.asp_quant import ASPQuantSpec, build_lut
    from repro.kernels.kan_spline.ops import kan_spline
    from repro.kernels.kan_spline.ref import kan_spline_ref

    spec = ASPQuantSpec(grid_size=8, order=3, n_bits=8, lo=-1.0, hi=1.0)
    e = build_lut(spec)
    lut = jnp.asarray(e["lut_q"] * e["scale"], jnp.float32)
    key = jax.random.PRNGKey(0)
    codes = jax.random.randint(key, (256, 128), 0, spec.num_codes)
    wc = jax.random.normal(key, (128, spec.num_basis, 128)) * 0.3
    wb = jax.random.normal(key, (128, 128)) * 0.3

    ref = jax.jit(lambda c: kan_spline_ref(c, lut, wc, wb, spec))
    ref(codes).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        ref(codes).block_until_ready()
    t_ref = (time.perf_counter() - t0) / 10 * 1e6
    print_fn(f"kan_spline_ref_jit,{t_ref:.0f},us_per_call (B=256 F=128 O=128)")

    out = kan_spline(codes, lut, wc, wb, spec)
    err = float(jnp.abs(out - ref(codes)).max())
    print_fn(f"kan_spline_pallas,allclose_err,{err:.2e}")


def _bench_runtime(print_fn=print):
    """The REAL serving hot path: the fused multi-layer pipeline through the
    runtime's backend registry (ref / pallas / acim), not just the
    single-layer kernel.  Uses the FFN-width geometry (64, 128, 64) so the
    numbers line up with bench_kan_pipeline's deployment rows; off-TPU the
    Pallas path runs in interpret mode (plumbing validation, not TPU perf).
    """
    import time

    from repro import runtime
    from repro.core.kan_layer import KANSpec, init_kan_network
    from repro.core.kan_network_deploy import (
        default_interpret,
        deploy_kan_network,
        kan_network_deploy_apply,
        quantize_kan_network,
    )

    interpret = default_interpret()
    kspec = KANSpec(dims=(64, 128, 64), grid_size=8)
    key = jax.random.PRNGKey(0)
    qparams = quantize_kan_network(init_kan_network(key, kspec), kspec)
    dep = deploy_kan_network(qparams, kspec, batch=64)
    x = jax.random.uniform(key, (64, 64), minval=-1.0, maxval=1.0)
    runtime.reset_cache()
    for backend in ("ref", "pallas", "acim"):
        fn = lambda x, b=backend: kan_network_deploy_apply(
            dep, x, interpret=interpret, backend=b,
            key=jax.random.PRNGKey(0) if b == "acim" else None,
        )
        fn(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            fn(x).block_until_ready()
        us = (time.perf_counter() - t0) / 10 * 1e6
        print_fn(f"kan_pipeline_runtime_{backend},{us:.0f},"
                 "us_per_call (B=64 dims=64-128-64 G=8)")
    err = float(jnp.abs(
        kan_network_deploy_apply(dep, x, interpret=interpret, backend="pallas")
        - kan_network_deploy_apply(dep, x, interpret=interpret, backend="ref")
    ).max())
    print_fn(f"kan_pipeline_fused_vs_ref,allclose_err,{err:.2e}")
    print_fn(f"kan_pipeline_plan_cache,{runtime.cache_stats()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced training budgets (CI-speed)")
    ap.add_argument("--skip", default="",
                    help="comma-list: fig10,fig11,fig12,fig13,kernels,"
                         "runtime,roofline")
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()

    from benchmarks.fig10_asp_vs_pact import run as fig10
    from benchmarks.fig11_input_generators import run as fig11
    from benchmarks.fig12_kan_sam import run as fig12
    from benchmarks.fig13_knot_e2e import run as fig13
    from benchmarks.roofline import run as roofline

    t0 = time.time()
    if "fig10" not in skip:
        fig10()
        print()
    if "fig11" not in skip:
        fig11()
        print()
    if "kernels" not in skip:
        _bench_kernel()
        print()
    if "runtime" not in skip:
        _bench_runtime()
        print()
    if "fig12" not in skip:
        fig12(fast=args.fast)
        print()
    if "fig13" not in skip:
        fig13(fast=args.fast)
        print()
    if "roofline" not in skip:
        roofline()
    print(f"\ntotal_bench_time_s,{time.time()-t0:.0f}")


if __name__ == "__main__":
    main()
