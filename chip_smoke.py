#!/usr/bin/env python3
"""Bring-up check: the KAN serving path on a TPU, at published widths.

    python chip_smoke.py                 # one chip: phases A and B
    python chip_smoke.py --four-chips    # phase B on a data=2,model=2 mesh,
                                         # compared with one chip

Phase A — the paper's edge nets, KAN1 (17, 1, 14) at G=5 and KAN2 at G=68:
deployed with ``deploy_kan_network``, run through ``runtime.execute`` on the
compiled "pallas" backend at a batch of several thousand rows, and compared
with ``backend="ref"`` under "highest" matmul precision (boundary codes and
outputs).

Phase B — qwen2.5-14b-kanffn at its published widths (d_model 5120, 40 q
heads padded to 48, 8 KV heads x 128, vocab 152064, KAN hidden 1280, G=8,
K=3, 8-bit, bf16), depth cut to 4 layers:

  B1. one KAN-FFN block through the fused pipeline vs the "ref" backend, at
      a decode batch and a prefill batch;
  B2. ``ServeEngine(kan_deploy=True, attn_backend="flash")`` on the paged KV
      pool serves seeded requests through ``Scheduler.run_until_idle``, once
      to warm up and once more with no compiles allowed;
  B3. the logits of the longest request (prefill, then each decode step
      through the paged cache) vs the same engine on the "ref" KAN and
      attention backends under "highest".

Weights and prompts come from ``--seed``; nothing is downloaded.  The run
refuses to start unless JAX's first device is a TPU, raises on the first
failed check, and prints the JSON result as its last line only when every
check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# --- tolerances, each with its reason --------------------------------------

# Boundary codes of the fused kernel vs the reference.  Both compute the
# pre-activation in f32; their summation orders differ by ~1e-6 relative,
# which moves a code only where the value sits that close to a rounding
# boundary (one code step is 2**-7 of the domain): the codes may differ by
# at most one step, in at most 0.1% of places.  A single bf16 pass (2**-9
# relative error on the LUT values and weights) flips several percent.
MAX_CODE_STEP = 1
MAX_CODE_FLIPS = 1e-3
# Rows whose boundary codes all agree: the outputs then differ only by f32
# reassociation (~1e-7 of the output scale for the edge nets' <= 71-term
# bands, up to ~1e-5 over the FFN's 5120 x 11 contraction).  A single bf16
# pass misses by ~1e-3.
EDGE_OUT_TOL = 1e-5
FFN_OUT_TOL = 1e-4
# Whole-model logits.  Activations are bf16 between layers (half-ulp 2**-9);
# the reference attention rounds its probabilities to bf16 before the PV
# product where the flash kernel keeps f32, and a hidden KAN code may flip
# as above.  Each moves the logits by well under 1% of their scale, while
# a missing or scrambled FFN moves them by tens of percent.
LOGITS_TOL = 2e-2
MESH_LOGITS_TOL = 2e-2  # same budget: a different partitioning reorders sums

EDGE_ROWS = 4096          # phase A batch: an offline edge-net batch
FFN_ROWS = (8, 256)       # B1: a decode batch and a prefill batch
LAYERS = 4                # phase-B depth cut (embedding + head ~3.1 GB)
REQUESTS = 12             # requests per serving pass, over 8 slots


def device_report() -> list:
    """Print the versions and devices; refuse to run anywhere but a TPU."""
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}", flush=True)
    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX's first device is {devices[0].platform!r}, "
                 "not a TPU; refusing to run")
    return devices


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


# --- comparisons -------------------------------------------------------------


def check_codes(name: str, got, want) -> np.ndarray:
    """Bound the boundary-code differences; returns the rows that agree."""
    from repro.runtime.parity import code_mismatch

    agree = None
    for li, (c, cr) in enumerate(zip(got, want)):
        worst, frac = code_mismatch(c, cr)
        print(f"  {name} boundary {li}: max code step {worst}, "
              f"{frac:.2e} of codes differ", flush=True)
        if worst > MAX_CODE_STEP or frac > MAX_CODE_FLIPS:
            raise AssertionError(
                f"{name} boundary {li}: codes differ by up to {worst} steps "
                f"in {frac:.2e} of places (allowed {MAX_CODE_STEP} step, "
                f"{MAX_CODE_FLIPS:.0e})")
        rows = np.all(np.asarray(c) == np.asarray(cr), axis=1)
        agree = rows if agree is None else agree & rows
    if agree is None:
        raise AssertionError(f"{name}: no boundary codes to compare")
    if agree.mean() < 0.5:
        raise AssertionError(
            f"{name}: only {agree.mean():.1%} of rows keep every code")
    return agree


def check_stack(name, dep, x, tol: float, why: str) -> None:
    """The compiled fused pipeline vs the reference composition."""
    from repro import runtime
    from repro.runtime.parity import assert_close

    y, codes = runtime.execute(dep, x, backend="pallas",
                               return_intermediates=True)
    keys = [k for k in runtime.PLAN_CACHE.keys()
            if k.backend == "pallas" and k.dims == tuple(dep.dims)]
    if not keys or any(k.interpret for k in keys):
        raise AssertionError(f"{name}: the pallas plan is not compiled: {keys}")
    with jax.default_matmul_precision("highest"):
        y_ref, codes_ref = runtime.execute(dep, x, backend="ref",
                                           return_intermediates=True)
    agree = check_codes(name, codes, codes_ref)
    y, y_ref = np.asarray(y), np.asarray(y_ref)
    if not np.all(np.isfinite(y)):
        raise AssertionError(f"{name}: non-finite outputs")
    err = assert_close(f"{name} outputs", y[agree], y_ref[agree], tol, why)
    print(f"  {name}: {x.shape[0]} rows, outputs {y.shape}, scaled max error "
          f"{err:.3e} on {agree.mean():.1%} of rows (tol {tol:.0e})",
          flush=True)


# --- phase A -----------------------------------------------------------------


def phase_a(seed: int) -> None:
    from repro.configs.registry import KAN_KNOT
    from repro.core.kan_layer import KANSpec, init_kan_network
    from repro.core.kan_network_deploy import (
        deploy_kan_network,
        quantize_kan_network,
    )

    print("phase A: KAN1/KAN2 edge nets", flush=True)
    for name, grid in (("KAN1", KAN_KNOT["g_kan1"]),
                       ("KAN2", KAN_KNOT["g_kan2"])):
        kspec = KANSpec(dims=KAN_KNOT["dims"], grid_size=grid)
        k_w, k_x = jax.random.split(jax.random.PRNGKey(seed))
        qparams = quantize_kan_network(init_kan_network(k_w, kspec), kspec)
        dep = deploy_kan_network(qparams, kspec, batch=EDGE_ROWS)
        x = jax.random.uniform(k_x, (EDGE_ROWS, kspec.dims[0]),
                               minval=-1.0, maxval=1.0)
        check_stack(f"{name} (G={grid})", dep, x, EDGE_OUT_TOL,
                    "f32 reassociation only")


# --- phase B -----------------------------------------------------------------


def lm_config(layers: int):
    from repro.configs.registry import get_config

    cfg = dataclasses.replace(get_config("qwen2.5-14b-kanffn"),
                              num_layers=layers)
    from repro.models.layers import kan_ffn_hidden

    widths = (cfg.d_model, cfg.phys_heads, cfg.phys_kv_heads, cfg.head_dim,
              cfg.vocab_size, kan_ffn_hidden(cfg), cfg.kan_grid,
              cfg.kan_order, cfg.kan_n_bits, cfg.dtype)
    if widths != (5120, 48, 8, 128, 152064, 1280, 8, 3, 8, "bfloat16"):
        raise AssertionError(f"not the published widths: {widths}")
    return cfg


def check_ffn_block(cfg, params, seed: int) -> None:
    """B1: one KAN-FFN block of the served model, pipeline vs reference."""
    from repro.core.kan_ffn_deploy import quantize_kan_ffn
    from repro.core.kan_network_deploy import deploy_kan_ffn_stack
    from repro.models.layers import kan_ffn_hidden, kan_ffn_specs

    ffn = jax.tree.map(lambda a: a[0], params["decoder"][0]["l0_ffn"])
    q = quantize_kan_ffn(ffn, cfg)
    d = cfg.d_model
    dims = (d, kan_ffn_hidden(cfg), d)
    for rows in FFN_ROWS:
        dep = deploy_kan_ffn_stack([q["l1"], q["l2"]], dims,
                                   kan_ffn_specs(cfg), batch=rows)
        x = jax.random.normal(jax.random.PRNGKey(seed + rows), (rows, d),
                              jnp.float32)
        check_stack(f"KAN-FFN {dims} x{rows}", dep, x, FFN_OUT_TOL,
                    "f32 reassociation over the 5120 x 11 contraction")


def make_requests(cfg, seed: int, n: int) -> list:
    """Seeded prompts of 128-1024 tokens (multiples of 64, so the 256-token
    prefill chunks fall into three length buckets) and 16-32 new tokens."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = 64 * int(rng.integers(2, 17))
        prompt = rng.integers(3, cfg.vocab_size, plen).tolist()
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new_tokens=int(rng.integers(16, 33)),
                            eos_id=-1))
    return reqs


def serve(engine, reqs, *, label: str) -> tuple:
    """Serve ``reqs`` to idle through the Scheduler; every request must
    finish with all its tokens.  Returns (requests, scheduler stats)."""
    from repro.serve.scheduler import Scheduler

    sched = Scheduler(engine)
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    done = sched.run_until_idle()
    wall = time.perf_counter() - t0
    bad = [(r.rid, r.status, len(r.output)) for r in done
           if r.status != "done" or len(r.output) != r.max_new_tokens]
    if len(done) != len(reqs) or bad:
        raise AssertionError(f"{label}: unfinished requests {bad} "
                             f"({len(done)}/{len(reqs)} returned)")
    stats = sched.stats()
    toks = sum(len(r.output) for r in done)
    print(f"  {label}: {len(done)} requests done, {toks} tokens, "
          f"{wall:.1f} s wall", flush=True)
    return done, stats


def compile_counts(engine) -> tuple:
    s = engine.compile_stats()
    return (s["prefill_traces"], s["decode_traces"],
            s["plan_cache"]["traces"], s["plan_cache"]["misses"])


def build_engine(params, cfg, *, kan_backend: str, attn_backend: str,
                 mesh=None):
    from repro.serve.engine import ServeEngine

    return ServeEngine(params, cfg, slots=8, max_len=2048, kan_deploy=True,
                       kan_backend=kan_backend, attn_backend=attn_backend,
                       kv_block_size=16, prefill_chunk=256, mesh=mesh)


def serve_warm(engine, cfg, seed: int, n: int, label: str) -> list:
    """B2: a warm-up pass, then the same request shapes with new tokens —
    the second pass may not compile anything."""
    serve(engine, make_requests(cfg, seed + 1000, n), label=f"{label} warm-up")
    before = compile_counts(engine)
    reqs, stats = serve(engine, make_requests(cfg, seed, n), label=label)
    after = compile_counts(engine)
    print(f"  {label}: compiles (prefill, decode, kan traces, kan misses) "
          f"after warm-up {before}, after serving {after}", flush=True)
    if after != before:
        raise AssertionError(f"{label}: compiled after warm-up: "
                             f"{before} -> {after}")
    ttft, itl = stats["ttft_s"], stats["itl_s"]
    print(f"  {label} (information, not a benchmark): TTFT p50 "
          f"{ttft['p50']:.4f} s, inter-token p50 {itl['p50']:.4f} s",
          flush=True)
    return reqs


def teacher_forced(engine, req) -> np.ndarray:
    logits = engine.teacher_forced_logits(req.prompt, req.output[:-1])
    if not np.all(np.isfinite(logits)):
        raise AssertionError("non-finite logits")
    return logits


def compare_logits(name, got, want, tol: float, why: str) -> None:
    from repro.runtime.parity import assert_close

    for i in range(got.shape[0]):
        assert_close(f"{name} row {i}", got[i], want[i], tol, why)
    err = assert_close(name, got, want, tol, why)
    print(f"  {name}: {got.shape[0]} rows x {got.shape[1]} logits, scaled "
          f"max error {err:.3e} (tol {tol:.0e})", flush=True)


def phase_b(cfg, seed: int, n_requests: int) -> None:
    from repro.models.model import init_params

    dev = jax.devices()[0]
    print(f"phase B: {cfg.name} at published widths, {cfg.num_layers} "
          "layers", flush=True)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    check_ffn_block(cfg, params, seed)

    engine = build_engine(params, cfg, kan_backend="pallas",
                          attn_backend="flash")
    print(f"  engine built: attention {engine.attn_backend}, paged "
          f"{engine.paged}; peak_bytes_in_use {peak_bytes(dev)}", flush=True)
    reqs = serve_warm(engine, cfg, seed, n_requests, "serve")
    probe = max(reqs, key=lambda r: len(r.prompt))
    # the probe's prompt is in the prefix cache now, so its prefill runs
    # only the tail after the cached blocks: a new chunk length may compile
    got = teacher_forced(engine, probe)
    print(f"  peak_bytes_in_use after serving {peak_bytes(dev)}", flush=True)
    del engine

    ref = build_engine(params, cfg, kan_backend="ref", attn_backend="ref")
    with jax.default_matmul_precision("highest"):
        want = teacher_forced(ref, probe)
    compare_logits(f"request {probe.rid} ({len(probe.prompt)}-token prompt) "
                   "logits vs ref", got, want, LOGITS_TOL,
                   "bf16 activations and reference rounding")


def phase_mesh(cfg, seed: int, n_requests: int) -> None:
    """The phase-B configuration on a data=2,model=2 mesh vs one chip."""
    from repro import runtime
    from repro.launch.mesh import parse_mesh_spec
    from repro.models.model import init_params

    print(f"four chips: {cfg.name}, {cfg.num_layers} layers, data=2,model=2 "
          "mesh vs one chip", flush=True)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    mesh = parse_mesh_spec("data=2,model=2")
    runtime.reset_shard_notes()
    sharded = build_engine(params, cfg, kan_backend="pallas",
                           attn_backend="flash", mesh=mesh)
    print(f"  mesh engine: {sharded.mesh_layout()}", flush=True)
    reqs = serve_warm(sharded, cfg, seed, n_requests, "mesh serve")
    notes = {fp: r for fp, r in runtime.shard_notes().items() if r}
    if notes:
        raise AssertionError(f"replicated-layer fallback on the mesh: {notes}")
    probes = sorted(reqs, key=lambda r: len(r.prompt))[-2:]
    got = [teacher_forced(sharded, r) for r in probes]
    del sharded

    single = build_engine(params, cfg, kan_backend="pallas",
                          attn_backend="flash")
    for r, g in zip(probes, got):
        compare_logits(f"request {r.rid} ({len(r.prompt)}-token prompt) "
                       "mesh vs one chip", g, teacher_forced(single, r),
                       MESH_LOGITS_TOL, "partitioned reductions")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, inputs and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=2,model=2 mesh path and its "
                         "one-chip comparison (needs four chips)")
    args = ap.parse_args()

    devices = device_report()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    cfg = lm_config(LAYERS)
    if args.four_chips:
        if len(devices) < 4:
            sys.exit(f"chip_smoke: --four-chips needs 4 devices, "
                     f"found {len(devices)}")
        phase_mesh(cfg, args.seed, REQUESTS)
    else:
        phase_a(args.seed)
        phase_b(cfg, args.seed, REQUESTS)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
