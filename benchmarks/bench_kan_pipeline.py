"""Benchmark the multi-layer KAN inference paths; seeds the perf trajectory.

Four executors over the same quantized network, all resolved through
``repro.runtime``:

  * ``float``      — kan_network_apply float path (Cox-de Boor basis, f32)
  * ``quant_ref``  — layered jnp quantized path (backend="ref"): per-layer
                     quantize / SH-LUT / banded matmul with f32 round-trips
                     between layers
  * ``fused``      — the fused Pallas pipeline (backend="pallas"): every
                     layer in the kan_spline kernel, inter-layer
                     requantization fused, int codes across boundaries
  * ``acim``       — the fused pipeline with the paper's measured RRAM-ACIM
                     non-idealities injected at the MAC stage (TM-DV input
                     noise, IR-drop, partial-sum sigma)

at the paper's KAN1 (17,1,14 / G=5) and KAN2 (G=68) edge configs and one
transformer-FFN width (the qwen2.5-14b smoke KAN-FFN geometry).  Each row
also reports executor throughput (rows through the KAN per second) and the
run ends with the runtime plan-cache hit/miss/trace counters plus a small
end-to-end served-tokens/s measurement of the continuous-batching engine on
the fused datapath.  A SUSTAINED section then drives the async scheduler
with a deterministic Poisson-ish arrival schedule of a mixed
shared-prefix/unique workload per runtime backend on the paged-KV engine
(block pool + prefix cache + chunked prefill), plus contiguous-slab and
prefix-cache-off comparison legs, recording TTFT p50/p95, inter-token
latency, tokens/s, queue-depth trace and the KV pool's hit-rate /
peak-blocks counters (the docs/serving.md metrics glossary).  Speculative
legs rerun the paged schedule per backend with ``spec_decode=2`` (a cheap
halved-grid KAN drafter + one-pass batched verify; greedy streams stay
bit-identical), recording accept rate, tokens-per-round and draft/verify
p50 next to the spec-off baselines.  An ATTENTION
section times the decode step per attention backend ("ref" chunked XLA vs
"flash" fused Pallas) on the KAN-deployed engine — with "flash" every
FLOP-heavy op of the step is a fused kernel — plus a prefill-shape SDPA
microbench.  A SHARDED section then times the mesh-sharded runtime
(data-only and data x model meshes over every host device, plus a
mesh-sharded engine leg), recording mesh shape and device count so the perf
trajectory captures scaling — run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to exercise it on a
CPU container.  ``--tuned`` adds a heuristic-plan vs tuned-plan leg:
``repro.tune.tiles`` sweeps tile geometries for each config (measured on
TPU, deterministic cost proxy in interpret mode), registers the winner with
the plan cache, and the fused executor is re-timed on it.  Off-TPU the
Pallas path runs in interpret mode — those numbers validate plumbing, not
TPU perf (same caveat as benchmarks/run.py's kernel microbench).

    PYTHONPATH=src python benchmarks/bench_kan_pipeline.py --out BENCH_kan_pipeline.json
    PYTHONPATH=src python benchmarks/bench_kan_pipeline.py --smoke --tuned  # CI step
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import runtime
from repro.launch.compile_cache import use_compile_cache
from repro.core.cim import CIMConfig
from repro.core.kan_layer import KANSpec, init_kan_network, kan_network_apply
from repro.core.kan_network_deploy import (
    default_interpret,
    deploy_kan_network,
    kan_network_deploy_apply,
    quantize_kan_network,
)

CONFIGS = [
    # (name, dims, grid)  — KAN1/KAN2 are the paper's edge nets (§4);
    # ffn_width is the LM deployment surface (models/layers KAN-FFN smoke).
    ("kan1_17_1_14_g5", (17, 1, 14), 5),
    ("kan2_17_1_14_g68", (17, 1, 14), 68),
    ("ffn_64_128_64_g8", (64, 128, 64), 8),
]

# The measured 22nm calibration used by examples/knot_e2e.py.
ACIM_CFG = CIMConfig(ir_gamma=0.06, sigma_ps_ref=0.05)


def _time_fn(fn, x, repeats: int) -> tuple:
    """(mean_us, min_us) over ``repeats`` timed calls after a warmup.

    The mean stays comparable with earlier committed runs; the min is the
    contention-robust number (shared CI/container CPUs jitter interpret-mode
    timings by 2-3x).
    """
    fn(x).block_until_ready()  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    return sum(times) / len(times) * 1e6, min(times) * 1e6


def _bench_serve(requests: int, max_new: int, print_fn=print,
                 mesh=None) -> dict:
    """End-to-end served-tokens/s of the fused datapath (continuous batching
    over the qwen2.5-14b smoke KAN-FFN config, mixed prompt lengths).  With
    ``mesh`` the engine serves mesh-sharded (slots/KV on "data", KAN-FFN
    channels on "model")."""
    from repro.configs.registry import smoke_config
    from repro.models.model import init_params
    from repro.serve.engine import Request, ServeEngine

    from repro import runtime

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, slots=2, max_len=64, kan_deploy=True,
                         mesh=mesh)
    rng = jax.random.PRNGKey(1)
    reqs = []
    for rid in range(requests):
        rng, k = jax.random.split(rng)
        plen = 4 + rid % 7  # mixed lengths exercise the prefill buckets
        prompt = jax.random.randint(k, (plen,), 3, cfg.vocab_size).tolist()
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    pc0 = runtime.cache_stats()
    d0 = dict(runtime.dispatch_counts())
    t0 = time.perf_counter()
    results = engine.run(reqs)
    wall = time.perf_counter() - t0
    total = sum(len(r.output) for r in results)
    stats = engine.compile_stats()
    pc1 = runtime.cache_stats()
    row = {
        "arch": "qwen2.5-14b-kanffn",
        "requests": requests,
        "tokens": total,
        "tokens_per_s": total / wall,
        "prefill_traces": stats["prefill_traces"],
        "decode_traces": stats["decode_traces"],
        "mesh": stats["mesh"],
        # this leg's slice of the process-wide runtime counters (the same
        # series the obs registry exports; docs/observability.md)
        "plan_cache": {k: pc1[k] - pc0[k]
                       for k in ("hits", "misses", "traces")},
        "backend_dispatch": {
            k: v - d0.get(k, 0)
            for k, v in sorted(runtime.dispatch_counts().items())
            if v - d0.get(k, 0)
        },
        "kv": engine.kv_stats(),
    }
    print_fn(
        f"serve,arch={row['arch']},tokens={total},"
        f"tokens_per_s={row['tokens_per_s']:.1f},"
        f"prefill_traces={row['prefill_traces']},"
        f"mesh={None if mesh is None else 'x'.join(map(str, row['mesh']['shape']))}"
    )
    return row


def _bench_sustained(requests: int, max_new: int, print_fn=print,
                     mean_interarrival_s: float = 0.05,
                     arrival_seed: int = 1234) -> dict:
    """Sustained mixed load through the async scheduler, per backend.

    A deterministic Poisson-ish arrival schedule (exponential inter-arrival
    gaps from a fixed-seed generator — identical offsets every run and for
    every engine) drives a MIXED workload: even-rid requests share a
    32-token prefix (the "common system prompt" — 4 full KV blocks at the
    paged legs' 8-token block size) with a short unique tail, odd-rid
    requests are unique mixed-length prompts.  Requests are submitted with
    future ``arrival_s`` offsets, so prompts prefill into free slots
    *between* decode steps of earlier requests exactly as under live
    traffic.

    Each runtime backend (``ref`` / ``pallas`` / ``acim``) serves the
    schedule on a PAGED engine (block pool + prefix cache + chunked
    prefill) after a warmup that compiles every trace the schedule hits, so
    TTFT measures scheduling + prefill, not jit compilation.  Two extra
    legs on the fused backend — the contiguous slab and the paged pool with
    the prefix cache off — isolate what the pool and the cache each buy:
    the shared-prefix half of the workload prefills once under
    ``paged_cache`` and every time under the other two.  Every row records
    the docs/serving.md metrics (TTFT p50/p95, inter-token latency,
    tokens/s, queue-depth trace) plus the KV pool counters (prefix hit
    rate, peak blocks in use, evictions) where applicable.
    """
    import random as _random

    from repro import runtime
    from repro.configs.registry import smoke_config
    from repro.models.model import init_params
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.scheduler import Scheduler

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    params = init_params(jax.random.PRNGKey(0), cfg)
    gen = _random.Random(arrival_seed)
    offsets, t = [], 0.0
    for _ in range(requests):
        offsets.append(t)
        t += gen.expovariate(1.0 / mean_interarrival_s)

    BS = 8                 # paged legs: KV block size (flash KV tile)
    KV_BLOCKS = 48         # pool head-room for cached prefixes + both slots
    SHARED = [9] * (4 * BS)  # the shared system prompt: 4 FULL blocks

    def make_prompts():
        rng = jax.random.PRNGKey(1)
        prompts = []
        for rid in range(requests):
            rng, k = jax.random.split(rng)
            if rid % 2 == 0:  # shared-prefix half: common prompt + 4-tok tail
                prompts.append(
                    SHARED
                    + jax.random.randint(k, (4,), 3, cfg.vocab_size).tolist())
            else:             # unique half, mixed lengths (prefill buckets)
                plen = 4 + rid % 7
                prompts.append(
                    jax.random.randint(k, (plen,), 3, cfg.vocab_size).tolist())
        return prompts

    prompts = make_prompts()

    def serve_one(engine, label):
        # compile outside the timed window.  Contiguous engines need one
        # prefill trace per length bucket the schedule hits; paged engines
        # chunk every prompt into `prefill_chunk`-token pieces (one bucket),
        # so a single full-chunk + partial-chunk warm prompt covers them.
        if engine.paged:
            warm_lens = {BS + 1, 2}
            if getattr(engine, "spec_k", 0):
                # the drafter prefills whole prompts through bucketed pads
                # (not chunks) — warm every bucket the schedule hits, or
                # its compiles land inside the measured window
                warm_lens |= {len(p) for p in prompts}
        else:
            warm_lens = {len(engine._padded_prompt([3] * len(p)))
                         for p in prompts}
        warm = [Request(rid=-1 - i, prompt=[5] * ln, max_new_tokens=2)
                for i, ln in enumerate(sorted(warm_lens))]
        engine.run(warm)
        if engine.paged:
            engine.pool.reset_stats()  # warm prompts are not workload hits
        # counter baselines AFTER warmup: the leg's plan-cache / dispatch
        # slice reflects the measured schedule, not compile warming
        pc0 = runtime.cache_stats()
        d0 = dict(runtime.dispatch_counts())
        # build the request list BEFORE the scheduler: its construction
        # starts the arrival_s timebase, and request construction must not
        # eat into the schedule (submit bumps past offsets to "now")
        reqs = [Request(rid=rid, prompt=p, max_new_tokens=max_new,
                        arrival_s=offsets[rid])
                for rid, p in enumerate(prompts)]
        sched = Scheduler(engine)
        for r in reqs:
            sched.submit(r)
        sched.run_until_idle()
        s = sched.stats()
        kv = s["kv"]
        sp = s["spec"]
        pc1 = runtime.cache_stats()
        row = {
            **label,
            "requests": requests,
            "completed": s["completed"],
            "tokens": s["tokens"],
            "tokens_per_s": s["tokens_per_s"],
            "ttft_p50_s": s["ttft_s"]["p50"],
            "ttft_p95_s": s["ttft_s"]["p95"],
            "itl_p50_s": s["itl_s"]["p50"],
            "itl_p95_s": s["itl_s"]["p95"],
            "queue_depth_max": s["queue_depth"]["max"],
            "queue_depth_mean": s["queue_depth"]["mean"],
            "prefix_hit_rate": None if kv is None else kv["prefix_hit_rate"],
            "prefix_hits": None if kv is None else kv["prefix_hits"],
            "prefix_misses": None if kv is None else kv["prefix_misses"],
            "kv_blocks_in_use_peak": (None if kv is None
                                      else kv["blocks_in_use_peak"]),
            "kv_blocks_cached": None if kv is None else kv["blocks_cached"],
            "kv_evictions": None if kv is None else kv["evictions"],
            "kv_allocs": None if kv is None else kv["allocs"],
            # speculative-decode leg fields (spec_k=0 rows: the baseline)
            "spec_k": 0 if sp is None else sp["k"],
            "tokens_per_round": s["tokens_per_round"],
            "accept_rate": None if sp is None else sp["accept_rate"],
            "draft_ms": (None if sp is None or sp["draft_s"]["p50"] is None
                         else sp["draft_s"]["p50"] * 1e3),
            "verify_ms": (None if sp is None or sp["verify_s"]["p50"] is None
                          else sp["verify_s"]["p50"] * 1e3),
            "plan_cache": {k: pc1[k] - pc0[k]
                           for k in ("hits", "misses", "traces")},
            "backend_dispatch": {
                k: v - d0.get(k, 0)
                for k, v in sorted(runtime.dispatch_counts().items())
                if v - d0.get(k, 0)
            },
        }
        print_fn(
            f"sustained,backend={row['backend']},kv={row['kv']},"
            f"spec_k={row['spec_k']},"
            f"tokens={row['tokens']},tokens_per_s={row['tokens_per_s']:.1f},"
            f"ttft_p50_ms={row['ttft_p50_s'] * 1e3:.1f},"
            f"ttft_p95_ms={row['ttft_p95_s'] * 1e3:.1f},"
            f"qdepth_max={row['queue_depth_max']}"
            + ("" if kv is None else
               f",hit_rate={row['prefix_hit_rate']:.2f},"
               f"kv_peak={row['kv_blocks_in_use_peak']}")
            + ("" if sp is None or row["accept_rate"] is None else
               f",accept_rate={row['accept_rate']:.2f},"
               f"tok_per_round={row['tokens_per_round']:.2f}")
        )
        return row

    paged_kw = dict(kv_block_size=BS, kv_blocks=KV_BLOCKS, prefill_chunk=BS)
    SPEC_K = 2  # speculative legs: k drafted tokens per slot per round
    rows = []
    for backend in ("ref", "pallas", "acim"):
        engine = ServeEngine(params, cfg, slots=2, max_len=64,
                             kan_deploy=True, kan_backend=backend,
                             prefix_cache=True, **paged_kw)
        rows.append(serve_one(engine, {"backend": backend,
                                       "kv": "paged_cache"}))
    # speculative-decode legs: same schedule, same paged engine, a cheap
    # KAN drafter (default halved grid) proposing SPEC_K tokens per round
    # with one batched verify pass — greedy streams stay bit-identical, so
    # tokens/tokens_per_s compare directly against the spec_k=0 rows above
    for backend in ("ref", "pallas", "acim"):
        engine = ServeEngine(params, cfg, slots=2, max_len=64,
                             kan_deploy=True, kan_backend=backend,
                             prefix_cache=True, spec_decode=SPEC_K,
                             **paged_kw)
        rows.append(serve_one(engine, {"backend": backend,
                                       "kv": "paged_cache"}))
    # what did the pool / the prefix cache each buy? — same schedule on the
    # fused backend with (a) the contiguous slab, (b) the pool, cache off
    for kv_mode, kw in (("contiguous", {}),
                        ("paged_nocache", dict(prefix_cache=False,
                                               **paged_kw))):
        engine = ServeEngine(params, cfg, slots=2, max_len=64,
                             kan_deploy=True, kan_backend="pallas", **kw)
        rows.append(serve_one(engine, {"backend": "pallas", "kv": kv_mode}))

    def _pallas(kv_mode):
        return next(r for r in rows
                    if r["backend"] == "pallas" and r["kv"] == kv_mode
                    and r["spec_k"] == 0)

    summary = {  # the cache-on-vs-off headline (acceptance: on <= off p95)
        "ttft_p95_contiguous_s": _pallas("contiguous")["ttft_p95_s"],
        "ttft_p95_paged_nocache_s": _pallas("paged_nocache")["ttft_p95_s"],
        "ttft_p95_paged_cache_s": _pallas("paged_cache")["ttft_p95_s"],
        "prefix_hit_rate": _pallas("paged_cache")["prefix_hit_rate"],
    }
    print_fn(
        f"sustained,kv_summary,"
        f"ttft_p95_contiguous_ms={summary['ttft_p95_contiguous_s'] * 1e3:.1f},"
        f"ttft_p95_nocache_ms={summary['ttft_p95_paged_nocache_s'] * 1e3:.1f},"
        f"ttft_p95_cache_ms={summary['ttft_p95_paged_cache_s'] * 1e3:.1f},"
        f"hit_rate={summary['prefix_hit_rate']:.2f}"
    )

    def _leg(backend, spec_k):
        return next(r for r in rows
                    if r["backend"] == backend and r["kv"] == "paged_cache"
                    and r["spec_k"] == spec_k)

    spec_summary = {  # the spec-on-vs-off headline per backend
        "k": SPEC_K,
        "per_backend": {
            b: {
                "tokens_per_s_off": _leg(b, 0)["tokens_per_s"],
                "tokens_per_s_on": _leg(b, SPEC_K)["tokens_per_s"],
                "accept_rate": _leg(b, SPEC_K)["accept_rate"],
                "tokens_per_round": _leg(b, SPEC_K)["tokens_per_round"],
                "draft_ms": _leg(b, SPEC_K)["draft_ms"],
                "verify_ms": _leg(b, SPEC_K)["verify_ms"],
            }
            for b in ("ref", "pallas", "acim")
        },
    }
    for b, d in spec_summary["per_backend"].items():
        print_fn(
            f"sustained,spec_summary,backend={b},k={SPEC_K},"
            f"tok_s_off={d['tokens_per_s_off']:.1f},"
            f"tok_s_on={d['tokens_per_s_on']:.1f},"
            + (f"accept_rate={d['accept_rate']:.2f},"
               if d["accept_rate"] is not None else "accept_rate=n/a,")
            + f"tok_per_round={d['tokens_per_round']:.2f}"
        )
    return {
        "arch": "qwen2.5-14b-kanffn",
        "slots": 2,
        "arrival_seed": arrival_seed,
        "mean_interarrival_s": mean_interarrival_s,
        "arrival_offsets_s": offsets,
        "workload": {
            "requests": requests,
            "shared_prefix_tokens": len(SHARED),
            "shared_prefix_share": 0.5,
            "unique_plen_range": [4, 10],
        },
        "kv_block_size": BS,
        "kv_blocks": KV_BLOCKS,
        "prefill_chunk": BS,
        "spec_k": SPEC_K,
        "rows": rows,
        "kv_summary": summary,
        "spec_summary": spec_summary,
    }


def _bench_attention(repeats: int, print_fn=print) -> dict:
    """Per-step decode latency per ATTENTION backend — the "every FLOP-heavy
    op fused" datapoint.

    Times the engine's compiled decode step (all slots advance one token) on
    the qwen2.5-14b smoke KAN-FFN config with ``kan_deploy=True``, once per
    registered attention backend: with "flash" both the attention and the
    KAN-FFN halves of every block execute as fused Pallas kernels
    (``all_fused`` in the row), with "ref" attention stays on the chunked
    XLA composition.  A full-sequence prefill-shape SDPA microbench (ref vs
    flash on the same GQA geometry) rides along.  Off-TPU both kernels run
    in interpret mode — these numbers validate plumbing, not TPU perf.
    """
    from repro.configs.registry import smoke_config
    from repro.models import layers as L
    from repro.models.model import init_params
    from repro.serve.engine import Request, ServeEngine

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    params = init_params(jax.random.PRNGKey(0), cfg)

    # prefill-shape microbench: full-sequence SDPA, per backend
    b, s, d = 2, 128, cfg.head_dim
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (b, s, cfg.num_heads, d), jnp.float32)
    k = jax.random.normal(key, (b, s, cfg.num_kv_heads, d), jnp.float32)
    v = jax.random.normal(key, (b, s, cfg.num_kv_heads, d), jnp.float32)
    prefill_rows = []
    for backend in runtime.available_attn_backends():
        fn = jax.jit(
            lambda q, be=backend: L._sdpa(q, k, v, cfg, "global", backend=be)
        )
        mean_us, min_us = _time_fn(fn, q, repeats)
        prefill_rows.append({
            "attn_backend": backend, "batch": b, "seq": s,
            "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "sdpa_us": mean_us, "sdpa_min_us": min_us,
        })
        print_fn(f"attention,leg=prefill_sdpa,attn_backend={backend},"
                 f"seq={s},us={mean_us:.0f}")

    # decode-step latency: continuous-batching engine, fused KAN datapath
    decode_rows = []
    for backend in runtime.available_attn_backends():
        engine = ServeEngine(params, cfg, slots=2, max_len=64,
                             kan_deploy=True, attn_backend=backend)
        for rid in range(engine.slots):
            engine._admit(Request(rid=rid, prompt=[5, 6, 7, 8],
                                  max_new_tokens=4))
        pos = jnp.asarray(engine.pos)

        def step(tok, eng=engine, pos=pos):
            with runtime.use_backend(eng.kan_backend):
                logits, _ = eng._decode(eng.params, eng.cache, tok, pos)
            return logits

        token = jnp.zeros((engine.slots,), jnp.int32)
        mean_us, min_us = _time_fn(step, token, repeats)
        row = {
            "attn_backend": engine.attn_backend,
            "kan_backend": runtime.resolve_backend(engine.kan_backend),
            "all_fused": engine.attn_backend == "flash",
            "slots": engine.slots,
            "decode_step_us": mean_us,
            "decode_step_min_us": min_us,
            "tokens_per_s": engine.slots / (min_us * 1e-6),
        }
        decode_rows.append(row)
        print_fn(
            f"attention,leg=decode_step,attn_backend={row['attn_backend']},"
            f"kan_backend={row['kan_backend']},"
            f"all_fused={int(row['all_fused'])},"
            f"decode_step_us={mean_us:.0f},tok_s={row['tokens_per_s']:.0f}"
        )
    return {"arch": "qwen2.5-14b-kanffn", "prefill": prefill_rows,
            "decode": decode_rows}


def _bench_sharded(batch: int, repeats: int, serve_requests: int,
                   serve_max_new: int, print_fn=print) -> dict:
    """Mesh-sharded legs: the perf trajectory's scaling axis.

    Times the fused executor on a data-only mesh over every host device and
    (when >= 2 devices) a data x model mesh, on the FFN-width config whose
    output channels actually shard, plus a sharded-engine served-tokens/s
    leg.  Mesh shape and device count ride in every row so BENCH json
    captures scaling, not just single-device latency.  On 1 device this
    degenerates to a 1x1 mesh — the overhead-of-shard_map datapoint.
    """
    from repro.launch.mesh import make_local_mesh

    n = len(jax.devices())
    interpret = default_interpret()
    name, dims, grid = CONFIGS[2]  # ffn width: op=128 per layer, shardable
    kspec = KANSpec(dims=dims, grid_size=grid)
    key = jax.random.PRNGKey(0)
    qparams = quantize_kan_network(init_kan_network(key, kspec), kspec)
    dep = deploy_kan_network(qparams, kspec, batch=batch)
    x = jax.random.uniform(key, (batch, dims[0]), minval=-1.0, maxval=1.0)

    legs = [("data", make_local_mesh(n, 1))]
    if n >= 2:
        legs.append(("data_x_model", make_local_mesh(n // 2, 2)))
    rows = []
    for leg, mesh in legs:
        fn = lambda x, m=mesh: kan_network_deploy_apply(
            dep, x, interpret=interpret, backend="pallas", mesh=m
        )
        mean_us, min_us = _time_fn(fn, x, repeats)
        row = {
            "name": name,
            "leg": leg,
            "mesh_axes": list(mesh.axis_names),
            "mesh_shape": [int(s) for s in mesh.devices.shape],
            "device_count": n,
            "batch": batch,
            "fused_sharded_us": mean_us,
            "fused_sharded_min_us": min_us,
            "fused_sharded_tokens_per_s": batch / (min_us * 1e-6),
        }
        rows.append(row)
        print_fn(
            f"sharded,{name},leg={leg},"
            f"mesh={'x'.join(map(str, row['mesh_shape']))},"
            f"devices={n},fused_sharded_us={mean_us:.0f},"
            f"tok_s={row['fused_sharded_tokens_per_s']:.0f}"
        )
    serve = _bench_serve(serve_requests, serve_max_new, print_fn=print_fn,
                         mesh=legs[-1][1])
    return {"device_count": n, "rows": rows, "serve": serve}


def _bench_quant_frontier(print_fn=print, epochs: int = 20) -> dict:
    """KANtize-style accuracy-vs-bits frontier on the paper's KAN1 geometry.

    One small float base network is trained once on the knot surrogate,
    then every per-layer bit allocation in the sweep is quantized/deployed
    from it (mixed-precision ``KANSpec.n_bits`` tuples; <=4-bit layers run
    int4-packed through the fused kernel) and scored exactly like the
    co-design search: accuracy on the ``acim`` backend with the measured
    22nm non-idealities, cost via ``kan_cost`` with bit-dependent cell
    area/energy.  Rows carry a ``pareto`` flag on (energy_pj, accuracy) —
    the sub-8-bit allocations trade accuracy for energy, and at least one
    lands on the front (the (4, 4) corner is the energy argmin by
    construction).
    """
    from repro import tune

    task = tune.make_knot_task(n_train=2048, n_val=256, epochs=epochs,
                               seed=0, base_grid=5, calib_n=128)
    allocations = ((8, 8), (8, 4), (4, 8), (4, 4))
    points = []
    for alloc in allocations:
        cand = tune.Candidate(grid_size=5, order=3, n_bits=8,
                              layer_bits=alloc)
        metrics = tune.evaluate_candidate(task, cand, acim_seeds=2)
        points.append(tune.EvaluatedPoint(candidate=cand, metrics=metrics))
    front = tune.pareto_front(points, ("energy_pj", "accuracy"))
    rows = []
    for p in points:
        row = {
            "layer_bits": list(p.candidate.layer_bits),
            "accuracy": p.metrics["accuracy"],
            "energy_pj": p.metrics["energy_pj"],
            "area_mm2": p.metrics["area_mm2"],
            "latency_ns": p.metrics["latency_ns"],
            "sub8": any(b < 8 for b in p.candidate.layer_bits),
            "pareto": any(q is p for q in front),
        }
        rows.append(row)
        print_fn(
            f"quant_frontier,bits={'/'.join(map(str, row['layer_bits']))},"
            f"accuracy={row['accuracy']:.3f},"
            f"energy_pj={row['energy_pj']:.1f},"
            f"area_mm2={row['area_mm2']:.4f},"
            f"pareto={int(row['pareto'])}"
        )
    assert any(r["pareto"] and r["sub8"] for r in rows), rows
    return {
        "dims": list(task.dims),
        "grid": 5,
        "objectives": ["energy_pj", "accuracy"],
        "rows": rows,
    }


def run(batch: int = 128, repeats: int = 10, serve_requests: int = 4,
        serve_max_new: int = 8, sustained_requests: int = 60,
        tuned: bool = False, tile_candidates: int = 10,
        print_fn=print) -> dict:
    interpret = default_interpret()
    runtime.reset_cache()
    rows = []
    for name, dims, grid in CONFIGS:
        kspec = KANSpec(dims=dims, grid_size=grid)
        key = jax.random.PRNGKey(0)
        params = init_kan_network(key, kspec)
        qparams = quantize_kan_network(params, kspec)
        dep = deploy_kan_network(qparams, kspec, batch=batch)
        x = jax.random.uniform(key, (batch, dims[0]), minval=-1.0, maxval=1.0)

        float_fn = jax.jit(lambda x, ks=kspec, p=params: kan_network_apply(p, x, ks))
        ref_fn = lambda x, d=dep: kan_network_deploy_apply(
            d, x, interpret=interpret, backend="ref"
        )
        fused_fn = lambda x, d=dep: kan_network_deploy_apply(
            d, x, interpret=interpret, backend="pallas"
        )
        acim_fn = lambda x, d=dep: kan_network_deploy_apply(
            d, x, interpret=interpret, backend="acim", cim=ACIM_CFG,
            key=jax.random.PRNGKey(0),
        )

        row = {"name": name, "dims": list(dims), "grid": grid, "batch": batch,
               "pallas_interpret": interpret}
        for label, fn in (("float", float_fn), ("quant_ref", ref_fn),
                          ("fused_pallas", fused_fn), ("acim", acim_fn)):
            mean_us, min_us = _time_fn(fn, x, repeats)
            row[f"{label}_us"] = mean_us
            row[f"{label}_min_us"] = min_us
        row["fused_tokens_per_s"] = batch / (row["fused_pallas_min_us"] * 1e-6)
        row["acim_tokens_per_s"] = batch / (row["acim_min_us"] * 1e-6)
        err = float(jnp.abs(fused_fn(x) - ref_fn(x)).max())
        row["fused_vs_ref_max_err"] = err
        row["acim_vs_fused_max_err"] = float(
            jnp.abs(acim_fn(x) - fused_fn(x)).max()
        )
        if tuned:
            # heuristic-plan vs tuned-plan fused execution.  The tile tuner
            # registers its winner with the plan cache (warm-traced inside
            # the tuner), so the same fused_fn transparently runs the tuned
            # geometry afterwards; off-TPU the tuner ranks by its
            # deterministic proxy and typically keeps the heuristic.
            from repro.tune import tune_tiles

            tile = tune_tiles(dep, batch=batch, interpret=interpret,
                              max_candidates=tile_candidates)
            mean_us, min_us = _time_fn(fused_fn, x, repeats)
            row["fused_tuned_us"] = mean_us
            row["fused_tuned_min_us"] = min_us
            row["tile_mode"] = tile.mode
            row["tile_trials"] = len(tile.trials)
            row["tile_tuned"] = tile.tuned
            row["tile_overrides"] = (
                None if tile.chosen_overrides is None
                else [list(t) for t in tile.chosen_overrides]
            )
            # exactness is a tuner invariant; assert it held end to end
            err_t = float(jnp.abs(fused_fn(x) - ref_fn(x)).max())
            assert err_t == err, (err_t, err)
            runtime.PLAN_CACHE.set_tile_overrides(
                tuple(dep.dims), tuple(dep.specs), dep.residual_raw, None
            )
        rows.append(row)
        msg = (
            f"{name},float_us={row['float_us']:.0f},"
            f"quant_ref_us={row['quant_ref_us']:.0f},"
            f"fused_pallas_us={row['fused_pallas_us']:.0f},"
            f"acim_us={row['acim_us']:.0f},"
            f"fused_tok_s={row['fused_tokens_per_s']:.0f},"
            f"err={err:.2e}"
        )
        if tuned:
            msg += (f",fused_tuned_us={row['fused_tuned_us']:.0f},"
                    f"tile_mode={row['tile_mode']},"
                    f"tile_tuned={int(row['tile_tuned'])}")
        print_fn(msg)
    quant_frontier = _bench_quant_frontier(print_fn=print_fn)
    serve = _bench_serve(serve_requests, serve_max_new, print_fn=print_fn)
    sustained = _bench_sustained(sustained_requests, serve_max_new,
                                 print_fn=print_fn)
    attention = _bench_attention(repeats, print_fn=print_fn)
    sharded = _bench_sharded(batch, repeats, serve_requests, serve_max_new,
                             print_fn=print_fn)
    cache = runtime.cache_stats()  # after the serve legs: they share the cache
    print_fn(f"plan_cache,{cache}")
    return {
        "benchmark": "kan_pipeline",
        "backend": jax.default_backend(),
        "pallas_interpret": interpret,
        "device_count": len(jax.devices()),
        "rows": rows,
        "quant_frontier": quant_frontier,
        "serve": serve,
        "sustained": sustained,
        "attention": attention,
        "sharded": sharded,
        "plan_cache": cache,
    }


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: small batch/repeats, short serve leg")
    ap.add_argument("--tuned", action="store_true",
                    help="add the heuristic-vs-tuned tile-plan leg "
                         "(repro.tune.tiles) to every config")
    ap.add_argument("--out", default="BENCH_kan_pipeline.json")
    args = ap.parse_args()
    if args.smoke:
        result = run(batch=32, repeats=2, serve_requests=2, serve_max_new=4,
                     sustained_requests=6, tuned=args.tuned,
                     tile_candidates=6)
    else:
        result = run(batch=args.batch, repeats=args.repeats,
                     tuned=args.tuned)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
