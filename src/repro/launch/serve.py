"""Serving entrypoint: async streaming scheduler over a (smoke) model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --requests 8 --slots 4
    # stream tokens as they are produced, sample instead of greedy decode:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --stream --sampling 0.8 --top-k 16 --seed 7
    # bounded queue + per-request deadlines (admission/backpressure demo):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --requests 16 --queue-limit 4 --deadline 2.0
    # the paper's datapath, with hardware non-idealities:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --kan-ffn --backend acim
    # deploy a repro.tune co-design artifact (quantization point + tuned
    # tile plan applied at startup):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --kan-ffn --tuned-config TUNE_artifact.json
    # mesh-sharded serving (slots/KV on "data", KAN-FFN channels on "model"):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch qwen2.5-14b --kan-ffn \
        --mesh data=4,model=2
    # paged KV pool with prefix caching and chunked prefill (vLLM-style;
    # greedy streams stay bit-identical to the contiguous slab):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --kv-block-size 16 --prefix-cache on --prefill-chunk 32
    # speculative decoding: a cheap refit KAN drafter proposes 4 tokens per
    # round, one batched target pass verifies them (streams bit-identical):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --kan-ffn --kv-block-size 16 --spec-decode 4 \
        --draft-spec grid=4,bits=6
    # observability: metrics registry + request tracing (docs/observability.md)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --metrics-dump metrics.prom --metrics-dump metrics.json \
        --trace-out trace.jsonl --stats-interval 1.0
    # a jax.profiler capture of the run, and beside it the request and
    # round spans on the profiler's clock (open both in Perfetto):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --kv-block-size 16 --prefill-chunk 8 --profile-dir prof/
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax

from .. import obs
from ..configs.registry import smoke_config
from ..models.model import init_params
from ..serve.engine import Request, ServeEngine
from ..serve.scheduler import QueueFull, SamplingParams, Scheduler
from .compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--kan-ffn", action="store_true")
    ap.add_argument(
        "--stream", action="store_true",
        help="print every token as the scheduler produces it (on_token "
             "streaming) instead of only per-request completion lines",
    )
    ap.add_argument(
        "--sampling", type=float, default=0.0, metavar="TEMP",
        help="decode temperature; 0 (default) = greedy argmax, >0 samples "
             "with --top-k/--top-p under --seed (reproducible)",
    )
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the K highest logits (0=off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0=off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed (per-request streams fold rid)")
    ap.add_argument(
        "--queue-limit", type=int, default=None, metavar="N",
        help="bounded scheduler queue: submissions past N waiting requests "
             "are rejected (admission backpressure); default unbounded",
    )
    ap.add_argument(
        "--deadline", type=float, default=None, metavar="S",
        help="per-request queueing deadline in seconds: a request still "
             "waiting after S is expired unserved",
    )
    ap.add_argument(
        "--backend", default=None, choices=("ref", "pallas", "acim"),
        help="KAN executor backend (with --kan-ffn); default resolves via "
             "REPRO_KAN_BACKEND, then 'pallas'",
    )
    ap.add_argument(
        "--attn-backend", default=None, choices=("ref", "flash"),
        help="attention backend: 'flash' = fused Pallas flash-attention "
             "kernel (online softmax, tiled KV; interpret mode off-TPU), "
             "'ref' = chunked XLA composition; default resolves via "
             "REPRO_ATTN_BACKEND, then flash on TPU / ref elsewhere",
    )
    ap.add_argument(
        "--kan-bits", default=None, metavar="BITS",
        help="with --kan-ffn: per-layer ASP bit widths for the two KANLinear "
             "halves, e.g. '8,4' (mixed precision; <=4-bit layers deploy "
             "int4-packed), or one value for uniform width.  A --tuned-"
             "config artifact's chosen allocation takes precedence; invalid "
             "PowerGap combinations are rejected at startup",
    )
    ap.add_argument(
        "--tuned-config", default=None, metavar="PATH",
        help="repro.tune artifact to deploy: applies its chosen "
             "quantization point to the KAN-FFN config and registers its "
             "tuned tile plan with the runtime plan cache",
    )
    ap.add_argument(
        "--mesh", default=None, metavar="SPEC",
        help="serve mesh-sharded: 'data=2,model=4' (one axis may omit =N to "
             "absorb the remaining devices, e.g. 'data,model=2').  Slots / "
             "KV cache shard on data, KAN-FFN output channels on model; "
             "takes precedence over any ambient runtime.use_mesh scope",
    )
    ap.add_argument(
        "--kv-block-size", type=int, default=None, metavar="TOKENS",
        help="paged KV cache: cut KV storage into blocks of this many "
             "tokens (a multiple of 8 — the flash kernel's KV tile; must "
             "divide max_len) with a free-list allocator, per-request block "
             "tables and a hash-keyed prefix cache; default keeps the "
             "contiguous per-slot slab.  Greedy streams are bit-identical "
             "either way",
    )
    ap.add_argument(
        "--prefix-cache", default="on", choices=("on", "off"),
        help="with --kv-block-size: share full prompt-prefix blocks across "
             "requests (shared system prompts prefill once); 'off' keeps "
             "the block pool a plain allocator",
    )
    ap.add_argument(
        "--spec-decode", type=int, default=0, metavar="K",
        help="speculative decoding: a cheap refit KAN drafter proposes K "
             "tokens per round and the target verifies all K+1 positions "
             "in one batched forward; greedy streams stay bit-identical "
             "(requires --kan-ffn and --kv-block-size); 0 = off",
    )
    ap.add_argument(
        "--draft-spec", default=None, metavar="SPEC",
        help="with --spec-decode: the drafter's deployment point, e.g. "
             "'grid=4,order=2,bits=6,backend=ref' (any subset of keys; "
             "defaults: half the target grid, same order/bits, engine "
             "backend)",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=None, metavar="TOKENS",
        help="with --kv-block-size: prefill long prompts this many tokens "
             "per scheduling round, interleaved with pooled decode, so one "
             "long prompt can't stall TTFT for the pool; default prefills "
             "whole prompts at admission",
    )
    ap.add_argument(
        "--metrics-port", type=int, default=None, metavar="PORT",
        help="enable the obs metrics registry and serve Prometheus text at "
             "http://127.0.0.1:PORT/metrics (JSON at /metrics.json); 0 "
             "picks an ephemeral port",
    )
    ap.add_argument(
        "--metrics-dump", action="append", default=None, metavar="PATH",
        help="enable the obs metrics registry and write a snapshot at "
             "shutdown: '.json' suffix -> JSON snapshot, anything else -> "
             "Prometheus text exposition; repeatable",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="record one span tree per request and export it at shutdown: "
             "'.json' suffix -> Chrome trace-event JSON (chrome://tracing), "
             "anything else (e.g. '.jsonl') -> JSONL span records",
    )
    ap.add_argument(
        "--profile-dir", default=None, metavar="DIR",
        help="capture a jax.profiler trace of the served run into DIR with "
             "profiler annotations on, and write the scheduler's spans "
             "beside the capture's .xplane.pb (spans.trace.json, Chrome "
             "trace-event JSON) shifted onto the profiler's clock",
    )
    ap.add_argument(
        "--log-level", default=None,
        choices=("debug", "info", "warning", "error"),
        help="structured-logger threshold (sets REPRO_LOG_LEVEL for this "
             "process); debug shows per-request scheduler chatter",
    )
    ap.add_argument(
        "--stats-interval", type=float, default=None, metavar="S",
        help="emit a one-line scheduler stats summary every S seconds "
             "through the structured logger",
    )
    args = ap.parse_args()

    if args.log_level:
        os.environ[obs.ENV_LOG_LEVEL_VAR] = args.log_level
    if args.metrics_port is not None or args.metrics_dump:
        obs.enable()
    log = obs.get_logger("serve")

    cfg = smoke_config(args.arch)
    # bit-allocation precedence: artifact > --kan-bits CLI > config default
    if args.kan_bits:
        bits = tuple(int(b) for b in args.kan_bits.split(","))
        if len(bits) == 1:
            cfg = dataclasses.replace(cfg, kan_n_bits=bits[0],
                                      kan_layer_bits=())
        else:
            cfg = dataclasses.replace(cfg, kan_layer_bits=bits)
    tuned_note = ""
    if args.tuned_config:
        from ..tune import apply_tuning_artifact, load_tuning_artifact

        art = load_tuning_artifact(args.tuned_config)
        resolved = apply_tuning_artifact(art)
        cand = resolved["candidate"]
        if cand is not None:
            # the chosen co-design point becomes the KAN-FFN quantization
            # (including its per-layer mixed-precision allocation, which
            # overrides any --kan-bits request)
            cfg = dataclasses.replace(
                cfg, kan_grid=cand.grid_size, kan_order=cand.order,
                kan_n_bits=cand.n_bits, kan_layer_bits=cand.layer_bits,
            )
        tuned_note = (
            f" [artifact {args.tuned_config}: task={art.get('task')}, "
            f"seed={art.get('seed')}, space={art.get('space_hash')}, "
            f"tile mode={None if not art.get('tile_plan') else art['tile_plan'].get('mode')}]"
        )
    if args.kan_ffn:
        cfg = cfg.kan_variant()
        # fail fast on a PowerGap-invalid bit allocation (reject, not clamp)
        from ..core.asp_quant import resolve_layer_bits

        try:
            resolve_layer_bits(cfg.kan_layer_bits or cfg.kan_n_bits, 2,
                               cfg.kan_grid)
        except ValueError as e:
            raise SystemExit(f"invalid KAN bit allocation: {e}")
    if cfg.family in ("audio",):
        raise SystemExit("serve demo supports decoder-only archs")
    params = init_params(jax.random.PRNGKey(0), cfg)
    # --kan-ffn serves the paper's datapath: FFN blocks are ASP-quantized at
    # startup and every prefill/decode step resolves its executor through
    # repro.runtime (interpret mode auto-selected off-TPU); --backend acim
    # additionally injects the measured RRAM-ACIM non-idealities.
    mesh = None
    if args.mesh:
        from .mesh import parse_mesh_spec

        mesh = parse_mesh_spec(args.mesh)
    if args.prefill_chunk is not None and args.kv_block_size is None:
        raise SystemExit("--prefill-chunk requires --kv-block-size")
    if args.spec_decode:
        if not args.kan_ffn:
            raise SystemExit("--spec-decode requires --kan-ffn (the drafter "
                             "is refit from the deployed KAN-FFN weights)")
        if args.kv_block_size is None:
            raise SystemExit("--spec-decode requires --kv-block-size "
                             "(draft rollback releases pool blocks)")
    engine = ServeEngine(params, cfg, slots=args.slots, max_len=128,
                         kan_deploy=args.kan_ffn, kan_backend=args.backend,
                         attn_backend=args.attn_backend, mesh=mesh,
                         kv_block_size=args.kv_block_size,
                         prefix_cache=args.prefix_cache == "on",
                         prefill_chunk=args.prefill_chunk,
                         spec_decode=args.spec_decode,
                         draft_spec=args.draft_spec)
    if engine.draft is not None:
        d = engine.draft.describe()
        log.info("spec decode", k=engine.spec_k, draft_grid=d["kan_grid"],
                 draft_order=d["kan_order"], draft_bits=d["kan_n_bits"],
                 draft_backend=d["kan_backend"] or "inherit")
    if engine.paged:
        kv = engine.kv_stats()
        log.info("paged kv", blocks=kv["num_blocks"],
                 block_size=kv["block_size"],
                 prefix_cache="on" if kv["prefix_cache"] else "off",
                 prefill_chunk=kv["prefill_chunk"] or "whole-prompt")
    log.info("attention backend", backend=engine.attn_backend,
             fused_decode=engine.attn_backend == "flash" and args.kan_ffn)
    if mesh is not None:
        layout = engine.mesh_layout()
        log.info("mesh",
                 shape=" x ".join(f"{a}={s}" for a, s in
                                  zip(layout["axes"], layout["shape"])),
                 devices=f"{layout['devices']}/{len(jax.devices())}",
                 slots=("sharded" if layout["slots_sharded"]
                        else "replicated"))
    if args.kan_ffn:
        log.info("kan-ffn", G=cfg.kan_grid, K=cfg.kan_order,
                 n_bits=cfg.kan_n_bits,
                 layer_bits=("uniform" if not cfg.kan_layer_bits
                             else ",".join(map(str, cfg.kan_layer_bits))),
                 plan_source=engine.kan_plan_source() + tuned_note)

    metrics_server = None
    if args.metrics_port is not None:
        metrics_server = obs.start_metrics_server(args.metrics_port)
        log.info("metrics server",
                 url=f"http://127.0.0.1:{metrics_server.server_port}/metrics")

    sampling = None
    if args.sampling > 0.0:
        sampling = SamplingParams(temperature=args.sampling, top_k=args.top_k,
                                  top_p=args.top_p, seed=args.seed)
        log.info("sampling", temperature=sampling.temperature,
                 top_k=sampling.top_k, top_p=sampling.top_p,
                 seed=sampling.seed)

    rng = jax.random.PRNGKey(1)
    reqs = []
    for rid in range(args.requests):
        rng, k = jax.random.split(rng)
        plen = int(4 + jax.random.randint(k, (), 0, 9))  # mixed-length stream
        rng, k = jax.random.split(rng)
        prompt = jax.random.randint(k, (plen,), 3, cfg.vocab_size).tolist()
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new_tokens=args.max_new,
                            deadline_s=args.deadline, sampling=sampling))

    sched = Scheduler(engine, max_queue=args.queue_limit,
                      log=None if args.stream else print,
                      trace=args.trace_out is not None
                      or args.profile_dir is not None,
                      stats_interval_s=args.stats_interval)
    on_token = None
    if args.stream:
        on_token = lambda r, tok: print(f"  req {r.rid} += {tok}", flush=True)
    dropped = 0
    for r in reqs:
        try:
            sched.submit(r, on_token=on_token)
        except QueueFull as e:
            dropped += 1
            log.warning("backpressure", detail=str(e))
    if args.profile_dir:
        obs.enable_profiler_annotations()
        jax.profiler.start_trace(args.profile_dir, create_perfetto_trace=True)
    t0 = time.perf_counter()
    results = sched.run_until_idle()
    wall = time.perf_counter() - t0
    if args.profile_dir:
        jax.profiler.stop_trace()
        write_profiler_spans(sched.tracer, args.profile_dir, log)
    served = [r for r in results if r.status == "done"]
    total = sum(len(r.output) for r in served)
    stats = engine.compile_stats()
    log.info("served", requests=len(served), tokens=total,
             tokens_per_s=round(total / wall, 1), rejected=dropped)
    log.info("compiles", prefill=stats["prefill_traces"],
             decode=stats["decode_traces"], verify=stats["verify_traces"],
             kan_plan_cache=stats["plan_cache"])
    # shutdown metrics summary (the docs/serving.md glossary)
    s = sched.stats()

    def _ms(v):
        return "n/a" if v is None else f"{v * 1e3:.1f}ms"

    log.info("scheduler", submitted=s["submitted"], completed=s["completed"],
             expired=s["expired"], rejected=s["rejected"])
    ttft = s["ttft_s"] or {"p50": None, "p95": None}
    log.info("latency", ttft_p50=_ms(ttft["p50"]), ttft_p95=_ms(ttft["p95"]),
             itl_p50=_ms(s["itl_s"]["p50"]), itl_p95=_ms(s["itl_s"]["p95"]),
             tokens_per_s=round(s["tokens_per_s"] or 0.0, 1))
    log.info("queue depth", max=s["queue_depth"]["max"],
             mean=round(s["queue_depth"]["mean"], 2),
             rounds=s["queue_depth"]["rounds"])
    if s["kv"] is not None:
        kv = s["kv"]
        log.info("kv pool", hit_rate=round(kv["prefix_hit_rate"], 2),
                 hits=kv["prefix_hits"], misses=kv["prefix_misses"],
                 in_use=kv["blocks_in_use"], cached=kv["blocks_cached"],
                 free=kv["blocks_free"], evictions=kv["evictions"],
                 truncations=kv["truncations"])
    if s["spec"] is not None:
        sp = s["spec"]
        log.info("spec decode", k=sp["k"], rounds=sp["rounds"],
                 drafted=sp["drafted"], accepted=sp["accepted"],
                 accept_rate=(round(sp["accept_rate"], 3)
                              if sp["accept_rate"] is not None else None),
                 draft_p50=_ms(sp["draft_s"]["p50"]),
                 verify_p50=_ms(sp["verify_s"]["p50"]),
                 tokens_per_round=(round(s["tokens_per_round"], 2)
                                   if s["tokens_per_round"] is not None
                                   else None))
    if mesh is not None:
        from .. import runtime

        for fp, reasons in runtime.shard_notes().items():
            for r in reasons:
                log.warning("shard fallback", reason=r)

    if args.trace_out:
        if args.trace_out.endswith(".json"):
            sched.tracer.export_chrome(args.trace_out)
        else:
            sched.tracer.export_jsonl(args.trace_out)
        log.info("trace written", path=args.trace_out,
                 records=len(sched.tracer.records()))
    for path in args.metrics_dump or ():
        obs.dump_metrics(path)
        log.info("metrics dump written", path=path)
    if metrics_server is not None:
        metrics_server.shutdown()


def write_profiler_spans(tracer, profile_dir: str, log) -> None:
    """Fit the tracer's clock to the capture under ``profile_dir`` and
    write its spans there, shifted onto the profiler's clock."""
    import glob

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise SystemExit(f"no .xplane.pb under {profile_dir}")
    fit = tracer.profiler_offset(obs.anchored_events(paths[-1]))
    if fit is None:
        raise SystemExit(f"{paths[-1]}: no span of this run is anchored")
    out = os.path.join(os.path.dirname(paths[-1]), "spans.trace.json")
    tracer.export_chrome(out, offset_s=fit.offset_s)
    log.info("profiler spans written", path=out, anchored=fit.n,
             offset_s=round(fit.offset_s, 9),
             spread_us=round(fit.spread_s * 1e6, 1))


if __name__ == "__main__":
    main()
