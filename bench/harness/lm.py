"""LM cells: the program's serving path under open- or closed-loop load.

Set-up makes the weights on the cell's chips from the seed (on a mesh,
each leaf where the program's sharding rules serve it), builds
``ServeEngine(kan_deploy=True, attn_backend="flash")`` on the paged KV pool
with the cell's slots, and warms up exactly the programs the traffic will
use (every prefill-chunk bucket and the decode step) through a Scheduler.
The window then drives a fresh ``Scheduler`` over the same engine; each
emitted token is timed on the host clock through ``on_token``.  After the
window the engine is freed, the weights are made again from the seed, and
the plain reference scores a seeded sample of the finished requests.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from . import traffic as T
from . import common
from .common import Checks, jax_key, nearest_rank, np_rng

# The widest gap, in logits, by which a served token may lie below the
# reference's best token at its position (see PERF.md, "correct").
GAP_LIMIT_KEY = "max_logit_gap"
# stable names the trace reduction keys on: the program's kernels and the
# jitted serving steps
KAN_KERNEL, ATTN_KERNEL = "kan_pipeline", "flash_attention"
DECODE_PROGRAM, PREFILL_PROGRAM = "_decode_paged", "_prefill_chunk_fn"
SAMPLE_TOKENS = 512       # served tokens the reference scores, at least,
SAMPLE_MIN_REQUESTS = 4   # over at least this many requests,
SAMPLE_MAX_REQUESTS = 12  # and at most this many


# --- one run ---------------------------------------------------------------------


def run(conf, traffic, args, devices, counter, profiler) -> dict:
    """One run of an LM cell (see ``run.py``): set-up, the window, then the
    reference over a sample of what it served."""
    engine, cfg, info = setup(conf, traffic, args.seed, len(devices))
    setup_end = time.perf_counter()
    mesh = engine.mesh

    counter.armed = True
    win = serve(engine, cfg, traffic, args.seed, args.seconds,
                trace=bool(args.trace), profiler=profiler)
    counter.armed = False
    info["programs_after_window"] = counts(engine)
    mem = common.peak_bytes(devices)
    e2e = window_metrics(win, traffic)
    for k in ("window_s", "due_in_window", "finished", "output_tokens",
              "prompt_tokens", "ttft_p90_ms", "itl_p95_ms", "tokens_per_s"):
        info[k] = e2e[k]
    info["ttft_samples_beyond_p90"] = common.beyond(len(e2e["ttft_s"]), 0.9)
    ctx = None
    if args.trace:
        ctx = context(conf, traffic, win, profiler, devices)
    # free the program's state before the reference runs (its weights and
    # KV pool go even if a reference cycle keeps the engine object alive)
    sched_requests = win.requests
    win.sched = None
    engine.params = engine.cache = None
    del engine
    gc.collect()
    limit = traffic.get("correct", conf["correct"])[GAP_LIMIT_KEY]
    checks, detail = check_served(conf, win, args.seed, traffic["max_len"],
                                  limit, mesh)
    info.update(detail)
    # open loop: the requests due in the window; closed loop: those that
    # emitted a token inside it
    attempted = e2e["due_in_window"] if traffic["kind"] == "open_loop" else \
        sum(1 for ts in win.times.values()
            if any(win.w0 <= t < win.w1 for t in ts))
    failed = sum(1 for r in sched_requests.values()
                 if r.status in ("expired", "rejected"))
    return {"e2e": e2e, "ctx": ctx, "checks": checks, "attempted": attempted,
            "failed": failed, "memory_peak_bytes": mem, "info": info,
            "setup_end": setup_end}


def context(conf, traffic, win, profiler, devices) -> dict:
    """What the per-layer metric readers of an LM cell read."""
    from roofline import peaks

    red = profiler.reduce(kernels=(KAN_KERNEL, ATTN_KERNEL),
                          programs=(DECODE_PROGRAM, PREFILL_PROGRAM))
    t0, t1 = win.trace_span
    return {"kind": "lm", "conf": conf, "traffic": traffic, "trace": red,
            "peaks": peaks(devices[0].device_kind),
            "steps": [s for s in win.steps if t0 <= s[1] < t1],
            "spans": win.sched_records, "due": win.due,
            "window": win.due_window(), "end": win.w1}


# --- configuration -------------------------------------------------------------


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file, refusing any
    width that differs from the file."""
    from repro.configs.registry import get_config
    from repro.models.layers import kan_ffn_hidden

    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    k = conf["kan_ffn"]
    want = (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["vocab_size"], conf["num_hidden_layers"],
            float(conf["rope_theta"]), bool(conf["attention_bias"]),
            k["hidden"], k["grid"], k["order"], k["bits"],
            conf["torch_dtype"])
    got = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
           cfg.vocab_size, cfg.num_layers, float(cfg.rope_theta),
           bool(cfg.qkv_bias), kan_ffn_hidden(cfg), cfg.kan_grid,
           cfg.kan_order, cfg.kan_n_bits, cfg.dtype)
    if want != got:
        raise SystemExit(f"program config {got} differs from the file {want}")
    if cfg.ffn_kind != "kan" or cfg.kan_layer_bits:
        raise SystemExit("the configuration states a uniform KAN-FFN")
    return cfg


def cell_mesh(traffic: dict, chips: int):
    """The mesh a cell serves on: none on one chip; on several,
    ``engine.mesh`` of the traffic file, else every chip on the model axis."""
    if chips == 1:
        return None
    from repro.launch.mesh import parse_mesh_spec

    return parse_mesh_spec(traffic.get("engine", {}).get("mesh",
                                                         f"model={chips}"))


def make_program_params(conf: dict, cfg, seed: int, mesh=None):
    """The program's parameter tree, made on the device in one call.  On a
    mesh each leaf is made where the program's own sharding rules
    (``param_pspecs``) put it, so no chip holds the whole tree; the values
    do not depend on the mesh."""
    import jax

    from repro.models.model import init_params

    from .lm_weights import canonical, program

    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    shardings = None
    if mesh is not None:
        from repro.dist.sharding import param_pspecs, to_shardings

        shardings = to_shardings(param_pspecs(want, mesh), mesh)
    fn = jax.jit(lambda key: program(conf, canonical(conf, key),
                                     cfg.phys_heads), out_shardings=shardings)
    params = fn(jax_key(seed, 1))
    got_s = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want_s = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got_s != want_s:
        raise SystemExit(f"weights do not fit the program's tree:\n{got_s}\n"
                         f"{want_s}")
    jax.block_until_ready(params)
    return params


def build_engine(params, cfg, traffic: dict, mesh=None):
    """The program's ``ServeEngine`` with the mix's slots and pool, and the
    options that the traffic file's ``engine`` entry names, serving on
    ``mesh`` (see ``cell_mesh``)."""
    from repro.serve.engine import ServeEngine

    opts = dict(traffic.get("engine", {}))
    opts.pop("mesh", None)
    return ServeEngine(params, cfg, slots=traffic["slots"],
                       max_len=traffic["max_len"],
                       kv_block_size=traffic["kv_block_size"],
                       prefill_chunk=traffic["prefill_chunk"], mesh=mesh,
                       **opts)


def setup(conf: dict, traffic: dict, seed: int, chips: int = 1) -> tuple:
    """(engine, cfg, timings): the weights made on the cell's chips from the
    seed, the engine built on them (the KAN-FFN quantized), and every
    program the cell's traffic uses warmed up."""
    info = {}
    cfg = program_config(conf)
    mesh = cell_mesh(traffic, chips)
    t = time.perf_counter()
    params = make_program_params(conf, cfg, seed, mesh)
    info["init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = build_engine(params, cfg, traffic, mesh)
    del params
    info["quantize_s"] = time.perf_counter() - t
    t = time.perf_counter()
    info["programs_after_warmup"] = warm_up(engine, cfg, traffic, seed)
    info["warmup_s"] = time.perf_counter() - t
    return engine, cfg, info


# --- warm-up -------------------------------------------------------------------


def chunk_buckets(traffic: dict) -> list:
    """Every prefill-chunk length the mix can produce, as the engine pads
    it (powers of two from 8 up to the chunk)."""
    from repro.runtime import bucket_batch

    lo = traffic["prompt_tokens"]["min"]
    hi = traffic["prompt_tokens"]["max"]
    chunk = traffic["prefill_chunk"]
    takes = {min(chunk, n - s) for n in range(lo, hi + 1)
             for s in range(0, n, chunk)}
    return sorted({bucket_batch(t) for t in takes})


def warm_up(engine, cfg, traffic: dict, seed: int) -> dict:
    """Serve one short request per chunk bucket, two tokens each, so every
    program the window uses is compiled (or read from the cache) here."""
    from repro.serve.engine import Request
    from repro.serve.scheduler import Scheduler

    chunk = traffic["prefill_chunk"]
    sched = Scheduler(engine)
    rng = np_rng(seed, 3)
    for i, b in enumerate(chunk_buckets(traffic)):
        n = chunk + b if chunk + b <= traffic["prompt_tokens"]["max"] else b
        sched.submit(Request(rid=-1 - i,
                             prompt=rng.integers(3, cfg.vocab_size, n).tolist(),
                             max_new_tokens=2, eos_id=-1))
    sched.run_until_idle()
    return counts(engine)


def counts(engine) -> dict:
    s = engine.compile_stats()
    return {"prefill": s["prefill_traces"], "decode": s["decode_traces"],
            "kan_traces": s["plan_cache"]["traces"],
            "kan_misses": s["plan_cache"]["misses"]}


# --- the window ----------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """What one measured window saw, on the scheduler's clock."""

    w0: float = 0.0
    w1: float = 0.0
    due: dict = dataclasses.field(default_factory=dict)       # rid -> due
    first: dict = dataclasses.field(default_factory=dict)     # rid -> t
    times: dict = dataclasses.field(default_factory=dict)     # rid -> [t]
    requests: dict = dataclasses.field(default_factory=dict)  # rid -> Request
    prefilled0: int = 0
    prefilled1: int = 0
    steps: list = dataclasses.field(default_factory=list)     # traced calls
    sched: object = None
    sched_records: list = dataclasses.field(default_factory=list)
    trace_span: tuple | None = None
    # open loop: the planned span [lead, lead + seconds) whose due requests
    # are the window's; it holds whole blocks of the plan, where the
    # measured window opens up to a round late
    due_span: tuple | None = None

    def due_window(self) -> tuple:
        """The span whose due requests the window's tails cover."""
        return self.due_span or (self.w0, self.w1)


def prefilled_tokens(engine, win: Window) -> int:
    """Prompt tokens prefilled so far over every request of the window."""
    mid = {st["req"].rid: st["next"] for st in engine._prefilling.values()}
    total = 0
    for rid, req in win.requests.items():
        if rid in win.first:
            total += len(req.prompt)
        else:
            total += mid.get(rid, 0)
    return total


def _sync(engine) -> None:
    import jax

    jax.block_until_ready(engine.cache)


def instrument(engine, win: Window, clock) -> None:
    """Record every decode step and prefill chunk the window dispatches
    (the shapes the roofline counts), around the engine's own calls."""
    decode, prefill = engine.decode_active, engine._prefill_step

    def decode_active(tokens):
        live = [int(engine.pos[i]) + 1 for i, r in enumerate(engine.active)
                if r is not None]
        win.steps.append(("decode", clock(), len(tokens), live))
        return decode(tokens)

    def prefill_step(slot):
        st = engine._prefilling[slot]
        start, plen = st["next"], len(st["req"].prompt)
        take = min(plen - start, engine.prefill_chunk or plen)
        win.steps.append(("prefill", clock(), start, take, plen))
        return prefill(slot)

    engine.decode_active = decode_active
    engine._prefill_step = prefill_step


def serve(engine, cfg, traffic: dict, seed: int, seconds: float, *,
          trace: bool = False, profiler=None) -> Window:
    """Run the mix against ``engine``: load starts ``lead_s`` before the
    window, and the window closes ``seconds`` later at a round boundary."""
    from repro.serve.engine import Request
    from repro.serve.scheduler import Scheduler

    win = Window()
    sched = Scheduler(engine, trace=trace)
    win.sched = sched
    lead = float(traffic.get("lead_s", 0.0))
    closed = traffic["kind"] == "closed_loop"
    if trace:
        instrument(engine, win, sched.elapsed)

    def on_token(req, tok):
        t = sched.elapsed()
        win.times.setdefault(req.rid, []).append(t)
        if req.rid not in win.first:
            win.first[req.rid] = t

    planned_iter = iter(())
    queue_min = int(traffic.get("queue_min", 0))

    def submit(p):
        req = Request(rid=p.rid,
                      prompt=T.prompt_tokens(traffic, seed, p, cfg.vocab_size),
                      max_new_tokens=p.output_len, eos_id=-1,
                      arrival_s=0.0 if closed else p.arrival_s)
        win.requests[p.rid] = req
        win.due[p.rid] = max(req.arrival_s, sched.elapsed())
        sched.submit(req, on_token=on_token)

    if closed:
        # the queue is kept at least queue_min deep: blocks are planned as
        # they are needed, in order, from the same seed
        block = int(traffic.get("block", 32))

        def planned():
            b = 0
            while True:
                yield from T.plan(traffic, seed, (b + 1) * block)[b * block:]
                b += 1

        planned_iter = planned()
        for _ in range(queue_min):
            submit(next(planned_iter))
    else:
        for p in T.plan(traffic, seed, T.requests_needed(traffic, seconds)):
            submit(p)
        win.due_span = (lead, lead + seconds)

    started = False
    trace_stop = None
    while True:
        now = sched.elapsed()
        if not started and now >= lead:
            _sync(engine)
            win.w0 = sched.elapsed()
            win.prefilled0 = prefilled_tokens(engine, win)
            started = True
            if profiler is not None:
                profiler.start()
                trace_stop = win.w0 + profiler.seconds
                win.trace_span = (win.w0, None)
        if trace_stop is not None and now >= trace_stop:
            _sync(engine)
            profiler.stop()
            win.trace_span = (win.trace_span[0], sched.elapsed())
            trace_stop = None
        if started and now >= win.w0 + seconds:
            break
        if closed:
            while len(sched.queue) < queue_min:
                submit(next(planned_iter))
        with _annotate(profiler, "bench.round"):
            progressed = sched.step()
        if not progressed:
            nxt = min((r.arrival_s for r in sched.queue), default=now + 0.01)
            target = min(nxt, lead if not started else win.w0 + seconds)
            with _annotate(profiler, "bench.wait_for_arrival"):
                time.sleep(max(0.0, min(target - sched.elapsed(), 0.05)))
    _sync(engine)
    win.w1 = sched.elapsed()
    win.prefilled1 = prefilled_tokens(engine, win)
    if trace_stop is not None:
        profiler.stop()
        win.trace_span = (win.trace_span[0], win.w1)
    if sched.tracer is not None:
        win.sched_records = sched.tracer.records()
    return win


class _annotate:
    """A host annotation in the profiler's trace while it records."""

    def __init__(self, profiler, name):
        self.ctx = profiler.annotate(name) if profiler is not None else None

    def __enter__(self):
        if self.ctx is not None:
            self.ctx.__enter__()

    def __exit__(self, *a):
        if self.ctx is not None:
            self.ctx.__exit__(*a)


# --- end-to-end metrics ----------------------------------------------------------


def window_metrics(win: Window, traffic: dict) -> dict:
    """The window's end-to-end numbers (see common.py for the rules)."""
    from .common import count_in_window, gaps_in_window, ttft_samples

    span = win.w1 - win.w0
    ttft = ttft_samples(win.due, win.first, *win.due_window(), win.w1)
    itl = gaps_in_window(win.times, win.w0, win.w1)
    out_tokens = sum(count_in_window(t, win.w0, win.w1)
                     for t in win.times.values())
    prompt_tokens = win.prefilled1 - win.prefilled0
    return {
        "window_s": span,
        "ttft_s": ttft,
        "itl_s": itl,
        "ttft_p90_ms": None if not ttft else 1e3 * nearest_rank(ttft, 0.90),
        "itl_p95_ms": None if not itl else 1e3 * nearest_rank(itl, 0.95),
        "tokens_per_s": (out_tokens + prompt_tokens) / span,
        "output_tokens": out_tokens,
        "prompt_tokens": prompt_tokens,
        "due_in_window": len(ttft),
        "finished": sum(1 for r in win.requests.values() if r.done),
    }


# --- correctness -----------------------------------------------------------------


def sample_finished(win: Window, seed: int) -> list:
    """A seeded sample of finished requests, the one with the most served
    tokens always in it, until SAMPLE_TOKENS served tokens and
    SAMPLE_MIN_REQUESTS requests are covered."""
    done = sorted((r for r in win.requests.values() if r.done),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.output), -r.rid))
    rest = [r for r in done if r is not longest]
    order = np_rng(seed, 5).permutation(len(rest))
    out, n = [longest], len(longest.output)
    for i in order:
        if (n >= SAMPLE_TOKENS and len(out) >= SAMPLE_MIN_REQUESTS
                or len(out) >= SAMPLE_MAX_REQUESTS):
            break
        out.append(rest[i])
        n += len(rest[i].output)
    return out


def reference_for(conf: dict, seed: int, max_len: int, bits=None,
                  mesh=None):
    """The plain reference over weights made again from the seed; on a
    mesh the weights are made spread over its chips (``reference.lm.spread``),
    with the same values."""
    import jax

    from reference.lm import Reference, spread

    from .lm_weights import canonical

    make = lambda k: canonical(conf, k)
    shardings = None
    if mesh is not None:
        shardings = spread(jax.eval_shape(make, jax_key(seed, 1)), mesh)
    w = jax.jit(make, out_shardings=shardings)(jax_key(seed, 1))
    return Reference(conf, w, max_len, bits=bits)


def logit_gaps(ref, req_tokens: list) -> list:
    """For each (prompt, served tokens): the gap between the reference's
    best logit and its logit of each served token, in logits."""
    import jax.numpy as jnp

    gaps = []
    for prompt, served in req_tokens:
        seq = list(prompt) + list(served[:-1])
        pos = list(range(len(prompt) - 1, len(seq)))
        lg = ref.logits(seq, pos)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], 1)[:, 0]
        gaps.append(np.asarray(best - got, np.float64))
    return gaps


def check_served(conf: dict, win: Window, seed: int, max_len: int,
                 limit: float, mesh=None) -> tuple:
    """Score the sample against the reference; returns (Checks, detail)."""
    sample = sample_finished(win, seed)
    checks = Checks()
    if not sample:
        checks.add("finished_requests", 0, 1, ok=False)
        return checks, {}
    t0 = time.perf_counter()
    ref = reference_for(conf, seed, max_len, mesh=mesh)
    gaps = logit_gaps(ref, [(r.prompt, r.output) for r in sample])
    worst = float(max(g.max() for g in gaps))
    n_tok = int(sum(g.size for g in gaps))
    detail = {"sampled_requests": len(sample), "sampled_tokens": n_tok,
              "reference_s": time.perf_counter() - t0,
              "gap_p50": float(np.median(np.concatenate(gaps)))}
    checks.add(GAP_LIMIT_KEY, worst, limit)
    return checks, detail


def control_gap(conf: dict, cfg, traffic: dict, win: Window, seed: int,
                bits: int = 4, mesh=None) -> tuple:
    """The control: the program's own lower-precision KAN-FFN path
    (``kan_n_bits=bits``), teacher-forced over the sampled requests'
    prompts and served tokens; returns (the widest gap of the tokens it
    puts first, detail)."""
    sample = sample_finished(win, seed)
    low = dataclasses.replace(cfg, kan_n_bits=bits)
    engine = build_engine(make_program_params(conf, cfg, seed, mesh), low,
                          traffic, mesh)
    picks = []
    for r in sample:
        rows = engine.teacher_forced_logits(r.prompt, r.output[:-1])
        picks.append((r.prompt, r.output, np.argmax(rows, axis=-1).tolist()))
    engine.params = engine.cache = None
    del engine
    ref = reference_for(conf, seed, traffic["max_len"], mesh=mesh)
    gaps = logit_gaps(ref, [(p, c) for p, _, c in picks])
    agree = float(np.mean(np.concatenate(
        [np.equal(o, c) for _, o, c in picks])))
    return float(max(g.max() for g in gaps)), {
        "control_tokens": int(sum(g.size for g in gaps)),
        "control_agreement": agree}
