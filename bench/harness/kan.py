"""KAN network cells: offline batches through ``runtime.execute``.

Set-up makes the float weights and a few distinct input batches on the
device from the seed, quantizes and deploys them with the program
(``quantize_kan_network`` -> ``deploy_kan_network``) and compiles the
``pallas`` plan for the call's batch.  The window issues calls back to
back, keeping ``in_flight`` of them queued, so the host never waits on an
idle device; a call's rows count when its result is ready inside the
window.  A seeded sample of calls keeps its outputs for the reference.
"""

from __future__ import annotations

import collections
import time

from . import common
from .common import Checks, jax_key, np_rng

KEEP = 6          # sampled calls whose outputs are checked
KERNEL = "kan_pipeline"


def run(conf, traffic, args, devices, counter, profiler) -> dict:
    """One run of a KAN network cell (see ``run.py``): set-up, the window,
    then the reference over the sampled calls."""
    info = {}
    t = time.perf_counter()
    dep, xs = setup(conf, traffic, args.seed)
    info["init_quantize_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(dep, xs)
    info["warmup_s"] = time.perf_counter() - t
    setup_end = time.perf_counter()
    counter.armed = True
    win = serve(dep, xs, traffic, args.seed, args.seconds, profiler=profiler)
    counter.armed = False
    mem = common.peak_bytes(devices)
    e2e = metrics(win, traffic)
    info.update(e2e)
    ctx = None
    if args.trace:
        from roofline import peaks

        ctx = {"kind": "kan", "conf": conf, "traffic": traffic,
               "trace": profiler.reduce(kernels=(KERNEL,)),
               "peaks": peaks(devices[0].device_kind)}
    del dep
    checks, detail = check(conf, traffic, win, xs, args.seed,
                           traffic.get("correct", conf["correct"])
                           ["max_row_err"])
    info.update(detail)
    return {"e2e": e2e, "ctx": ctx, "checks": checks,
            "attempted": win.done_in_window, "failed": 0,
            "memory_peak_bytes": mem, "info": info, "setup_end": setup_end}


def program_spec(conf: dict):
    from repro.core.kan_layer import KANSpec

    return KANSpec(dims=tuple(conf["dims"]), grid_size=conf["grid"],
                   order=conf["order"], n_bits=conf["bits"],
                   lut_bits=conf["lut_bits"], lo=conf["lo"], hi=conf["hi"])


def setup(conf: dict, traffic: dict, seed: int):
    """(deployed bundle, input batches) for the cell."""
    import jax

    from repro.core.kan_network_deploy import (deploy_kan_network,
                                               quantize_kan_network)

    from reference.kan import make_inputs, make_weights

    rows = traffic["rows_per_call"]
    weights = jax.jit(lambda k: make_weights(conf, k))(jax_key(seed, 1))
    xs = jax.jit(lambda k: make_inputs(conf, k, traffic["distinct_batches"],
                                       rows))(jax_key(seed, 2))
    kspec = program_spec(conf)
    dep = deploy_kan_network(quantize_kan_network(weights, kspec), kspec,
                             batch=rows)
    jax.block_until_ready((dep.layers, xs))
    return dep, xs


def call(dep, x):
    from repro import runtime

    return runtime.execute(dep, x, backend="pallas")


def warm_up(dep, xs) -> None:
    call(dep, xs[0]).block_until_ready()
    call(dep, xs[1 % xs.shape[0]]).block_until_ready()


class Window:
    def __init__(self):
        self.w0 = self.w1 = 0.0
        self.done_in_window = 0      # calls whose result was ready inside
        self.kept: dict = {}         # call index -> output (device)


def serve(dep, xs, traffic: dict, seed: int, seconds: float,
          profiler=None) -> Window:
    """Back-to-back calls for ``lead_s`` + ``seconds``; the window is the
    last ``seconds``."""
    win = Window()
    in_flight = int(traffic["in_flight"])
    nb = xs.shape[0]
    stride = int(traffic.get("check_stride", 16))
    phase = int(np_rng(seed, 4).integers(stride))
    kept = collections.OrderedDict()
    pending = collections.deque()
    t_start = time.perf_counter()
    win.w0 = t_start + float(traffic.get("lead_s", 0.0))
    win.w1 = win.w0 + seconds
    traced, trace_stop = False, None
    i = 0
    while True:
        while len(pending) < in_flight:
            pending.append((i, call(dep, xs[i % nb])))
            i += 1
        j, y = pending.popleft()
        y.block_until_ready()
        t = time.perf_counter()
        if t >= win.w1:
            break
        if t >= win.w0:
            win.done_in_window += 1
            if j % stride == phase:
                kept[j] = y
                while len(kept) > KEEP:
                    kept.popitem(last=False)
            if profiler is not None and not traced:
                profiler.start()
                traced, trace_stop = True, t + profiler.seconds
        if trace_stop is not None and t >= trace_stop:
            for _, yy in pending:
                yy.block_until_ready()
            profiler.stop()
            trace_stop = None
    for _, yy in pending:
        yy.block_until_ready()
    if trace_stop is not None:
        profiler.stop()
    win.kept = dict(kept)
    return win


def check(conf: dict, traffic: dict, win: Window, xs, seed: int,
          limit: float) -> tuple:
    """Every row of the sampled calls against the reference."""
    import jax
    import jax.numpy as jnp

    from reference.kan import make_weights, row_errors

    checks = Checks()
    if not win.kept:
        checks.add("sampled_calls", 0, 1, ok=False)
        return checks, {}
    weights = jax.jit(lambda k: make_weights(conf, k))(jax_key(seed, 1))
    worst, multi, rows = 0.0, 0, 0
    for j, y in sorted(win.kept.items()):
        x = xs[j % xs.shape[0]]
        err, ambiguous = row_errors(y, conf, weights, x)
        err = jnp.where(ambiguous, 0.0, err)
        worst = max(worst, float(jnp.max(err)))
        multi += int(ambiguous.sum())
        rows += int(y.shape[0])
    checks.add("max_row_err", worst, limit)
    return checks, {"sampled_calls": len(win.kept), "rows_checked": rows,
                    "rows_left_out": multi}


def metrics(win: Window, traffic: dict) -> dict:
    rows = win.done_in_window * traffic["rows_per_call"]
    return {"rows_per_s": rows / (win.w1 - win.w0),
            "calls_in_window": win.done_in_window}
