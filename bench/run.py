#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``, and the runner of the configuration's ``kind`` as
``harness/<kind>.py`` (see ``harness/common.py``).  The run refuses to start
unless JAX's first device is an accelerator and there are as many as the
cell asks for; it makes weights and inputs from ``--seed``, warms up the
cell's programs (``setup_s``), measures ``--seconds`` of load, checks what
the timed path produced against the plain reference, and prints one JSON
line: the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics (from a device trace of part of the window) with ``--trace 1``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import os  # noqa: E402

# libtpu would log under /tmp; a run writes only inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]

from harness import common  # noqa: E402
from harness.common import log  # noqa: E402

TRACE_SECONDS = 6.0   # how much of a traced run's window is recorded
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def accelerators(chips: int):
    """The devices, or exit without a result where there is no accelerator
    or too few."""
    import jax

    devices = jax.devices()
    log(f"devices: platform {devices[0].platform}, kind "
        f"{devices[0].device_kind!r}, count {len(devices)}")
    if devices[0].platform not in ("tpu", "gpu"):
        sys.exit(f"bench: JAX's first device is {devices[0].platform!r}, "
                 "not an accelerator; refusing to measure")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Backend compiles seen while ``armed`` (the measured window)."""

    def __init__(self):
        import jax

        self.armed = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == COMPILE_EVENT:
            self.n += 1


def use_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed place,
    keeping every program so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return use_compile_cache()


def wanted(entries: list, cell: str) -> list:
    """Metric entries that apply to the cell."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def main(argv=None, root: pathlib.Path = REPO) -> int:
    """One run.  ``root`` is the checkout that holds ``BENCHMARK.json`` and
    the benchmark's files (tests point it at a throwaway tree)."""
    args = parse(argv)
    base = root / BENCH.name
    bench, cell, conf, traffic = common.load_cell(args.workload, root)
    runner = common.runner(conf["kind"], base)
    devices = accelerators(int(cell["chips"]))

    log(f"compile cache: {use_cache()}")
    counter = CompileCounter()
    profiler = None
    if args.trace:
        from harness.trace import Profiler
        from repro.obs.trace import enable_profiler_annotations

        enable_profiler_annotations()
        profiler = Profiler(str(root / ".bench_trace" / args.workload),
                            min(TRACE_SECONDS, args.seconds))

    out = runner.run(conf, traffic, args, devices, counter, profiler)
    setup_s = out["setup_end"] - T0
    out["e2e"]["setup_s"] = out["info"]["setup_s"] = setup_s

    metrics = {}
    for m in wanted(bench["per_layer"] if args.trace else bench["end_to_end"],
                    args.workload):
        if args.trace:
            v = common.metric_reader(m["name"], base)(out["ctx"])
        else:
            v = out["e2e"].get(m["name"])
        if v is None:
            log(f"metric {m['name']}: nothing to read")
        else:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = out["checks"]
    checks.add("compiles_in_window", counter.n, 0)
    device = common.device_info(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    breakdown = None
    if args.trace:
        red = out["ctx"]["trace"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.device_ops,
                     "idle_gaps": red.idle_gaps}
    for k, v in out["info"].items():
        log(f"info {k}: {v}")
    checks.print()
    print(common.result_line(
        correct=checks.ok, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, checks=checks, breakdown=breakdown),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
