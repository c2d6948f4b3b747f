"""Device trace capture and its reduction to busy time, kernel time and
idle gaps named by what the host was doing.

A traced run records ``jax.profiler`` for part of its window into
``<checkout>/.bench_trace/<workload>``, reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData`` and reduces it here:

* device events: the op events of each accelerator plane (the line that
  carries XLA ops); busy time is the union of their intervals inside the
  traced window, averaged over the chips;
* kernel time: the summed durations of op events whose own name (not
  its operands') contains a stable kernel name (``kan_pipeline``,
  ``flash_attention``);
* program time: module events (one per executed XLA program) whose name
  contains a jit name (``_decode_paged``, ``_prefill_chunk_fn``);
* idle gaps: the complement of busy time, each named by the innermost host
  annotation open at its midpoint (``serve.decode_step``, ``bench.round``,
  ...), else "host: none".

Only the reduction below decides these numbers; the program contributes
its kernel names, jit names and annotations.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil

OP_LINES = ("XLA Ops", "Ops")
MODULE_LINES = ("XLA Modules", "Modules")
# host annotations that name idle gaps: the benchmark's own and the
# program's profile scopes
HOST_PREFIXES = ("bench.", "serve.", "kan_spline.")
# ops whose event spans the ops nested in it on the same line (a loop of
# layers): left out of the op breakdown, which would count time twice
CONTAINER_OPS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: float    # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Events of one profile: per device plane, its ops and modules; and
    the host annotations of every host thread."""

    ops: dict          # plane name -> [Event]
    modules: dict      # plane name -> [Event]
    host: list         # [Event] host annotations and calls


def is_device_plane(name: str) -> bool:
    return bool(re.match(r"^/device:(TPU|GPU):\d+$", name))


def is_op_line(plane: str, line: str) -> bool:
    return is_device_plane(plane) and line in OP_LINES


def is_module_line(plane: str, line: str) -> bool:
    return is_device_plane(plane) and line in MODULE_LINES


def op_name(text: str) -> str:
    """The instruction's own name.  A TPU op event carries the whole
    instruction (``%kan_pipeline.18 = f32[..] custom-call(.. %copy.9 ..)``),
    in which the names of its operands appear too."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, op_line=is_op_line, module_line=is_module_line,
         op_event=lambda name: True) -> Trace:
    """Read a ``.xplane.pb``.  ``op_line(plane, line)`` picks the lines of
    device op events (``op_event(name)`` filters them), ``module_line``
    those of program events; host annotations come from every host line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        for line in plane.lines:
            if op_line(plane.name, line.name):
                named = ((op_name(e.name), e) for e in line.events)
                ops.setdefault(plane.name, []).extend(
                    Event(n, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for n, e in named if op_event(n))
            elif module_line(plane.name, line.name):
                modules.setdefault(plane.name, []).extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend(Event(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9) for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return Trace(ops, modules, host)


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, w0: float, w1: float) -> list:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def window_of(tr: Trace, marker: str) -> tuple:
    """The traced window: from the start of the first host annotation
    named ``marker`` to the end of the last one."""
    ev = [e for e in tr.host if e.name == marker]
    if not ev:
        raise ValueError(f"no host annotation {marker!r} in the trace")
    return min(e.start for e in ev), max(e.end for e in ev)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over device planes
    kernel_s: dict                # name -> seconds (summed over chips)
    kernel_calls: dict            # name -> events
    program_s: dict               # jit name -> seconds (summed over chips)
    program_calls: dict
    device_ops: list              # [[name, seconds]] top 10
    idle_gaps: list               # [[host name, seconds]] top 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_family(name: str) -> str:
    """An op's name without its numeric suffixes (``fusion.12`` ->
    ``fusion``), so repeated ops of one kind add up."""
    return re.sub(r"(\.\d+)+$", "", name)


def reduce(tr: Trace, w0: float, w1: float, kernels=(), programs=()) -> Reduced:
    planes = sorted(tr.ops)
    if not planes:
        raise ValueError("no device op events in the trace")
    busy_total = 0.0
    busy_first = None
    kernel_s = collections.Counter()
    kernel_calls = collections.Counter()
    fam = collections.Counter()
    for p in planes:
        evs = [e for e in tr.ops[p] if e.end > w0 and e.start < w1]
        busy = union(clip(((e.start, e.end) for e in evs), w0, w1))
        busy_total += sum(e - s for s, e in busy)
        if busy_first is None:
            busy_first = busy
        for e in evs:
            d = min(e.end, w1) - max(e.start, w0)
            if op_family(e.name) not in CONTAINER_OPS:
                fam[op_family(e.name)] += d
            for k in kernels:
                if k in e.name:
                    kernel_s[k] += d
                    kernel_calls[k] += 1
    program_s = collections.Counter()
    program_calls = collections.Counter()
    for p, evs in tr.modules.items():
        for e in evs:
            if e.end <= w0 or e.start >= w1:
                continue
            for k in programs:
                if k in e.name:
                    program_s[k] += min(e.end, w1) - max(e.start, w0)
                    program_calls[k] += 1
    gaps = idle_gaps(busy_first or [], w0, w1, tr.host)
    return Reduced(
        window_s=w1 - w0, busy_s=busy_total / len(planes),
        kernel_s=dict(kernel_s), kernel_calls=dict(kernel_calls),
        program_s=dict(program_s), program_calls=dict(program_calls),
        device_ops=[[n, s] for n, s in fam.most_common(10)],
        idle_gaps=gaps)


def idle_gaps(busy: list, w0: float, w1: float, host: list,
              top: int = 10) -> list:
    """Idle time of one device, summed by the innermost host annotation
    open at each gap's midpoint (annotations nest, so that is the open
    one that started last)."""
    import bisect

    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    spans = sorted((e.start, e.end, e.name) for e in host
                   if e.end > w0 and e.start < w1)
    starts = [s for s, _, _ in spans]
    by = collections.Counter()
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        name = "none"
        hi = bisect.bisect_right(starts, mid) - 1
        for i in range(hi, max(-1, hi - 256), -1):
            if spans[i][1] >= mid:
                name = spans[i][2]
                break
        by["host: " + name] += e - s
    return [[n, t] for n, t in by.most_common(top)]


class Profiler:
    """Start/stop ``jax.profiler`` around part of a window and read the
    result back; ``annotate`` marks host work in the same trace."""

    MARK = "bench.window"

    def __init__(self, out_dir: str, seconds: float):
        self.out_dir = out_dir
        self.seconds = seconds
        self._mark = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        # annotations only: a Python tracer would record every call of
        # the serving loop and slow the host it is meant to observe
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._mark = self.annotate(self.MARK)
        self._mark.__enter__()

    def stop(self) -> None:
        import jax

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @staticmethod
    def annotate(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def path(self) -> str:
        hits = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                         recursive=True)
        if len(hits) != 1:
            raise RuntimeError(f"expected one trace under {self.out_dir}, "
                               f"found {hits}")
        return hits[0]

    def reduce(self, kernels=(), programs=()) -> Reduced:
        tr = load(self.path())
        w0, w1 = window_of(tr, self.MARK)
        return reduce(tr, w0, w1, kernels=kernels, programs=programs)
