"""Cell lookup by name, seeds, window statistics and the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` under the benchmark directory, and the runner of
the configuration's ``kind`` as ``harness/<kind>.py``, so a new cell,
traffic mix, per-layer metric or kind of configuration is a new file and
a new entry, never an edit of an existing file.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import re
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def log(*parts) -> None:
    """Progress lines go to stderr; stdout carries only the result line."""
    print(*parts, file=sys.stderr, flush=True)


# --- finding things by name --------------------------------------------------


def load_benchmark(root: pathlib.Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_entry(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload named {workload!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no configuration named {name!r} in BENCHMARK.json")


def read_data(path: pathlib.Path) -> dict:
    """A traffic or configuration file (JSON)."""
    return json.loads(path.read_text())


def find_data(kind: str, name: str, base: pathlib.Path = BENCH) -> pathlib.Path:
    """``<base>/<kind>/<name>.json``."""
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no {kind} file named {name!r} under {base / kind}")
    return path


def load_cell(workload: str, root: pathlib.Path = REPO) -> tuple:
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic),
    all found by name under ``root``."""
    bench = load_benchmark(root)
    cell = cell_entry(bench, workload)
    conf = read_data(root / config_entry(bench, cell["config"])["file"])
    traffic = read_data(find_data("traffic", cell["traffic"],
                                  root / BENCH.name))
    return bench, cell, conf, traffic


def load_module(path: pathlib.Path, name: str | None = None):
    """The module in ``path``, registered under ``name`` before it runs (a
    dataclass looks its module up while it is being defined)."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, base: pathlib.Path = BENCH):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return load_module(base / "metrics" / f"{name}.py").read


def runner(kind: str, base: pathlib.Path = BENCH):
    """``harness/<kind>.py``, whose ``run(conf, traffic, args, devices,
    counter, profiler)`` runs a cell of that kind of configuration.  It
    is loaded as a module of this package (its relative imports find the
    harness), once: a module already loaded from the same file is reused."""
    path = base / "harness" / f"{kind}.py"
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", kind) or not path.is_file():
        raise SystemExit(f"no runner for configuration kind {kind!r}: "
                         f"{path} is not a file")
    name = f"{__package__}.{kind}"
    mod = sys.modules.get(name)
    if mod is None or pathlib.Path(mod.__file__).resolve() != path.resolve():
        mod = load_module(path, name)
    if not callable(getattr(mod, "run", None)):
        raise SystemExit(f"{path} has no run() for configuration kind "
                         f"{kind!r}")
    return mod


# --- seeds -------------------------------------------------------------------


def seed_words(seed: int, *tags: int) -> tuple:
    """Two 31-bit words from any whole-number seed (and optional tags):
    ``--seed`` may exceed what 32 signed bits hold."""
    import numpy as np

    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), *tags])
    a, b = ss.generate_state(2)
    return int(a) & 0x7FFFFFFF, int(b) & 0x7FFFFFFF


def np_rng(seed: int, *tags: int):
    import numpy as np

    return np.random.default_rng([int(seed) & (2**64 - 1), *tags])


def jax_key(seed: int, *tags: int):
    import jax

    a, b = seed_words(seed, *tags)
    return jax.random.fold_in(jax.random.PRNGKey(a), b)


# --- window statistics -------------------------------------------------------


def nearest_rank(xs, q: float) -> float | None:
    """The q-quantile by nearest rank: the smallest sample with at least a
    share q of the samples at or below it (None for no samples)."""
    if not xs:
        return None
    s = sorted(xs)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def beyond(n: int, q: float) -> int:
    """How many of n samples lie above the nearest-rank q-quantile."""
    return n - max(0, math.ceil(q * n))


def ttft_samples(due: dict, first: dict, w0: float, w1: float,
                 end: float | None = None) -> list:
    """Time to first token of every request due in [w0, w1), from its due
    time.  A request with no first token by ``end`` (the window's close,
    by default w1) enters at (end - due)."""
    end = w1 if end is None else end
    out = []
    for rid, t_due in due.items():
        if not (w0 <= t_due < w1):
            continue
        t_first = first.get(rid)
        out.append((t_first if t_first is not None and t_first <= end
                    else end) - t_due)
    return out


def gaps_in_window(token_times: dict, w0: float, w1: float) -> list:
    """Every gap between consecutive emitted tokens of one request whose
    later token falls in [w0, w1), over all requests."""
    out = []
    for times in token_times.values():
        for a, b in zip(times, times[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out


def count_in_window(times, w0: float, w1: float) -> int:
    return sum(1 for t in times if w0 <= t < w1)


# --- the result ----------------------------------------------------------------


class Checks:
    """Numbers compared against their limits; every one is printed beside
    its limit, and the run is correct only if all hold."""

    def __init__(self):
        self.items: list = []

    def add(self, name: str, value, limit, ok: bool | None = None) -> bool:
        """A number that must not exceed its limit (or an explicit
        verdict ``ok``)."""
        if ok is None:
            ok = value is not None and value <= limit
        self.items.append((name, value, limit, bool(ok)))
        return bool(ok)

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(ok for *_, ok in self.items)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim, "ok": ok}
                for n, v, lim, ok in self.items}

    def print(self) -> None:
        for n, v, lim, ok in self.items:
            log(f"check {n}: {v!r} limit {lim!r} {'ok' if ok else 'FAILED'}")


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    statistics, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: Checks, breakdown: dict | None = None
                ) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks.as_dict()
    return json.dumps(out)
