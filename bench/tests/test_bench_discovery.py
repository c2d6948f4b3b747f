"""The harness finds a configuration, a traffic mix and a per-layer metric
by name: adding one is adding files and entries."""

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import run  # noqa: E402
from harness import common as C  # noqa: E402


def test_throwaway_cell_is_found_by_name(tmp_path):
    base = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (base / d).mkdir(parents=True)
    (base / "configs" / "toy-net.json").write_text(json.dumps(
        {"kind": "kan", "dims": [3, 2]}))
    (base / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "batch", "rows_per_call": 64}))
    (base / "metrics" / "toy_share.trickle.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    bench = {"configs": [{"name": "toy-net",
                          "file": "bench/configs/toy-net.json"}],
             "workloads": [{"name": "toy-net.trickle", "config": "toy-net",
                            "traffic": "trickle", "chips": 1}],
             "per_layer": [{"name": "toy_share.trickle",
                            "workloads": ["toy-net.trickle"]},
                           {"name": "other", "workloads": ["x.y"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    b = C.load_benchmark(tmp_path)
    cell = C.cell_entry(b, "toy-net.trickle")
    conf = C.read_data(tmp_path / C.config_entry(b, cell["config"])["file"])
    assert conf == {"kind": "kan", "dims": [3, 2]}
    assert C.read_data(C.find_data("traffic", "trickle", base)) == {
        "kind": "batch", "rows_per_call": 64}
    assert C.load_cell("toy-net.trickle", tmp_path)[2:] == (
        conf, {"kind": "batch", "rows_per_call": 64})
    names = [m["name"] for m in run.wanted(b["per_layer"], cell["name"])]
    assert names == ["toy_share.trickle"]
    read = C.metric_reader("toy_share.trickle", base)
    assert read({"x": 1.5}) == 1.5 and read({}) is None
    with pytest.raises(SystemExit):
        C.find_data("traffic", "missing", base)
    with pytest.raises(SystemExit):
        C.cell_entry(b, "toy-net.nothing")


def test_every_entry_of_the_benchmark_has_its_files():
    b = C.load_benchmark(BENCH.parent)
    for w in b["workloads"]:
        assert (BENCH.parent / C.config_entry(b, w["config"])["file"]).is_file()
        C.find_data("traffic", w["traffic"])
    for m in b["per_layer"]:
        assert callable(C.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


def test_refuses_without_an_accelerator():
    with pytest.raises(SystemExit):
        run.accelerators(1)


TOY_RUNNER = '''
from __future__ import annotations

import dataclasses
import time

from .common import Checks


@dataclasses.dataclass
class Window:          # a dataclass needs its module registered as it loads
    calls: int = 3


def run(conf, traffic, args, devices, counter, profiler):
    checks = Checks()
    checks.add("toy_err", 0.0, conf["limit"])
    return {"e2e": {"toy_rate": 2.0 * traffic["rate"]}, "ctx": None,
            "checks": checks, "attempted": Window().calls, "failed": 0,
            "memory_peak_bytes": 0, "info": {"devices": len(devices)},
            "setup_end": time.perf_counter()}
'''


def test_a_new_kind_is_one_new_runner_file(tmp_path, monkeypatch, capsys):
    """A configuration of a kind the harness has never seen runs from
    ``harness/<kind>.py`` alone, with nothing edited."""
    import jax
    import repro.launch.compile_cache as cache

    base = tmp_path / "bench"
    for d in ("configs", "traffic", "harness"):
        (base / d).mkdir(parents=True)
    (base / "configs" / "toy.json").write_text(json.dumps(
        {"kind": "toy", "limit": 0.5}))
    (base / "traffic" / "steady.json").write_text(json.dumps({"rate": 4}))
    (base / "harness" / "toy.py").write_text(TOY_RUNNER)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.steady", "config": "toy",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": [{"name": "toy_rate", "unit": "1/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    monkeypatch.setattr(run, "accelerators",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(cache, "use_compile_cache", lambda: "off (test)")
    assert run.main(["--workload", "toy.steady", "--seed", str(2**40 + 1),
                     "--seconds", "1", "--trace", "0"], root=tmp_path) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] and res["attempted"] == 3
    assert res["metrics"]["toy_rate"] == {"value": 8.0, "unit": "1/s"}
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res["checks"]) == ["toy_err", "compiles_in_window"]
