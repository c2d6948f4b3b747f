"""A cell on several chips: the program's weights are made on the cell's
mesh in the layout the engine serves, and the reference's are spread over
the chips, with the same numbers as on one chip.  Four host devices, in a
process of their own (``mesh_probe.py``), since their count is fixed when
JAX starts."""

import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(BENCH / "tests")]

import pytest  # noqa: E402

import bench_tiny as B  # noqa: E402

PROBE = BENCH / "tests" / "mesh_probe.py"


@pytest.fixture(scope="module")
def probe():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"{flags} --xla_force_host_platform_device_count=4").strip())
    r = subprocess.run([sys.executable, str(PROBE)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_one_chip_tree_is_the_single_jit_makers(probe):
    assert probe["one_chip_is_old"]
    assert probe["one_chip_on_first"]


def test_mesh_tree_equals_the_one_chip_tree(probe):
    assert probe["devices"] == 4
    assert probe["mesh"] == {"data": 2, "model": 2}
    assert probe["mesh_equals_one_chip"]


def test_mesh_tree_is_placed_as_the_engine_serves_it(probe):
    """Every leaf has the sharding of ``param_pspecs``, so the engine's own
    placement moves nothing, and a chip holds about half of the tree
    (model=2), not all of it."""
    assert probe["shardings_are_param_pspecs"]
    assert probe["engine_put_moves_nothing"]
    assert probe["first_chip_share"] < 0.6


def test_reference_on_the_mesh_reads_the_same_logits(probe):
    assert probe["reference_weights_equal"]
    assert probe["reference_first_chip_share"] < 0.3
    assert probe["reference_logits_equal"]
    assert probe["reference_logits_on_first"]


def test_a_kind_with_no_runner_exits_naming_it(tmp_path):
    import run

    root = B.make_root(tmp_path)
    path = root / "bench" / "configs" / "qwen2.5-14b-kanffn.json"
    conf = json.loads(path.read_text())
    path.write_text(json.dumps(dict(conf, kind="nosuch")))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen2.5-14b-kanffn.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], root=root)
    assert "'nosuch'" in str(e.value)
    assert str(root / "bench" / "harness" / "nosuch.py") in str(e.value)
