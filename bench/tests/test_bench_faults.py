"""A whole run past the look for a chip, with the timed path broken
underneath: ``correct`` must come out false for each fault the cell can
have.  (One chip: there is no exchange between chips to leave out.)"""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(BENCH / "tests")]

import pytest  # noqa: E402

import bench_tiny as B  # noqa: E402
from harness import faults, kan, lm  # noqa: E402

CHAT = "qwen2.5-14b-kanffn.chat"
BATCH = "kan2-knot.batch"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return B.make_root(tmp_path_factory.mktemp("checkout"))


def test_sound_runs_are_correct(root):
    assert B.run_cell(root, CHAT)["correct"]
    res = B.run_cell(root, BATCH)
    assert res["correct"] and res["metrics"]["rows_per_s"]["value"] > 0


def _break_engine(monkeypatch, fault):
    build = lm.build_engine

    def broken(*args, **kw):
        engine = build(*args, **kw)
        fault(engine)
        return engine

    monkeypatch.setattr(lm, "build_engine", broken)


@pytest.mark.parametrize("fault", list(faults.LM.values()))
def test_lm_faults_are_caught(root, monkeypatch, fault):
    _break_engine(monkeypatch, fault)
    res = B.run_cell(root, CHAT)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", list(faults.KAN.values()))
def test_batch_faults_are_caught(root, monkeypatch, fault):
    call = kan.call
    monkeypatch.setattr(kan, "call", lambda dep, x: fault(call(dep, x)))
    res = B.run_cell(root, BATCH)
    assert not res["correct"], res["checks"]
