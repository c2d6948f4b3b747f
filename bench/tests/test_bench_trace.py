"""The trace reduction: busy union, idle share, kernel time by name and
idle gaps named by the host annotation open in them."""

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from harness import trace as T  # noqa: E402


def ev(name, start, dur):
    return T.Event(name, start, dur)


def test_union_and_clip():
    assert T.union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    assert T.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_reduce_by_hand():
    ops = {"/device:TPU:0": [ev("fusion.1", 0.0, 1.0),
                             ev("kan_pipeline.3", 0.5, 1.0),   # overlaps
                             ev("flash_attention", 3.0, 1.0),
                             ev("fusion.2", 6.0, 2.0)]}        # half outside
    modules = {"/device:TPU:0": [ev("jit__decode_paged(1)", 0.0, 1.5),
                                 ev("jit__decode_paged(2)", 3.0, 1.0)]}
    host = [ev("bench.round", 0.0, 4.5), ev("serve.decode_step", 1.6, 1.0),
            ev("bench.wait_for_arrival", 4.5, 2.5)]
    r = T.reduce(T.Trace(ops, modules, host), 0.0, 7.0,
                 kernels=("kan_pipeline", "flash_attention"),
                 programs=("_decode_paged",))
    assert r.window_s == 7.0
    assert r.busy_s == pytest.approx(1.5 + 1.0 + 1.0)
    assert r.idle_share == pytest.approx(1 - 3.5 / 7.0)
    assert r.kernel_s == {"kan_pipeline": 1.0, "flash_attention": 1.0}
    assert r.program_s == {"_decode_paged": 2.5}
    assert r.program_calls == {"_decode_paged": 2}
    gaps = dict(r.idle_gaps)
    # gap 1.5-3.0 (mid 2.25) lies in serve.decode_step, nested in a round;
    # 4.0-6.0 (mid 5.0) in the wait
    assert gaps["host: serve.decode_step"] == pytest.approx(1.5)
    assert gaps["host: bench.wait_for_arrival"] == pytest.approx(2.0)
    assert dict(r.device_ops)["fusion"] == pytest.approx(2.0)


def test_op_names_and_containers():
    """A kernel's time is read from its own events, not from ops that name
    it as an operand; a loop op that spans nested ops is busy time but not
    an entry of the op breakdown."""
    assert T.op_name("%kan_pipeline.18 = f32[256,1280] custom-call("
                     "s32[256,5120] %copy.9)") == "kan_pipeline.18"
    assert T.op_name("%copy.4 = f32[256,1280] copy(f32[256,1280] "
                     "%kan_pipeline.18)") == "copy.4"
    assert T.op_name("fusion.2") == "fusion.2"
    ops = {"/device:TPU:0": [ev("while.12", 0.0, 4.0),
                             ev("kan_pipeline.18", 0.5, 1.0),
                             ev("copy.4", 1.5, 0.5),
                             ev("fusion.3", 3.0, 2.0)]}
    r = T.reduce(T.Trace(ops, {}, []), 0.0, 6.0, kernels=("kan_pipeline",))
    assert r.busy_s == pytest.approx(5.0)
    assert r.kernel_s == {"kan_pipeline": 1.0}
    assert dict(r.device_ops) == {"fusion": 2.0, "kan_pipeline": 1.0,
                                  "copy": 0.5}


@pytest.fixture
def no_compile_cache():
    """Compile in this process: an executable read back from a persistent
    cache runs under other event names."""
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def test_recorded_cpu_profile(tmp_path, no_compile_cache):
    """A profile recorded here: the CPU client's op events stand in for a
    device's, the benchmark's annotations name the gaps."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((200, 200))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with T.Profiler.annotate(T.Profiler.MARK):
        for _ in range(3):
            with T.Profiler.annotate("bench.round"):
                f(x).block_until_ready()
            with T.Profiler.annotate("bench.wait_for_arrival"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = T.Profiler(str(tmp_path), 1.0).path()
    tr = T.load(path, op_line=lambda p, l: l.startswith("tf_XLAPjRtCpuClient"),
                op_event=lambda n: n in ("wrapped_sine", "dot_general.1"))
    w0, w1 = T.window_of(tr, T.Profiler.MARK)
    r = T.reduce(tr, w0, w1, kernels=("dot_general",))
    evs = [e for p in tr.ops.values() for e in p]
    assert sum(e.name == "dot_general.1" for e in evs) == 3
    dots = sum(min(e.end, w1) - max(e.start, w0) for e in evs
               if e.name == "dot_general.1")
    assert r.kernel_s["dot_general"] == pytest.approx(dots)
    assert r.kernel_calls["dot_general"] == 3
    assert 0 < r.busy_s < r.window_s
    assert r.busy_s == pytest.approx(sum(
        e - s for s, e in T.union(T.clip(((e.start, e.end) for e in evs),
                                         w0, w1))))
    gaps = dict(r.idle_gaps)
    assert gaps["host: bench.wait_for_arrival"] >= 0.05
    assert gaps["host: bench.wait_for_arrival"] == max(gaps.values())
