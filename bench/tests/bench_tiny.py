"""A checkout of the benchmark at a size the CPU holds: the same files and
entries, with the widths, lengths and rates cut down."""

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
SEED = 2**31 + 77


def tiny_lm_conf() -> dict:
    conf = json.loads((BENCH / "configs" / "qwen2.5-14b-kanffn.json"
                       ).read_text())
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, vocab_size=512, num_hidden_layers=2)
    conf["kan_ffn"]["hidden"] = 16
    # 4 query heads in 2 groups, padded to 6 slots: the loader's head
    # placement is exercised as at full size
    conf["program"]["overrides"] = dict(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        vocab_size=512, kan_d_hidden=16, head_pad_multiple=3,
        kv_pad_multiple=0, d_ff=176, remat=False)
    # the file's own limit holds here too: at this size sound runs read
    # 0.0-0.016, the 4-bit control 0.59-1.57, and a decode step that keeps
    # its KV pool 0.25-0.29, the least of the faults (CPU readings)
    return conf


def tiny_traffic(name: str) -> dict:
    t = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    if name == "batch":
        t.update(rows_per_call=512, lead_s=0.3, check_stride=2)
        return t
    t.update(lead_s=0.5, slots=4, max_len=256, prefill_chunk=32)
    t["prompt_tokens"].update(min=8, max=100)
    t["output_tokens"].update(min=2, max=12)
    if name == "chat":
        t["rate_per_s"] = 6.0
        t["prompt_tokens"]["median"] = 40
        t["output_tokens"]["median"] = 6
    else:
        t["queue_min"] = 8
    return t


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """BENCHMARK.json and bench/{configs,traffic,metrics,harness} under
    ``tmp``; the harness is this one, linked, so that a test that patches
    its modules patches what the run loads."""
    base = tmp / "bench"
    shutil.copytree(BENCH / "metrics", base / "metrics")
    (base / "harness").symlink_to(BENCH / "harness", target_is_directory=True)
    (base / "configs").mkdir()
    (base / "traffic").mkdir()
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    (base / "configs" / "qwen2.5-14b-kanffn.json").write_text(
        json.dumps(tiny_lm_conf()))
    shutil.copy(BENCH / "configs" / "kan2-knot.json",
                base / "configs" / "kan2-knot.json")
    for name in ("chat", "longdoc", "batch"):
        (base / "traffic" / f"{name}.json").write_text(
            json.dumps(tiny_traffic(name)))
    return tmp


def run_cell(root, cell: str, seconds: float = 1.5, trace: int = 0,
             seed: int = SEED) -> dict:
    """Drive ``run.main`` past its look for a chip; returns the result."""
    import contextlib
    import io

    import jax
    import repro.launch.compile_cache as cache

    import run

    run.accelerators = lambda chips: jax.devices()[:chips]
    cache.use_compile_cache = lambda: "off (test)"
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)], root=root)
    return json.loads(out.getvalue().strip().splitlines()[-1])
