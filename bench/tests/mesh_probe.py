"""Weights made on a data=2,model=2 mesh of four host devices against
weights made on one, at the tiny size; prints one JSON line of readings.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/mesh_probe.py

``test_bench_mesh.py`` runs it in a process of its own, since the count of
host devices is fixed when JAX starts.
"""

import json
import pathlib
import sys
from unittest import mock

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(BENCH / "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import bench_tiny as B  # noqa: E402
from harness import common as C  # noqa: E402
from harness import lm  # noqa: E402
from harness import lm_weights  # noqa: E402
from harness.lm_weights import canonical, program  # noqa: E402

LAYERS = 4   # a layer stack that the four chips divide


def conf4() -> dict:
    conf = B.tiny_lm_conf()
    conf["num_hidden_layers"] = LAYERS
    conf["program"]["overrides"]["num_layers"] = LAYERS
    return conf


def concat_pad_heads(w, axis, hkv, phys):
    """The head placement as first written: each group's heads, then its
    zero slots, concatenated."""
    import jax.numpy as jnp

    g, gp = w.shape[axis] // hkv, phys // hkv
    zeros = jnp.zeros(w.shape[:axis] + (gp - g,) + w.shape[axis + 1:],
                      w.dtype)
    out = []
    for part in jnp.split(w, hkv, axis=axis):
        out += [part, zeros]
    return jnp.concatenate(out, axis=axis)


def same(a, b) -> bool:
    """Leaf for leaf, bit for bit."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(
            np.asarray(x).reshape(-1).view(np.uint8),
            np.asarray(y).reshape(-1).view(np.uint8))
        for x, y in zip(la, lb))


def first_chip_share(tree) -> float:
    """The share of the tree's bytes that the first chip holds."""
    first = jax.devices()[0]
    held = total = 0
    for a in jax.tree.leaves(tree):
        total += a.nbytes
        held += sum(s.data.nbytes for s in a.addressable_shards
                    if s.device == first)
    return held / total


def main() -> None:
    from repro.dist.sharding import param_pspecs, to_shardings

    conf = conf4()
    cfg = lm.program_config(conf)
    mesh = lm.cell_mesh({"engine": {"mesh": "data=2,model=2"}}, 4)
    one = lm.make_program_params(conf, cfg, B.SEED)
    # the maker as it was before it took a mesh: one plain jit, and the
    # head slots placed by concatenation
    with mock.patch.object(lm_weights, "_pad_heads", concat_pad_heads):
        old = jax.jit(lambda k: program(conf, canonical(conf, k),
                                        cfg.phys_heads))(C.jax_key(B.SEED, 1))
    spread = lm.make_program_params(conf, cfg, B.SEED, mesh)
    want = to_shardings(param_pspecs(spread, mesh), mesh)
    placed = jax.device_put(spread, want)   # what ServeEngine does with it
    out = {
        "devices": len(jax.devices()),
        "mesh": dict(zip(mesh.axis_names, map(int, mesh.devices.shape))),
        "one_chip_is_old": same(one, old),
        "one_chip_on_first": all(a.devices() == {jax.devices()[0]}
                                 for a in jax.tree.leaves(one)),
        "mesh_equals_one_chip": same(spread, one),
        "shardings_are_param_pspecs": all(
            a.sharding.is_equivalent_to(s, a.ndim) for a, s in
            zip(jax.tree.leaves(spread), jax.tree.leaves(want))),
        "engine_put_moves_nothing": all(
            a is b for a, b in zip(jax.tree.leaves(spread),
                                   jax.tree.leaves(placed))),
        "first_chip_share": first_chip_share(spread),
    }

    ref_one = lm.reference_for(conf, B.SEED, 256)
    ref_mesh = lm.reference_for(conf, B.SEED, 256, mesh=mesh)
    out["reference_weights_equal"] = same(ref_mesh.w, ref_one.w)
    out["reference_first_chip_share"] = first_chip_share(ref_mesh.w)
    rng = C.np_rng(B.SEED, 9)
    tokens = rng.integers(3, conf["vocab_size"], 200).tolist()
    positions = list(range(0, 200, 7))
    a = ref_one.logits(tokens, positions)
    b = ref_mesh.logits(tokens, positions)
    out["reference_logits_equal"] = same(a, b)
    out["reference_logits_on_first"] = b.devices() == {jax.devices()[0]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
