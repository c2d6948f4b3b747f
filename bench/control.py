#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, in one process: the
program's number on many seeds, and on a few of them the control's and
each planted fault's.

    python3 bench/control.py --workload qwen2.5-14b-kanffn.chat \
        --seeds 11,12,13 --control-seeds 11,12,13 \
        --faults state_unchanged --seconds 10

For each seed it makes the weights, serves the cell's own load for
``--seconds`` (after the cell's lead-in) through the timed path and scores
what it produced against the plain reference, as a run does.  For the
control seeds it also reads the control: for LM cells the program's own
4-bit KAN-FFN path teacher-forced over the same prompts and served tokens;
for KAN network cells the reference at 4-bit weights in the program's
place.  Each fault named in ``--faults`` (see ``harness/faults.py``) is
planted in a set-up of its own and read the same way on the control
seeds.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys

import jax

import run
from harness import common, faults, kan, lm


def lm_reading(conf, traffic, seed, seconds, chips, fault=None):
    """(cfg, mesh, window, the widest gap, detail) of one served window."""
    engine, cfg, _ = lm.setup(conf, traffic, seed, chips)
    mesh = engine.mesh
    if fault:
        faults.LM[fault](engine)
    win = lm.serve(engine, cfg, traffic, seed, seconds)
    win.sched = None
    engine.params = engine.cache = None
    del engine
    gc.collect()
    checks, detail = lm.check_served(conf, win, seed, traffic["max_len"],
                                     math.inf, mesh)
    name, value = checks.items[0][:2]
    return (cfg, mesh, win, value if name == lm.GAP_LIMIT_KEY else None,
            detail)


def lm_seed(conf, traffic, seed, seconds, chips, control, fault_names):
    cfg, mesh, win, gap, detail = lm_reading(conf, traffic, seed, seconds,
                                             chips)
    row = {"seed": seed, "program": gap, **detail}
    if control and gap is not None:
        row["control"], extra = lm.control_gap(conf, cfg, traffic, win, seed,
                                               mesh=mesh)
        row.update(extra)
        for name in fault_names:
            gc.collect()
            row[f"fault.{name}"] = lm_reading(conf, traffic, seed, seconds,
                                              chips, name)[3]
    return row


def kan_reading(conf, traffic, seed, seconds, fault=None):
    dep, xs = kan.setup(conf, traffic, seed)
    call = kan.call
    if fault:
        kan.call = lambda d, x: faults.KAN[fault](call(d, x))
    try:
        kan.warm_up(dep, xs)
        win = kan.serve(dep, xs, traffic, seed, seconds)
    finally:
        kan.call = call
    checks, detail = kan.check(conf, traffic, win, xs, seed, math.inf)
    name, value = checks.items[0][:2]
    return win, xs, value if name == "max_row_err" else None, detail


def kan_seed(conf, traffic, seed, seconds, chips, control, fault_names):
    import jax.numpy as jnp

    from reference.kan import make_weights, outputs, row_errors

    win, xs, err, detail = kan_reading(conf, traffic, seed, seconds)
    row = {"seed": seed, "program": err, **detail}
    if control and err is not None:
        w = jax.jit(lambda k: make_weights(conf, k))(common.jax_key(seed, 1))
        worst = 0.0
        for j in sorted(win.kept):
            x = xs[j % xs.shape[0]]
            y4, _, _ = outputs(conf, w, x, weight_bits=4)
            e, amb = row_errors(y4, conf, w, x)
            worst = max(worst, float(jnp.max(jnp.where(amb, 0.0, e))))
        row["control"] = worst
        for name in fault_names:
            row[f"fault.{name}"] = kan_reading(conf, traffic, seed, seconds,
                                               name)[2]
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    _, cell, conf, traffic = common.load_cell(args.workload, run.REPO)
    chips = len(run.accelerators(int(cell["chips"])))
    run.use_cache()
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    fault_names = [f for f in args.faults.split(",") if f]
    one = {"lm": lm_seed, "kan": kan_seed}[conf["kind"]]
    for s in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(one(conf, traffic, s, args.seconds, chips,
                             s in ctrl, fault_names)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
