#!/usr/bin/env python3
"""Readings of the correctness comparison's control and planted faults,
for setting a cell's limits (``limits/<workload>.json``).

    python3 bench/tools/controls.py --workload <name> --seeds 11 12 13 [--rehearse]

For each seed it replays the cell's checked steps (the same weights, pool
rows and schedule the program gets) three ways and prints the comparison's
numbers of the last two against the first:

- ``reference``: the plain reference, float32 at ``highest`` precision;
- ``control``: the same reference in bfloat16, the precision below the
  configuration's float32;
- ``half_batch``: the reference with half of every batch left out, the
  mean taken over the rest.

A state returned unchanged reads 1 on ``change_gap`` by its definition
and needs no run. Runs on the chip at the cell's own size; ``--rehearse``
runs the tiny CPU size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def readings(ctx, variant: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import correct as cmp

    cfg, tr = ctx.cfg, ctx.traffic
    kind = tr["kind"]
    if variant == "control":
        dtype, prec, rows = jnp.bfloat16, jax.lax.Precision.DEFAULT, None
    else:
        dtype, prec = jnp.float32, jax.lax.Precision.HIGHEST
        rows = tr["batch"] // 2 if variant == "half_batch" else None
    roles = ["teacher", "student"] if kind == "kd" else ["student"]
    w = {k: jax.tree_util.tree_map(lambda x: x.astype(dtype), v)
         for k, v in ctx.weights(roles).items()}
    pool = ctx.pool()
    if kind == "kd":
        from reference import kd as ref
        batches = [pool.draw() for _ in range(tr["epoch_steps"])]
        losses, p, m = ref.follow(w["teacher"], w["student"], batches,
                                  cfg["distill"],
                                  row_block=tr["reference_row_block"],
                                  precision=prec, rows=rows)
        return {"loss": losses, "update": np.asarray(cmp.leaf_norms(m)),
                "change": np.asarray(cmp.diff_norms(p, w["student"]))}
    from reference import fed as ref
    from repro.core.fleet import DeviceProfile, Fleet
    from repro.types import FedConfig
    profiles = [DeviceProfile(*p) for p in cfg["fleet"]["profiles"]] \
        * tr["clients_per_profile"]
    fed = FedConfig(num_clients=len(profiles), **cfg["fed"])
    fl = Fleet.from_lists(profiles, [None] * len(profiles))
    iters = [fl.iters(k, fed) for k in range(len(profiles))]
    n = tr["check_updates"]
    w0 = w["student"]
    if kind == "sync":
        rounds = [[[pool.draw(k) for _ in range(h)]
                   for k, h in enumerate(iters)] for _ in range(n)]
        losses, models = ref.sync_rounds(w0, rounds, cfg["fed"], prec, rows)
    else:
        losses, models, _ = ref.async_receives(
            w0, [(p.epoch_seconds, 0.0) for p in profiles],
            lambda k: [pool.draw(k) for _ in range(iters[k])], n,
            cfg["fed"], prec, rows)
    return {"loss": losses,
            "update": np.asarray(cmp.diff_norms(models[0], w0)),
            "change": np.asarray(cmp.diff_norms(models[-1], w0))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import run as harness
    import correct as cmp

    _, _, cfg, traffic = harness.load_cell(args.workload)
    for seed in args.seeds:
        ctx = harness.Ctx(SimpleNamespace(seed=seed, seconds=0, trace=0,
                                          rehearse=args.rehearse),
                          cfg, traffic, None)
        ref = readings(ctx, "reference")
        row = {"seed": seed}
        for variant in ("control", "half_batch"):
            row[variant] = cmp.gaps(readings(ctx, variant), ref)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
