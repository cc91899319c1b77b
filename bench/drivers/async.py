"""Driver of the ``async`` kind: the paper's Algorithm 1 through the
program's ``core.simulator.run_async`` (scan engine, ``window`` from the
traffic; 0 is event by event). Each receive mixes one client's update into
the global and hands that client the new global: one client program of
H^k steps at the traffic's batch, then one mix.

The correctness check replays the virtual-clock schedule from the Jetson
profiles and follows the first ``check_updates`` receives, which take in
the batched kickoff and the per-H^k client programs of the window.
"""
from __future__ import annotations

import numpy as np


def run(ctx):
    from repro.core import simulator
    import correct as cmp
    import flops
    from fed_window import FedWindow, fleet_setup, visits

    tr = ctx.traffic
    mc, fed, fleet, pool, iters = fleet_setup(ctx)
    params0 = ctx.weights(["student"])["student"]
    win = FedWindow(ctx, pool, params0, tr["check_updates"],
                    tr["warm_updates"])
    win.drive(lambda: simulator.run_async(
        params0, mc, fed, fleet, eval_fn=win, eval_every=1,
        engine="scan", window=tr["window"]))
    del params0
    res = win.results(tr["batch"])
    prog = win.prog_readings()
    n_check = tr["check_updates"]
    profiles = [(e, 0.0) for _, e, _ in ctx.cfg["fleet"]["profiles"]] \
        * tr["clients_per_profile"]

    def check():
        from reference import fed as ref
        import jax
        w0 = ctx.weights(["student"])["student"]
        per_client = visits(pool, n_check)
        rows = [r.start for v in per_client.values() for vis in v
                for r in vis]
        if len(set(rows)) != len(rows):
            raise RuntimeError("the checked receives reuse pool rows")

        def batches_for(k):
            return [pool.batch_of(r) for r in per_client[k].pop(0)]

        losses, models, _ = ref.async_receives(
            w0, profiles, batches_for, n_check, ctx.cfg["fed"],
            jax.lax.Precision.HIGHEST)
        refr = {"loss": losses,
                "update": np.asarray(cmp.diff_norms(models[0], w0)),
                "change": np.asarray(cmp.diff_norms(models[-1], w0))}
        return cmp.gaps(prog, refr)

    return {
        "e2e": {"client_clips_per_s": res["clips"] / res["window_s"],
                "update_ms_p90": res["p90_ms"]},
        "attempted": res["updates"], "failed": res["failed"],
        "layer": {"clips": res["clips"],
                  "flops": res["clips"] * flops.train_flops_per_clip(ctx.cfg)},
        "counts": {**res, "iters": iters},
        "check": check,
    }
