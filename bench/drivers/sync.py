"""Driver of the ``sync`` kind: synchronous FedProx rounds through the
program's ``core.simulator.run_sync`` with the traffic's ``engine``
(``scan``: every client of a round in one vmapped, padded program;
``shard``: the same round split over the chips' ('clients',) mesh).

Each client k is fed H^k batches per round (``Fleet.iters``, slower
devices fewer), so a round pads to H_max and executes more clip-steps than
it uses; only the used ones count. The correctness check follows the
first ``check_updates`` rounds.
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
    win.drive(lambda: simulator.run_sync(
        params0, mc, fed, fleet, eval_fn=win, eval_every=1,
        engine=tr["engine"]))
    del params0
    res = win.results(tr["batch"])
    prog = win.prog_readings()
    n_check = tr["check_updates"]

    def check():
        from reference import fed as ref
        import jax
        w0 = ctx.weights(["student"])["student"]
        per_client = visits(pool, n_check)
        rows = [r.start for v in per_client.values() for vis in v
                for r in vis]
        if len(set(rows)) != len(rows):
            raise RuntimeError("the checked rounds reuse pool rows")
        rounds = [[[pool.batch_of(r) for r in per_client[k][i]]
                   for k in range(len(iters))] for i in range(n_check)]
        losses, models = ref.sync_rounds(w0, rounds, ctx.cfg["fed"],
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
