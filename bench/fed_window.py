"""The measured window of a federated cell: one continuous
``simulator.run_sync`` / ``run_async`` call whose ``eval_fn`` hook
(``eval_every=1``) sees every server update.

The first ``check_updates`` updates are the steps the correctness check
follows: the hook reads each update's loss from the simulator's history
and the norms of the global model's change. After ``warm_updates`` more
(every program has compiled by then) the window opens; from then on the
hook blocks on the new global, timestamps the update, and ends the call
once ``--seconds`` have passed. Kickoff and compiles stay in set-up.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np


class WindowDone(Exception):
    """Raised from the hook to end the simulator call."""


class FedWindow:
    def __init__(self, ctx, pool, params0, check_updates: int,
                 warm_updates: int):
        self.ctx, self.pool, self.params0 = ctx, pool, params0
        self.check_updates = check_updates
        self.start_update = check_updates + warm_updates
        self.losses: list = []
        self.update_norms = self.change_norms = None
        self.t0 = None
        self.first = None             # the update at which the window opened
        self.times: list = []
        self.window_losses: list = []
        self._between = None          # the span open between two hooks

    def __call__(self, t, _virtual_now, params):
        import correct as cmp
        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None
        # the simulator's own record of this update: (time, epoch, loss)
        loss = sys._getframe(1).f_locals["history"][-1][2]
        self.pool.update = t          # what is drawn next feeds update t+1
        if self.t0 is None:
            if t <= self.check_updates:
                self.losses.append(loss)
                if t == 1:
                    self.update_norms = np.asarray(
                        cmp.diff_norms(params, self.params0))
                if t == self.check_updates:
                    self.change_norms = np.asarray(
                        cmp.diff_norms(params, self.params0))
                    self.params0 = None
            if t == self.start_update:
                jax.block_until_ready(params)
                self.t0 = self.ctx.window_start()
                self.first = t
                self._open_between()
            return
        with self.ctx.span("bench.hook"):
            jax.block_until_ready(params)
        now = time.perf_counter()
        self.times.append(now)
        self.window_losses.append(loss)
        if now - self.t0 >= self.ctx.seconds:
            self.ctx.window_end()
            raise WindowDone
        self._open_between()

    def _open_between(self):
        """The program's own host work between two updates (the
        simulator loop, stacking and padding, dispatch) is traced as
        ``bench.program``; draws from the pool nest inside it."""
        self._between = self.ctx.span("bench.program")
        self._between.__enter__()

    def drive(self, call):
        try:
            call()
        except WindowDone:
            return
        raise RuntimeError("the simulator ended before the window closed")

    def prog_readings(self) -> dict:
        return {"loss": self.losses, "update": self.update_norms,
                "change": self.change_norms}

    def results(self, batch: int) -> dict:
        """End-to-end numbers of the window: clips of every executed
        (unpadded) client step drawn after the window opened, over the
        window; the real time per update."""
        steps = sum(1 for u, _, _ in self.pool.log if u >= self.first)
        window_s = self.times[-1] - self.t0
        dts = np.diff([self.t0] + self.times)
        failed = int(np.sum(~np.isfinite(self.window_losses)))
        return {"clips": steps * batch, "window_s": window_s,
                "updates": len(self.times), "failed": failed,
                "p90_ms": float(np.percentile(dts, 90) * 1e3),
                "median_ms": float(np.median(dts) * 1e3),
                "max_ms": float(np.max(dts) * 1e3)}


def fleet_setup(ctx):
    """The program's model, FedConfig and Fleet for this cell, with the
    clip pool feeding each client ``H^k`` batches per visit."""
    from repro.core.fleet import DeviceProfile, Fleet
    from repro.types import FedConfig
    cfg, tr = ctx.cfg, ctx.traffic
    mc = ctx.model_cfg("student")
    profiles = [DeviceProfile(n, e, s) for n, e, s in cfg["fleet"]["profiles"]]
    profiles = profiles * tr["clients_per_profile"]
    fed = FedConfig(num_clients=len(profiles), global_epochs=10 ** 9,
                    seed=ctx.seed, **cfg["fed"])
    pool = ctx.pool()
    placeholder = Fleet.from_lists(profiles, [None] * len(profiles))
    iters = [placeholder.iters(k, fed) for k in range(len(profiles))]
    data = [pool.client_data(k, h) for k, h in enumerate(iters)]
    return mc, fed, Fleet.from_lists(profiles, data), pool, iters


def visits(pool, upto: int):
    """Per client, its visits among the draws feeding updates 1..upto:
    {client: [[rows of batch 1, ...], ...]} in draw order. A visit is a
    run of consecutive draws by one client that feed one update."""
    out: dict = {}
    last = None
    for u, k, rows in pool.log:
        if u >= upto:
            break
        if (u, k) != last:
            out.setdefault(k, []).append([])
            last = (u, k)
        out[k][-1].append(rows)
    return out
