"""Event-driven simulator of the heterogeneous embedded-device fleet.

The paper's testbed (Table IV/V) is four NVIDIA Jetson device types whose
per-epoch times differ by up to 4.7×. We cannot run Jetsons here, so the
simulator advances a *virtual clock* using the measured per-epoch times
while executing *real* JAX updates on synthetic data. This reproduces both
the learning dynamics (accuracy curves, staleness distribution) and the
wall-clock claims (async ≈ 40% faster than sync, Table II).

Fleets are described by ``core.fleet``: a resident ``Fleet.from_lists``
for small explicit fleets (the paper's four Jetsons), or a streaming
``FleetSpec`` for populations up to 10^6 clients — a sampled client's
profile, loader and H^k materialize on demand and are released when the
client leaves the sampled/in-flight set, so resident state is O(sampled),
never O(population). Per-round subsampling (sync) and a bounded in-flight
set (async) are switched by ``fed.clients_per_round``; see docs/fleet.md.

Device profiles are the paper's measurements; custom fleets are supported.
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import algorithms, fed_engine, fedasync, fedavg
from repro.core.compression import roundtrip
from repro.core.fedasync import ServerState
# DeviceProfile and the Jetson fleets live in core/fleet now; re-exported
# here so existing imports keep working.
from repro.core.fleet import (ASYNC_ENGINES, SYNC_ENGINES, DeviceProfile,
                              EngineSpec, Fleet, FleetSpec,
                              JETSON_FLEET_HMDB51, JETSON_FLEET_UCF101)
from repro.data.synthetic import stack_batches
from repro.optim import trainable_mask
from repro.types import FedConfig, ModelConfig

__all__ = [
    "DeviceProfile", "JETSON_FLEET_HMDB51", "JETSON_FLEET_UCF101",
    "Fleet", "FleetSpec", "EngineSpec", "TraceEvent", "SimResult",
    "Scheduler", "run_async", "run_sync", "analytic_speedup",
]


@dataclass
class TraceEvent:
    time: float
    kind: str            # "dispatch" | "receive" | "round"
    client: int
    global_epoch: int
    staleness: int = 0
    beta_t: float = 0.0
    loss: float = math.nan


@dataclass
class SimResult:
    wall_clock_s: float
    history: list            # (virtual_time, global_epoch, loss)
    trace: list = field(default_factory=list)
    params: object = None
    staleness_hist: dict = field(default_factory=dict)
    # receive-group sizes drained per window (async): {group_size: count}.
    # window=0 is always {1: global_epochs}.
    group_hist: dict = field(default_factory=dict)
    # Scheduler heap high-water mark (async): the arrival model's resident
    # state, asserted O(in-flight) — not O(population) — by the fleet tests.
    max_inflight: int = 0

    @property
    def final_loss(self) -> float:
        return self.history[-1][2] if self.history else math.nan


def _client_time(profile: DeviceProfile, local_iters: int,
                 iters_per_epoch: int, rng: np.random.Generator,
                 jitter: float) -> float:
    epochs = local_iters / max(iters_per_epoch, 1)
    t = profile.epoch_seconds * epochs + profile.upload_seconds
    if jitter:
        # E[lognormal(μ, σ)] = exp(μ + σ²/2); μ = -σ²/2 makes the
        # multiplier mean-one so jitter does not inflate wall-clocks.
        t *= float(rng.lognormal(mean=-0.5 * jitter * jitter, sigma=jitter))
    return t


class Scheduler:
    """Virtual-clock event queue for the async simulator.

    Wraps the ``(finish_time, seq, client, w_new, τ, loss)`` heapq that
    used to live inline in ``run_async`` and owns the *staleness-bounded
    micro-batching window*: ``pop_window`` returns the earliest pending
    receive plus every later receive that

      (a) finishes within ``window`` virtual seconds of it,
      (b) would be applied at unclamped staleness ≤ ``max_staleness``
          given its position in the group (the i-th receive of a group
          started at global epoch t lands at epoch t+i), and
      (c) fits the remaining global-epoch ``budget``.

    ``policy`` decides what happens when an in-window event fails (b):
    ``"skip"`` (default) leaves it in the queue and keeps scanning — a
    later in-window receive at *lower* staleness can still legally join
    the group; ``"stop"`` is the legacy behavior that ended the whole
    group at the first too-stale event (kept reachable as the parity
    oracle). A skipped event is not lost: it leads (or joins) a later
    group, where Algorithm 1's clamp applies as usual.

    ``window <= 0`` degenerates to pop-one — exactly the legacy
    event-by-event loop, including its tie handling (two receives sharing
    a finish time still apply as two separate groups).

    This heap is also the population-scale arrival model: only dispatched
    (in-flight) clients have entries, so a 10^6-client population with an
    in-flight set of m costs O(m) heap entries — receive interarrivals
    are drawn from the superposition of the m in-flight clients' virtual
    finish-time processes, never from per-population state.
    ``max_inflight`` records the high-water mark (asserted O(in-flight)
    by the fleet tests and bench).
    """

    def __init__(self, window: float = 0.0, policy: str = "skip"):
        if policy not in ("skip", "stop"):
            raise ValueError(
                f"policy must be 'skip' or 'stop', got {policy!r}")
        self.window = float(window)
        self.policy = policy
        self._events: list = []
        self._seq = 0
        self.max_inflight = 0

    def push(self, finish_time: float, client: int, w_new, tau: int,
             loss: float) -> None:
        heapq.heappush(self._events,
                       (finish_time, self._seq, client, w_new, tau, loss))
        self._seq += 1
        self.max_inflight = max(self.max_inflight, len(self._events))

    def __len__(self) -> int:
        return len(self._events)

    def pop_window(self, t: int, max_staleness: int, budget: int) -> list:
        """Drain one receive group; see the class docstring for the rules.

        Returns a list of ``(finish_time, client, w_new, τ, loss)`` in
        virtual-time order (heap order), never empty, never longer than
        ``budget``.
        """
        ft, _, k, w_new, tau, loss = heapq.heappop(self._events)
        group = [(ft, k, w_new, tau, loss)]
        if self.window > 0:
            deadline = ft + self.window
            skipped = []
            while self._events and len(group) < budget:
                if self._events[0][0] > deadline:
                    break
                ev = heapq.heappop(self._events)
                if (t + len(group)) - ev[4] > max_staleness:
                    # admitting it here would exceed Assumption 3
                    skipped.append(ev)
                    if self.policy == "stop":
                        break        # legacy: first stale event ends group
                    continue         # skip: a fresher later event may join
                ft, _, k, w_new, tau, loss = ev
                group.append((ft, k, w_new, tau, loss))
            for ev in skipped:
                heapq.heappush(self._events, ev)
        return group


# ---------------------------------------------------------------------------
# Asynchronous (paper Algorithm 1)
# ---------------------------------------------------------------------------

def run_async(params0, cfg: ModelConfig, fed: FedConfig,
              fleet,
              client_data: Optional[Sequence[Callable[[], Iterable]]] = None,
              iters_per_epoch: int = 1, jitter: float = 0.0,
              eval_fn: Optional[Callable] = None,
              eval_every: int = 10, engine="scan",
              window: float = 0.0,
              window_policy: str = "skip", algorithm=None) -> SimResult:
    """Virtual-clock run of asynchronous federated learning.

    ``fleet`` is a ``core.fleet.Fleet`` (or a ``FleetSpec``, which is
    wrapped): each client's ``DeviceProfile``, fresh-iterator factory and
    H^k come from it. The legacy two-sequence signature —
    ``fleet: Sequence[DeviceProfile]`` plus ``client_data:
    Sequence[Callable]`` — still works through a deprecation shim
    (``Fleet.resolve``) for one release.

    ``fed.clients_per_round`` bounds the *in-flight set*: 0 (default)
    dispatches the whole population (legacy semantics — every client
    streams updates forever); m > 0 keeps exactly m clients in flight,
    sampling each replacement uniformly from the population minus the
    in-flight set. With a streaming ``FleetSpec`` fleet the resident
    client state (and the Scheduler heap) then stays O(m) however large
    the population — receive events arrive from the superposition of the
    m in-flight clients' finish-time processes.

    ``engine``: "scan" (default) runs each client's H local iterations as
    one compiled ``lax.scan`` program (core/fed_engine.py) — one dispatch
    and one host sync per *update* instead of per *iteration* — and
    batches *concurrent* dispatches (the fleet-wide kickoff, or any burst
    sharing one server state) into a single padded vmap program even
    though each client has its own H^k: stacks pad to H_max and the
    engine's iteration mask absorbs the difference. "loop" is the legacy
    per-iteration path, kept as a parity oracle. The accepted set is
    defined once, in ``core.fleet.EngineSpec``. The event-driven virtual
    clock is identical under both.

    ``window`` (virtual seconds) is the staleness-bounded micro-batching
    window: receives finishing within ``window`` of the earliest pending
    one — and whose staleness at their position in the group stays ≤
    ``fed.max_staleness`` — drain together (``Scheduler.pop_window``;
    ``window_policy`` picks between skipping a too-stale event, the
    default, and the legacy stop-at-first behavior). The group applies to
    the server as ONE fused sequential mix
    (``fedasync.server_receive_many``: a ``lax.scan`` over the stacked
    ``(w_new, β_t)``, preserving Algorithm 1's mixing order), and the
    group's re-dispatches burst through the padded batched engine as ONE
    program — steady-state async then runs the same compile-cache-friendly
    hot path as the kickoff. The virtual-clock cost of a window is that a
    grouped client idles until the group's last receive before picking up
    its next model; ``eval_fn`` granularity also coarsens to group
    boundaries. ``window=0`` (default) is the exact event-by-event loop.

    ``algorithm``: a ``core.algorithms.FedAlgorithm`` (or its registry
    name). ``None`` keeps the exact legacy FedProx paths; a stateful
    algorithm threads per-client state through local runs, sends
    ``(w_new, msg)`` over the (scheduler's virtual) wire and mixes with
    ``algorithm.mix`` — the staleness-damped generalization of Algorithm
    1's receive. Updates route through the algorithm's wire codec when
    ``fed.compress_bits`` is set or the algorithm demands it
    (``wire_always``, e.g. low-rank projection).
    """
    fleet = Fleet.resolve(fleet, client_data, fed)
    alg = (algorithms.make_algorithm(algorithm)
           if algorithm is not None else None)
    if alg is not None:
        alg.bind_fleet(fleet)
    stateful = alg is not None and alg.stateful
    espec = EngineSpec.from_str(engine, allowed=ASYNC_ENGINES)
    rng = np.random.default_rng(fed.seed)
    sample_rng = np.random.default_rng((fed.seed, 0xA51C))
    if espec is EngineSpec.SCAN:
        run = fed_engine.make_client_run(cfg, fed, algorithm=alg)
    else:
        step, opt = fedasync.cached_client_step(cfg, fed)
    mask = trainable_mask(params0, fed.trainable)
    mix_many = fedasync.make_batched_server_update(fed)
    server = ServerState(params=params0, t=0)

    # per-client assigned local iteration counts H^k ∈ [H_min, H_max]:
    # slower devices get fewer iterations (the server's resource-aware
    # choice, ``Fleet.iters``) — filled lazily so a sampled run never
    # touches more than the dispatched clients
    H: dict = {}
    inflight: set = set()
    m_inflight = fed.clients_per_round or fleet.population

    sched = Scheduler(window, policy=window_policy)
    trace, history = [], []
    staleness_hist: dict = {}
    group_hist: dict = {}

    def _empty_result(k):
        """Out-of-data client: the unchanged global goes back (stateful
        algorithms still finalize at zero iterations so the msg channel —
        SCAFFOLD's Δc=0, low-rank's capacity — stays well-formed)."""
        if not stateful:
            return (server.params, [])
        st = alg.state_for(k, server.params)
        w, st2, msg = alg.client_finalize(
            server.params, server.params, st, jnp.int32(0),
            alg.ctx_for(server.params), fed)
        alg.store_state(k, st2)
        return ((w, msg), [])

    def _run_clients(ks):
        """Local training for clients ``ks`` from the *current* server
        model. Returns {k: (w_new, losses)} — the w_new slot holds
        ``(w_new, msg)`` for stateful algorithms. Concurrent scan
        dispatches batch as one padded program; the per-client path covers
        the rest (single dispatches, the loop oracle, batches that won't
        pad)."""
        results = {}
        if espec is EngineSpec.SCAN:
            stacks = {k: stack_batches(fleet.data(k)(), limit=H[k])
                      for k in ks}
            live = [k for k in ks if stacks[k] is not None]
            if len(live) > 1:
                try:
                    padded, iters = fed_engine.pad_client_batches(
                        [stacks[k] for k in live],
                        H_max=fed.local_iters_max)
                except ValueError:        # shapes disagree across clients
                    padded = None
                if padded is not None and stateful:
                    w_news, new_states, msgs, loss_arr = run.run_batch(
                        server.params, padded, iters, mask=mask,
                        donate=True,
                        server_ctx=alg.ctx_for(server.params),
                        states=alg.stacked_states(server.params, live),
                        client_ids=live)
                    with obs.span("fed.readback"):    # single host sync
                        la = jax.device_get(loss_arr)
                    per_client = run.unstack((w_news, new_states, msgs),
                                             len(live))
                    for j, k in enumerate(live):
                        w, st, msg = per_client[j]
                        alg.store_state(k, st)
                        results[k] = ((w, msg),
                                      [float(la[j, iters[j] - 1])])
                elif padded is not None:
                    w_news, loss_arr = run.run_batch(
                        server.params, padded, iters, mask=mask,
                        donate=True)
                    with obs.span("fed.readback"):    # single host sync
                        la = jax.device_get(loss_arr)
                    per_client = run.unstack(
                        w_news, len(live))       # one dispatch, not n×leaves
                    for j, k in enumerate(live):
                        results[k] = (per_client[j],
                                      [float(la[j, iters[j] - 1])])
            for k in ks:
                if k in results:
                    continue
                if stacks[k] is None:            # client out of data
                    results[k] = _empty_result(k)
                elif stateful:
                    w, st, msg, loss_arr = run(
                        server.params, stacks[k], mask=mask, donate=True,
                        server_ctx=alg.ctx_for(server.params),
                        state=alg.state_for(k, server.params))
                    alg.store_state(k, st)
                    with obs.span("fed.readback"):
                        la = jax.device_get(loss_arr)
                    results[k] = ((w, msg), [float(la[-1])])
                else:
                    w_new, loss_arr = run(server.params, stacks[k],
                                          mask=mask, donate=True)
                    # one explicit transfer; indexing happens on host
                    with obs.span("fed.readback"):
                        la = jax.device_get(loss_arr)
                    results[k] = (w_new, [float(la[-1])])
        elif alg is not None:
            for k in ks:
                w_new, st, msg, losses = algorithms.client_update_loop(
                    server.params, fleet.data(k)(), cfg, fed, alg,
                    client_id=k, num_iters=H[k], mask=mask,
                    server_ctx=alg.ctx_for(server.params))
                results[k] = ((w_new, msg) if stateful else w_new, losses)
        else:
            for k in ks:
                w_new, _, losses = fedasync.client_update(
                    server.params, server.t, fleet.data(k)(), cfg, fed,
                    step=step, opt=opt, mask=mask, num_iters=H[k])
                results[k] = (w_new, losses)
        return results

    def dispatch(ks, now: float):
        with obs.span("sim.dispatch"):
            tau = server.t
            for k in ks:
                if k not in H:
                    H[k] = fleet.iters(k, fed)
                inflight.add(k)
            # run the local training NOW (numerically); finish time is virtual
            results = _run_clients(ks)
            for k in ks:
                w_new, losses = results[k]
                if alg is not None and (fed.compress_bits or alg.wire_always):
                    # the algorithm's wire codec (int8/int4 deltas, low-rank
                    # factors); decode against the anchor the server handed out
                    w, msg = w_new if stateful else (w_new, ())
                    wire = alg.encode(w, msg, server.params, fed)
                    w, msg = alg.decode(wire, server.params, fed)
                    w_new = (w, msg) if stateful else w
                elif fed.compress_bits:
                    # int8 delta on the wire; server reconstructs against the
                    # anchor it handed out (communication-efficient FL, §II)
                    w_new, _ = roundtrip(w_new, server.params,
                                         fed.compress_bits)
                dt = _client_time(fleet.profile(k), H[k], iters_per_epoch, rng,
                                  jitter)
                sched.push(now + dt, k, w_new, tau,
                           losses[-1] if losses else math.nan)
                trace.append(TraceEvent(now, "dispatch", k, tau))

    if m_inflight < fleet.population:
        kickoff = [int(k) for k in fleet.sample(sample_rng, m_inflight)]
    else:
        kickoff = list(range(fleet.population))
    dispatch(kickoff, 0.0)

    now = 0.0
    while server.t < fed.global_epochs and len(sched):
        with obs.span("sim.receive"):
            group = sched.pop_window(server.t, fed.max_staleness,
                                     fed.global_epochs - server.t)
            t0 = server.t
            with obs.span("server.mix"):
                if stateful:
                    server, new_ctx, stals, betas = \
                        fedasync.server_receive_many(
                            server, [(w, msg, tau)
                                     for _, _, (w, msg), tau, _ in group],
                            fed, algorithm=alg,
                            server_ctx=alg.ctx_for(server.params))
                    alg.set_ctx(new_ctx)
                else:
                    server, stals, betas = fedasync.server_receive_many(
                        server,
                        [(w_new, tau) for _, _, w_new, tau, _ in group],
                        fed, mix_many=mix_many)
            for i, ((ft, k, _, _, loss), st, bt) in enumerate(
                    zip(group, stals, betas)):
                now = ft
                staleness_hist[st] = staleness_hist.get(st, 0) + 1
                trace.append(TraceEvent(ft, "receive", k, t0 + i + 1, st,
                                        bt, loss))
                history.append((ft, t0 + i + 1, loss))
            group_hist[len(group)] = group_hist.get(len(group), 0) + 1
            obs.count(obs.UPDATES, len(group))
        if eval_fn is not None and any(
                t % eval_every == 0 for t in range(t0 + 1, server.t + 1)):
            # the fused mix has no intermediate params: evaluate once at
            # the group boundary (exact per-epoch cadence at window=0)
            eval_fn(server.t, now, server.params)
        finished = [k for _, k, _, _, _ in group]
        if server.t < fed.global_epochs:
            if m_inflight < fleet.population:
                # population-scale steady state: finished clients leave
                # the in-flight set (their state is released) and fresh
                # clients are sampled from the rest of the population
                inflight.difference_update(finished)
                for k in finished:
                    H.pop(k, None)
                fleet.release(finished)
                replacements = [int(k) for k in fleet.sample(
                    sample_rng, len(finished), exclude=inflight)]
                dispatch(replacements, now)
            else:
                dispatch(finished, now)
        else:
            inflight.difference_update(finished)
            if m_inflight < fleet.population:
                fleet.release(finished)

    return SimResult(wall_clock_s=now, history=history, trace=trace,
                     params=server.params, staleness_hist=staleness_hist,
                     group_hist=group_hist, max_inflight=sched.max_inflight)


# ---------------------------------------------------------------------------
# Synchronous FedAvg baseline
# ---------------------------------------------------------------------------

def run_sync(params0, cfg: ModelConfig, fed: FedConfig,
             fleet,
             client_data: Optional[Sequence[Callable[[], Iterable]]] = None,
             iters_per_epoch: int = 1, jitter: float = 0.0,
             eval_fn: Optional[Callable] = None,
             eval_every: int = 10, engine="scan",
             algorithm=None) -> SimResult:
    """Virtual-clock synchronous FedAvg: each round costs max(client time).

    ``fleet`` is a ``core.fleet.Fleet`` / ``FleetSpec``; the legacy
    (profiles, client_data) sequence pair still works through the
    deprecation shim (see ``run_async``).

    ``fed.clients_per_round`` enables per-round client subsampling: each
    round draws m clients uniformly without replacement, runs them as one
    padded batched program, and (for streaming fleets) releases their
    state afterwards — resident state is O(m) whatever the population.
    A round then advances m global epochs, so
    ``rounds = max(global_epochs // m, 1)``. 0 (default) runs the whole
    population every round, the legacy semantics.

    ``engine="scan"`` (default) runs every round as one vmap-over-clients
    batched program; ``"shard"`` additionally splits the round's client
    axis over this host's device mesh (``launch.mesh.make_fleet_mesh``)
    with shard_map; ``"hier"`` splits it over a two-level
    ``('edge', 'clients')`` mesh — clients reduce to edge aggregators and
    edges to the server as a nested psum, numerically the flat weighted
    average; ``"loop"`` is the legacy per-client loop (parity oracle).
    The accepted set is defined once, in ``core.fleet.EngineSpec``.

    Each round the batched engines donate the incoming global params (the
    new global aliases their buffers; ``params0`` itself is copied once up
    front and never donated), so an ``eval_fn`` must evaluate the params
    it is handed immediately, not stash them for later.

    ``algorithm``: a ``core.algorithms.FedAlgorithm`` (or its registry
    name); ``None`` keeps the exact legacy FedProx round. Stateful
    algorithms persist per-client state on the instance across rounds,
    keyed by the sampled client ids.
    """
    fleet = Fleet.resolve(fleet, client_data, fed)
    alg = (algorithms.make_algorithm(algorithm)
           if algorithm is not None else None)
    if alg is not None:
        alg.bind_fleet(fleet)
    espec = EngineSpec.from_str(engine, allowed=SYNC_ENGINES)
    rng = np.random.default_rng(fed.seed)
    sample_rng = np.random.default_rng((fed.seed, 0x5A3D))
    if espec is EngineSpec.LOOP:
        step, opt = fedasync.cached_client_step(cfg, fed)
        round_engine = None
    else:
        round_engine = espec.build_sync(cfg, fed, algorithm=alg)
    mask = trainable_mask(params0, fed.trainable)
    params = params0
    if round_engine is not None:
        # defensive copy so EVERY round can donate its params under one
        # jit donation signature (a second signature would re-trace and
        # re-compile the whole round program) while the caller's params0
        # stays untouched
        params = jax.tree_util.tree_map(jnp.array, params0)
    now = 0.0
    history, trace = [], []
    m = fed.clients_per_round or fleet.population
    rounds = fed.global_epochs // max(m, 1)
    rounds = max(rounds, 1)
    for r in range(rounds):
        with obs.span("sim.round"):
            if m < fleet.population:
                ids = [int(k) for k in fleet.sample(sample_rng, m)]
            else:
                ids = list(range(fleet.population))
            batches = [fleet.data(k)() for k in ids]
            if round_engine is not None:
                # the incoming global (our private copy, or the previous
                # round's output) is dead after this call: donate it so the
                # new global reuses its buffers
                params, losses = fedavg.fedavg_round(params, batches, cfg, fed,
                                                     engine=round_engine,
                                                     mask=mask,
                                                     donate_params=True,
                                                     algorithm=alg,
                                                     client_ids=ids)
            else:
                params, losses = fedavg.fedavg_round_loop(
                    params, batches, cfg, fed, step=step, opt=opt, mask=mask,
                    algorithm=alg, client_ids=ids)
            dt = max(_client_time(fleet.profile(k), fed.local_iters_max,
                                  iters_per_epoch, rng, jitter)
                     for k in ids)
            if m < fleet.population:
                fleet.release(ids)
            now += dt
            loss = float(np.mean([l[-1] for l in losses if l]))
            history.append((now, r + 1, loss))
            trace.append(TraceEvent(now, "round", -1, r + 1, 0, 0.0, loss))
            obs.count(obs.UPDATES)
        if eval_fn is not None and (r + 1) % eval_every == 0:
            eval_fn(r + 1, now, params)
    return SimResult(wall_clock_s=now, history=history, trace=trace,
                     params=params)


# ---------------------------------------------------------------------------
# Analytic speedup model (reproduces the Table II 40% claim without training)
# ---------------------------------------------------------------------------

def analytic_speedup(fleet: Sequence[DeviceProfile], epochs: int,
                     local_epochs: int = 3) -> dict:
    """Wall-clock for sync vs async on a fleet, ignoring numerics.

    Sync: rounds of max(client); each round consumes n_clients global epochs
    worth of aggregation (one per client). Async: clients stream updates
    independently; the server finishes when `epochs` updates arrived, i.e.
    wall clock ≈ epochs / aggregate_rate.
    """
    n = len(fleet)
    per_update = [p.epoch_seconds * local_epochs + p.upload_seconds
                  for p in fleet]
    rounds = epochs / n
    sync = rounds * max(per_update)
    rate = sum(1.0 / t for t in per_update)       # updates per second
    async_ = epochs / rate
    return {"sync_s": sync, "async_s": async_,
            "reduction": 1.0 - async_ / sync}
