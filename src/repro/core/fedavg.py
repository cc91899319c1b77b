"""Synchronous FedAvg baseline (McMahan et al. [30]; paper baseline #2).

Each round every client runs E_local epochs from the current global model;
the server replaces the model with the data-size-weighted average. Wall
clock per round = slowest client (the straggler penalty the async variant
removes).

``fedavg_round`` runs the whole round as ONE batched program: client batch
stacks get a leading client axis and ``jax.vmap`` maps the scan-compiled
local training over it (see core/fed_engine.py), so a sync round costs a
single dispatch instead of n_clients × H jitted steps plus n_clients × H
host syncs. Heterogeneous fleets — clients with different iteration
budgets H^k, including clients that ran out of data — batch too: their
stacks zero-pad to a common H_max and the engine's per-client iteration
mask makes padded steps identity (docs/fed_engine.md). Only clients whose
*batch shapes* disagree drop to the per-client fallback.
``fedavg_round_loop`` is the legacy per-client Python loop, kept as the
parity oracle.
"""
from __future__ import annotations

import itertools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import algorithms, compile_cache, fed_engine
from repro.core.fedasync import cached_client_step, make_client_step
from repro.data.synthetic import (count_stacked, device_zeros, stack_batches,
                                  stack_rows)
from repro.optim import trainable_mask
from repro.types import FedConfig, ModelConfig


# Aggregation shares one counted jit pool: one traced program per client
# count (the pytree arity is the compile key), observable via num_compiled.
_JITS = compile_cache.JitCache()


def _weighted_average_impl(param_trees, weights):
    def avg(*leaves):
        stacked = jnp.stack([l.astype(jnp.float32) for l in leaves])
        w = weights.reshape((-1,) + (1,) * (stacked.ndim - 1))
        return jnp.sum(stacked * w, axis=0).astype(leaves[0].dtype)
    return jax.tree_util.tree_map(avg, *param_trees)


def weighted_average(param_trees: Sequence, weights: jax.Array):
    """weights normalized data sizes, shape (n_clients,)."""
    return _JITS.call("weighted_average", _weighted_average_impl,
                      (), (list(param_trees), weights))


def _client_weights(n: int, data_sizes: Sequence[int] | None):
    # built on the host and handed over explicitly (no implicit transfer)
    if data_sizes is None:
        return jnp.asarray(np.full((n,), 1.0 / n, np.float32))
    s = jnp.asarray(np.asarray(data_sizes, np.float32))
    return s / jnp.sum(s)


def _alg_round_io(algorithm, params_global, n, client_ids):
    """Explicit per-round state for a stateful algorithm: the memoized
    engine may be bound to a different (equal-keyed) instance, so the
    *caller's* instance supplies ctx/states and commits the results.
    Returns (ids, engine-call kwargs); ids is None for stateless."""
    if algorithm is None or not algorithm.stateful:
        return None, {}
    ids = list(client_ids) if client_ids is not None else list(range(n))
    return ids, {"server_ctx": algorithm.ctx_for(params_global),
                 "states": algorithm.stacked_states(params_global, ids)}


def _alg_round_commit(algorithm, ids, out):
    """Unpack an engine round output, committing stateful results back to
    the caller's algorithm instance. Returns (new_global, losses)."""
    if ids is None:
        return out
    new_global, new_ctx, new_states, losses = out
    algorithm.set_ctx(new_ctx)
    algorithm.store_states(ids, new_states)
    return new_global, losses


def fedavg_round(params_global, client_batches: Sequence, cfg: ModelConfig,
                 fed: FedConfig, engine=None,
                 mask=None, data_sizes: Sequence[int] | None = None,
                 donate_params: bool = False, algorithm=None,
                 client_ids: Sequence[int] | None = None):
    """One synchronous round as a single vmap-batched program.

    ``client_batches``: per-client iterable of batches (the legacy
    contract); each is stacked to at most H = fed.local_iters_max
    iterations and all clients run together. Returns
    (new_global_params, per_client_losses) with losses as lists of floats
    (length H^k per client), matching the loop oracle. A homogeneous fleet
    takes the plain vmap path; clients with *different batch counts* H^k
    (including zero — out of data) pad to H_max and run the masked-scan
    path. Only batch shapes that disagree within or across clients drop to
    the per-client fallback; see ``_ragged_fallback``.

    ``engine``: a ``fed_engine.SyncRound`` instance, ``None`` (the default
    memoized vmap engine), or an ``core.fleet.EngineSpec`` / its string
    value — the one validated definition of the engine knob ("loop"
    routes to ``fedavg_round_loop``).

    ``donate_params=True`` lets the engine alias the new global onto
    ``params_global``'s buffers — only pass it when the caller will never
    use ``params_global`` again (e.g. round r > 0 of a training loop).

    ``algorithm``: a ``core.algorithms.FedAlgorithm`` (or ``None`` for the
    default ``FedProx``, bit-identical to the pre-refactor round).
    Stateful algorithms persist per-client state on the instance keyed by
    ``client_ids`` (default ``range(n_clients)``).
    """
    if algorithm is not None:
        algorithm = algorithms.make_algorithm(algorithm)
    if engine is not None and not isinstance(engine, fed_engine.SyncRound):
        from repro.core.fleet import EngineSpec
        spec = EngineSpec.from_str(engine)
        engine = spec.build_sync(cfg, fed, algorithm=algorithm)
        if engine is None:                  # EngineSpec.LOOP
            return fedavg_round_loop(params_global, client_batches, cfg,
                                     fed, mask=mask, data_sizes=data_sizes,
                                     algorithm=algorithm,
                                     client_ids=client_ids)
    # materialize up to H batches per client first: iterators may be
    # generators, so raggedness must be detected before anything is lost
    client_lists = [list(itertools.islice(b, fed.local_iters_max))
                    for b in client_batches]
    # one signature scan decides all three paths: a single shared batch
    # signature is the batched programs' precondition; equal non-zero
    # counts additionally allow the mask-free homogeneous program
    sigs = {_batch_sig(b) for bl in client_lists for b in bl}
    counts = [len(bl) for bl in client_lists]
    if client_lists and len(sigs) == 1:
        if min(counts) == max(counts) > 0:
            # stack straight to (n_clients, H, ...) on the device — one
            # program, not a per-client stack followed by a restack
            with obs.span("fed.pad"):
                stacked_clients = stack_rows(jax.device_put(client_lists))
                count_stacked(stacked_clients)
            if engine is None:
                engine = fed_engine.make_sync_round(cfg, fed,
                                                    algorithm=algorithm)
            weights = _client_weights(len(client_lists), data_sizes)
            ids, alg_kw = _alg_round_io(algorithm, params_global,
                                        len(client_lists), client_ids)
            out = engine(params_global, stacked_clients,
                         weights=weights, mask=mask, donate=True,
                         donate_params=donate_params, **alg_kw)
            new_global, losses = _alg_round_commit(algorithm, ids, out)
            with obs.span("fed.readback"):
                losses = np.asarray(losses)
            return new_global, [[float(x) for x in row] for row in losses]
        return _padded_round(params_global, client_lists, cfg, fed,
                             engine, mask, data_sizes, donate_params,
                             algorithm, client_ids)
    return _ragged_fallback(params_global, client_lists, cfg, fed,
                            engine, mask, data_sizes, algorithm, client_ids)


def _batch_sig(b):
    # ``v.dtype``, not ``np.asarray(v).dtype``: on a device leaf the
    # latter is a hidden device-to-host copy
    return tuple(sorted((k, np.shape(v), str(v.dtype))
                        for k, v in b.items()))


def _padded_round(params_global, client_lists, cfg, fed, engine, mask,
                  data_sizes, donate_params=False, algorithm=None,
                  client_ids=None):
    """Heterogeneous-H round as one padded masked-scan program.

    The (n_clients, H_max, ...) stack is assembled on the device, mirroring
    the homogeneous branch: every real batch goes to the device as it is,
    each pad slot takes the one shared device zero batch of its shape, and
    one program keyed by (n_clients, H_max) and the batch shapes stacks the
    slots. The engine threads the true H^k vector through the scan mask:
    one compiled program per round shape, whatever the H^k draw, for the
    stack as for the round. Empty clients run zero iterations and
    contribute the unchanged global to the weighted average, matching the
    loop oracle. Zero pad rows are what the mask discards, so their
    contents never matter.
    """
    n = len(client_lists)
    H_max = max(fed.local_iters_max, max(len(bl) for bl in client_lists))
    iters = np.asarray([len(bl) for bl in client_lists], np.int32)
    with obs.span("fed.pad"):
        client_lists = jax.device_put(client_lists)
        ref = next(b for bl in client_lists for b in bl)
        zero = jax.tree_util.tree_map(
            lambda v: device_zeros(v.shape, v.dtype), ref)
        stacked = stack_rows([bl + [zero] * (H_max - len(bl))
                              for bl in client_lists])
        count_stacked(stacked)
    if engine is None:
        engine = fed_engine.make_sync_round(cfg, fed, algorithm=algorithm)
    weights = _client_weights(n, data_sizes)
    ids, alg_kw = _alg_round_io(algorithm, params_global, n, client_ids)
    out = engine(params_global, stacked, weights=weights,
                 mask=mask, iters=iters, donate=True,
                 donate_params=donate_params, **alg_kw)
    new_global, losses = _alg_round_commit(algorithm, ids, out)
    with obs.span("fed.readback"):
        losses = np.asarray(losses)
    return new_global, [[float(x) for x in row[:h]]
                        for row, h in zip(losses, iters)]


def _ragged_fallback(params_global, client_lists, cfg, fed, engine,
                     mask, data_sizes, algorithm=None, client_ids=None):
    """Per-client runs + weighted average when no batched program can form
    (batch *shapes* disagree — count-only raggedness takes
    ``_padded_round``): stackable clients use the scan engine,
    within-client-ragged ones drop to the per-iteration step loop, empty
    ones return the global model. Stateful algorithms route through the
    algorithm-aware loop oracle + ``server_reduce``."""
    if algorithm is not None and algorithm.stateful:
        ids = list(client_ids) if client_ids is not None \
            else list(range(len(client_lists)))
        if mask is None:
            mask = trainable_mask(params_global, fed.trainable)
        ctx = algorithm.ctx_for(params_global)
        w_news, states, msgs, losses = [], [], [], []
        for k, bl in zip(ids, client_lists):
            w, st, msg, ls = algorithms.client_update_loop(
                params_global, bl, cfg, fed, algorithm, client_id=k,
                mask=mask, server_ctx=ctx)
            w_news.append(w)
            states.append(st)
            msgs.append(msg)
            losses.append(ls)
        new_global, _ = algorithms.server_reduce(
            algorithm, params_global, w_news, states, msgs,
            _client_weights(len(ids), data_sizes), server_ctx=ctx)
        return new_global, losses
    # reuse the round engine's client (and its compile cache) if provided —
    # a fresh ClientRun per round would recompile every call
    run = engine.client if engine is not None \
        else fed_engine.make_client_run(cfg, fed)
    if mask is None:
        mask = trainable_mask(params_global, fed.trainable)
    results, losses = [], []
    for bl in client_lists:
        if not bl:                          # client out of data
            results.append(params_global)
            losses.append([])
            continue
        try:
            s = stack_batches(bl)
        except ValueError:                  # ragged shapes within client:
            s = None                        # per-iteration oracle path
        if s is None:
            step, opt = cached_client_step(cfg, fed)
            params = params_global
            opt_state = opt.init(params)
            cl = []
            for batch in bl:
                params, opt_state, loss = step(params, opt_state,
                                               params_global, batch, mask)
                cl.append(float(loss))
            results.append(params)
            losses.append(cl)
        else:
            w_new, ls = run(params_global, s, mask=mask)
            results.append(w_new)
            losses.append([float(x) for x in np.asarray(ls)])
    return (weighted_average(results,
                             _client_weights(len(results), data_sizes)),
            losses)


def fedavg_round_loop(params_global, client_batches: Sequence,
                      cfg: ModelConfig, fed: FedConfig, step=None, opt=None,
                      mask=None, data_sizes: Sequence[int] | None = None,
                      algorithm=None,
                      client_ids: Sequence[int] | None = None):
    """Legacy per-client / per-iteration loop — the engine's parity oracle.

    One jitted step dispatch and one ``float(loss)`` host sync per local
    iteration. Returns (new_global_params, per_client_losses).
    Stateful algorithms route through the algorithm-aware loop oracle
    (``algorithms.client_update_loop`` + ``server_reduce``); stateless
    ones keep the legacy step, bit-identical to the pre-refactor loop.
    """
    if algorithm is not None:
        algorithm = algorithms.make_algorithm(algorithm)
        if algorithm.stateful:
            client_lists = [list(itertools.islice(b, fed.local_iters_max))
                            for b in client_batches]
            return _ragged_fallback(params_global, client_lists, cfg, fed,
                                    None, mask, data_sizes, algorithm,
                                    client_ids)
    if step is None:
        step, opt = make_client_step(cfg, fed)
    if mask is None:
        mask = trainable_mask(params_global, fed.trainable)
    results, losses = [], []
    for batches in client_batches:
        params = params_global
        opt_state = opt.init(params)
        cl = []
        for i, batch in zip(range(fed.local_iters_max), batches):
            params, opt_state, loss = step(params, opt_state, params_global,
                                           batch, mask)
            cl.append(float(loss))
        results.append(params)
        losses.append(cl)
    n = len(results)
    return (weighted_average(results, _client_weights(n, data_sizes)),
            losses)
