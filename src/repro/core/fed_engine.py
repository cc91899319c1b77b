"""Compiled client-execution engine for the federated hot path.

The legacy path (``fedasync.client_update`` / ``fedavg.fedavg_round_loop``)
dispatches one jitted ``step(...)`` per local iteration and host-syncs
``float(loss)`` after each — at simulator scale the fleet is dispatch-bound,
not compute-bound. This module collapses the H local proximal-SGD iterations
into a single ``jax.lax.scan`` over a pre-stacked batch pytree (zero
per-iteration host syncs) and, for synchronous rounds, runs *all* clients as
one batched program with ``jax.vmap`` (the global anchor broadcasts; the
per-client batch stacks carry a leading client axis).

Heterogeneous fleets — the paper's whole point: each device k gets its own
local-iteration budget H^k ∈ [H_min, H_max] — batch through the *padded*
path: every client's batch stack is zero-padded to a common H_max
(``pad_client_batches``) and a per-client iteration count threads through
the scan body as a mask; steps with index ≥ H^k are identity on the
(params, opt_state) carry and emit NaN losses. H^k arrives as a *traced*
int32 vector, so the compile cache holds ONE entry per round shape
``(n_clients, H_max, batch...)`` instead of one per distinct H — a fleet
drawing H^k from [H_min, H_max] compiles once and runs compile-free.

``ShardedSyncRound`` additionally splits the client axis of the padded
round over a device mesh (``launch.mesh.make_fleet_mesh``,
``sharding.specs.fed_round_specs``) with ``shard_map``: each shard runs its
local clients' scans and the weighted average reduces with ``psum``.

Buffer donation (``jax.jit(..., donate_argnums)``): callers that own their
inputs hand them to XLA for in-place reuse. The engine donates the batch
stacks whenever it built them itself, and — on explicit
``donate_params=True`` — the old global params, whose buffers the new
global aliases exactly (the scan carry starts from them); ``run_sync``
uses this from the second round on, when the previous round's output is
provably dead. See docs/fed_engine.md.

The jit pool itself (``compile_cache.JitCache``) is shared with the
serving stack: serving's bucketed prefill keys into the same
static-shape cache machinery this engine keys ``(H, trainable)`` round
shapes into. See core/compile_cache.py.

The *algorithm* inside the programs — the per-iteration update rule, the
client-carried state, the server fold, the wire format — is pluggable:
every engine takes ``algorithm=`` (a ``core.algorithms.FedAlgorithm``,
default ``FedProx()``, bit-identical to the pre-refactor behavior). A
stateless algorithm keeps the legacy entry-point outputs
``(w_new, losses)``; a stateful one (SCAFFOLD variates, low-rank
capacities) threads ``(server_ctx, states)`` through the same programs —
appended at the END of every jitted argument tuple so the donation
argnums (params, batch stacks) stay put — and returns
``(w_new, new_state, msg, losses)`` per client / ``(new_global, new_ctx,
new_states, losses)`` per round. Algorithm identity folds into the
engine memo key via ``cache_key()``; traced per-client quantities (H^k,
low-rank capacity) stay out of it, keeping ONE compiled program per
``(round shape, algorithm)``.

The legacy loop remains in place as a parity oracle
(tests/test_fed_engine.py checks float32 agreement).
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import obs
from repro.core import algorithms
from repro.core.compile_cache import JitCache as _JitCache
from repro.data.synthetic import count_stacked, device_zeros, stack_trees
from repro.models import registry
from repro.optim import sgd, trainable_mask
from repro.types import FedConfig, ModelConfig


def stack_client_batches(client_batch_stacks: Sequence[Any]):
    """Stack per-client batch stacks (each leaf (H, ...)) into one pytree
    with a leading client axis (n_clients, H, ...) for the vmap round.

    All clients must share the same H and batch shapes (homogeneous sync
    round); raises ValueError otherwise — heterogeneous fleets batch
    through ``pad_client_batches``, which pads per-client H to a common
    H_max and returns the iteration mask for the padded scan. Host leaves
    go to the device as they are and the stack is assembled there
    (``data.synthetic.stack_trees``): the leaves returned are device
    arrays.
    """
    if not client_batch_stacks:
        raise ValueError("no client batch stacks")
    shapes = [
        tuple(l.shape for l in jax.tree_util.tree_leaves(s))
        for s in client_batch_stacks
    ]
    if any(s != shapes[0] for s in shapes[1:]):
        raise ValueError(
            f"heterogeneous client batch stacks {shapes}; use "
            "pad_client_batches to pad per-client H to a common H_max and "
            "run the padded masked-scan round (one batched program)")
    with obs.span("fed.pad"):
        stacked = stack_trees(jax.device_put(list(client_batch_stacks)))
        count_stacked(stacked)
    return stacked


# module-level like data.synthetic's stack programs (see there)
# repro-lint: disable=R1
@functools.partial(jax.jit, static_argnums=1)
def _pad_steps(leaves, H_max: int):
    """One client's (H^k, ...) leaves zero-padded to (H_max, ...) on the
    device: keyed by H^k, so at most H_max programs."""
    return [jnp.concatenate(
        [l, jnp.zeros((H_max - l.shape[0],) + l.shape[1:], l.dtype)])
        for l in leaves]


def pad_client_batches(client_batch_stacks: Sequence[Any],
                       H_max: int | None = None):
    """Pad per-client batch stacks (each leaf (H^k, ...)) to a common H_max
    and stack to (n_clients, H_max, ...).

    Returns ``(stacked, iters)`` where ``iters`` is an int32 array of the
    true per-client iteration counts H^k — the scan mask. Padding is
    zeros: the masked scan computes a (discarded) step on pad batches, so
    their contents never reach the model update. Clients may be empty
    (H^k = 0, ``None`` or zero-length stacks) as long as one client has a
    batch to take shapes from. Trailing (per-batch) shapes and dtypes must
    agree across clients; raises ValueError otherwise — that raggedness
    needs the per-client fallback, not padding.

    The stack is assembled on the device: host leaves go there as they
    are, a short client is padded by one program per H^k (``_pad_steps``),
    an empty one takes the shared device zero stack, and one program
    keyed by (n_clients, H_max) stacks the clients. ``stacked`` holds
    device arrays.
    """
    if not client_batch_stacks:
        raise ValueError("no client batch stacks")
    lens = [(0 if s is None else
             int(jax.tree_util.tree_leaves(s)[0].shape[0])
             if jax.tree_util.tree_leaves(s) else 0)
            for s in client_batch_stacks]
    if not any(lens):
        raise ValueError("all clients empty; nothing to pad from")
    if H_max is None:
        H_max = max(lens)
    if max(lens) > H_max:
        raise ValueError(f"client iteration counts {lens} exceed "
                         f"H_max={H_max}")

    with obs.span("fed.pad"):
        stacks = jax.device_put(list(client_batch_stacks))
        ref = next(s for s, h in zip(stacks, lens) if h)
        ref_flat, treedef = jax.tree_util.tree_flatten(ref)
        # dtypes of the device leaves: canonical, and read without a copy
        trailing = [(tuple(l.shape[1:]), l.dtype) for l in ref_flat]
        padded = []
        for s, h in zip(stacks, lens):
            if h == 0:
                flat = [device_zeros((H_max,) + shp, dt)
                        for shp, dt in trailing]
                padded.append(jax.tree_util.tree_unflatten(treedef, flat))
                continue
            if jax.tree_util.tree_structure(s) != treedef:
                raise ValueError(
                    "client batch stacks disagree on pytree structure "
                    "(keys); matching leaf shapes cannot substitute for "
                    "matching keys")
            flat = jax.tree_util.tree_leaves(s)
            if [(tuple(l.shape[1:]), l.dtype) for l in flat] != trailing:
                raise ValueError(
                    "client batch stacks disagree on per-batch "
                    "shapes/dtypes; padding only evens out iteration "
                    "counts — use the per-client fallback for truly ragged "
                    "batches")
            if h < H_max:
                flat = _pad_steps(flat, H_max)
            padded.append(jax.tree_util.tree_unflatten(treedef, flat))
        stacked = stack_trees(padded)
        count_stacked(stacked)
    return stacked, np.asarray(lens, np.int32)


def _count_clip_steps(stacked, iters=None, clients: bool = True) -> None:
    """``clip_steps_executed`` and ``clip_steps_useful`` of one call on a
    client-stacked pytree (leaves (n, H_max, batch, ...); one client's
    (H, batch, ...) with ``clients=False``): n·H_max·batch run, ΣH^k·batch
    of them unmasked (all when ``iters`` is None). An ``iters`` on the
    device is not read back: that call goes uncounted."""
    if not obs.enabled() or isinstance(iters, jax.Array):
        return
    shape = jax.tree_util.tree_leaves(stacked)[0].shape
    n, H, batch = shape[:3] if clients else (1,) + tuple(shape[:2])
    obs.count("clip_steps_executed", n * H * batch)
    obs.count("clip_steps_useful",
              (n * H if iters is None else int(np.sum(iters))) * batch)


def _batch_len(stacked) -> int:
    return int(jax.tree_util.tree_leaves(stacked)[0].shape[0])


def _varying_like(tree, like):
    """Type every leaf of ``tree`` as varying over the manual mesh axes
    that ``like`` varies over (identity outside ``shard_map``).

    A client's scan carry starts from replicated values (the global
    params, a fresh optimizer state) and ends on values computed from its
    own batches. Under ``shard_map`` those batches vary over the client
    axes, and scan needs its carry typed alike on entry and exit.
    """
    axes = jax.typeof(like).vma

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree_util.tree_map(cast, tree)


def _full_iters(stacked_clients):
    """(n,) iteration vector for 'every client runs the whole stack'."""
    n, H = jax.tree_util.tree_leaves(stacked_clients)[0].shape[:2]
    return np.full((int(n),), int(H), np.int32)


def _pad_H(fed: FedConfig, client_stacks) -> int:
    """Pad target: the config's H_max, stretched if a caller handed in a
    longer stack — constant across rounds, so the padded program's shape
    (and compile-cache entry) stays stable whatever H^k is drawn."""
    return max(fed.local_iters_max,
               max((_batch_len(s) for s in client_stacks
                    if s is not None), default=0))


class ClientRun:
    """Scan-compiled local training: H proximal SGD iterations in one call.

    ``engine(params_global, stacked, mask=None)`` -> ``(w_new, losses)``
    where ``stacked`` is a batch pytree with leading axis H (see
    ``repro.data.stack_batches``) and ``losses`` is a device array of shape
    (H,) — the only host sync the caller pays is reading it.

    ``run_batch(params_global, client_stacks, iters)`` is the padded
    batched variant: many clients with *different* H^k run as one vmapped
    masked-scan program, returning per-client ``(w_news, losses)`` with
    leading client axes (no aggregation — the async simulator uses this to
    batch concurrent dispatches: the fleet-wide kickoff and, with a
    positive ``simulator.run_async(window=...)``, every steady-state
    re-dispatch burst; ``SyncRound`` adds the weighted average). Burst
    sizes m ≤ n_clients each compile once per (m, H_max) shape, so a
    windowed run is compile-free after its first pass over the sizes.
    """

    def __init__(self, cfg: ModelConfig, fed: FedConfig, loss_kwargs=None,
                 algorithm=None):
        self.cfg = cfg
        self.fed = fed
        self.loss_kwargs = dict(loss_kwargs or {})
        self.algorithm = (algorithm if algorithm is not None
                          else algorithms.FedProx())
        self.opt = sgd(fed.lr, fed.momentum, fed.weight_decay)
        self._jits = _JitCache()

    # -- pure (unjitted) core, reused by the vmap round ------------------
    def _task_loss(self, params, batch):
        return registry.loss_fn(params, self.cfg, batch,
                                **self.loss_kwargs)[0]

    def _ctx(self, anchor, mask, server_ctx):
        return algorithms.StepCtx(jax.value_and_grad(self._task_loss),
                                  self.opt, anchor, mask, server_ctx,
                                  self.fed)

    def _run(self, params_global, stacked, mask, server_ctx=(), state=()):
        alg = self.algorithm
        ctx = self._ctx(params_global, mask, server_ctx)

        def body(carry, batch):
            return alg.client_step(ctx, carry, batch)

        init = (params_global, self.opt.init(params_global), state)
        (w_new, _, state_f), losses = jax.lax.scan(body, init, stacked)
        if not alg.stateful:
            return w_new, losses
        w_new, new_state, msg = alg.client_finalize(
            w_new, params_global, state_f, jnp.int32(_batch_len(stacked)),
            server_ctx, self.fed)
        return w_new, new_state, msg, losses

    def _run_padded(self, params_global, stacked, n_iters, mask,
                    server_ctx=(), state=()):
        """Masked scan over an H_max-padded stack: steps with index >=
        ``n_iters`` (a traced int32 scalar) are identity on the carry and
        emit NaN. H^k therefore never enters the compile key — one program
        covers every iteration budget at this pad length."""
        alg = self.algorithm
        ctx = self._ctx(params_global, mask, server_ctx)

        def body(carry, xs):
            i, batch = xs
            new_carry, loss = alg.client_step(ctx, carry, batch)
            active = i < n_iters
            carry = jax.tree_util.tree_map(
                lambda new, old: jnp.where(active, new, old),
                new_carry, carry)
            return carry, jnp.where(active, loss, jnp.nan)

        H = _batch_len(stacked)
        init = _varying_like(
            (params_global, self.opt.init(params_global), state),
            jax.tree_util.tree_leaves(stacked)[0])
        (w_new, _, state_f), losses = jax.lax.scan(
            body, init, (jnp.arange(H, dtype=jnp.int32), stacked))
        if not alg.stateful:
            return w_new, losses
        w_new, new_state, msg = alg.client_finalize(
            w_new, params_global, state_f, n_iters, server_ctx, self.fed)
        return w_new, new_state, msg, losses

    def _run_padded_batch(self, params_global, stacked_clients, iters, mask,
                          server_ctx=(), states=()):
        return jax.vmap(
            lambda s, n, st: self._run_padded(params_global, s, n, mask,
                                              server_ctx, st)
        )(stacked_clients, iters, states)

    def _alg_inputs(self, params_global, server_ctx, state_or_states,
                    ids=None):
        """Resolve the (server_ctx, state) pair for a call: empty pytrees
        for stateless algorithms (zero traced leaves — the legacy
        programs), the bound instance's persisted state otherwise."""
        alg = self.algorithm
        if not alg.stateful:
            return (), ()
        if server_ctx is None:
            server_ctx = alg.ctx_for(params_global)
        if state_or_states is None:
            if ids is None:
                state_or_states = alg.state_for(0, params_global)
            else:
                state_or_states = alg.stacked_states(params_global, ids)
        return server_ctx, state_or_states

    @property
    def num_compiled(self) -> int:
        """Distinct programs actually traced across this engine's entry
        points. For the unpadded path H is the scan length (a static
        shape): one compile per distinct H. For the padded path H^k is a
        traced argument: one compile per (n_clients, H_max) round shape
        regardless of the H vector."""
        return self._jits.num_compiled

    def __call__(self, params_global, stacked, mask=None, donate=False,
                 server_ctx=None, state=None):
        """``donate=True`` hands ``stacked``'s buffers to XLA — only safe
        when the caller will not touch them again (fresh stack per call).

        Stateful algorithms return ``(w_new, new_state, msg, losses)``
        instead of ``(w_new, losses)``; ``server_ctx``/``state`` default
        to the bound algorithm instance's persisted values (client 0)."""
        if mask is None:
            mask = trainable_mask(params_global, self.fed.trainable)
        server_ctx, state = self._alg_inputs(params_global, server_ctx,
                                             state)
        _count_clip_steps(stacked, clients=False)
        return self._jits.call("run", self._run, (1,) if donate else (),
                               (params_global, stacked, mask, server_ctx,
                                state))

    def run_batch(self, params_global, client_stacks, iters=None, mask=None,
                  donate=None, server_ctx=None, states=None,
                  client_ids=None):
        """Batched padded execution of many clients with per-client H^k.

        ``client_stacks``: a sequence of per-client stacked batch pytrees
        (padded here via ``pad_client_batches``; the pad copy is engine-
        owned, so it is donated) or an already client-stacked pytree with
        (n_clients, H_max, ...) leaves plus an explicit ``iters``. Returns
        ``(w_news, losses)`` with leading client axes; ``losses`` rows are
        NaN beyond each client's H^k. Stateful algorithms additionally
        take per-client ``states`` stacked on the client axis (default:
        the bound instance's states for ``client_ids``, default
        ``range(n)``) and return ``(w_news, new_states, msgs, losses)``.
        """
        if isinstance(client_stacks, (list, tuple)):
            client_stacks, lens = pad_client_batches(
                client_stacks, H_max=_pad_H(self.fed, client_stacks))
            if iters is None:
                iters = lens
            if donate is None:
                donate = True
        if iters is None:
            iters = _full_iters(client_stacks)
        if mask is None:
            mask = trainable_mask(params_global, self.fed.trainable)
        server_ctx, states = self._alg_inputs(
            params_global, server_ctx, states,
            ids=(client_ids if client_ids is not None
                 else range(_batch_len(client_stacks))))
        _count_clip_steps(client_stacks, iters)
        return self._jits.call(
            "batch", self._run_padded_batch, (1,) if donate else (),
            (params_global, client_stacks, jnp.asarray(iters, jnp.int32),
             mask, server_ctx, states))

    def unstack(self, stacked, n: int):
        """Split a client-stacked pytree (leaves (n, ...)) into n
        per-client pytrees in ONE jitted dispatch.

        The eager equivalent — ``tree_map(lambda a: a[j], stacked)`` per
        client — enqueues n × n_leaves tiny slice ops; for a steady-state
        async burst that fan-out is paid per *group* and would eat the
        window's dispatch savings. Living on the engine's ``_JitCache``,
        the compiled slice programs share the engine's lifetime (and the
        FIFO-bounded engine cache) instead of accumulating at module
        scope; one compile per burst size n.
        """
        def _unstack(tree):
            return tuple(jax.tree_util.tree_map(lambda a: a[j], tree)
                         for j in range(n))

        return self._jits.call(("unstack", n), _unstack, (), (stacked,))


_ENGINE_CACHE: dict = {}
_ENGINE_CACHE_MAX = 32      # FIFO-bounded: engines hold compiled executables


def _engine_key(kind, cfg: ModelConfig, fed: FedConfig, loss_kwargs,
                algorithm=None):
    """Cache key over the fields that affect the compiled client program.

    Server-side knobs (mixing_beta, staleness_a, ...) don't — two sweeps
    differing only in staleness must share compiled engines. ``kind`` may
    carry extra identity (e.g. the sharded round's Mesh). The algorithm
    enters through ``cache_key()`` — equal keys promise equal traced
    hooks, so all default/FedProx callers share one engine, and all
    Scaffold instances share another (their mutable per-client state
    lives on the caller's instance and flows through arguments).
    """
    lk = tuple(sorted((loss_kwargs or {}).items()))
    ak = (algorithm.cache_key() if algorithm is not None
          else algorithms.FedProx().cache_key())
    key = (kind, cfg, fed.lr, fed.momentum, fed.weight_decay,
           fed.prox_theta, fed.trainable, lk, ak)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def cached_engine(key, build):
    """FIFO-bounded engine memo shared across subsystems.

    Engines hold compiled executables, so repeated construction (sweeps,
    benchmarks, the KD->fine-tune pipeline) must reuse them. The fed
    engines key through ``_engine_key``; the distillation engines
    (``core.distill``) bring their own hashable keys. ``key=None`` (or an
    unhashable key) skips memoization and builds fresh.
    """
    if key is not None:
        try:
            hash(key)
        except TypeError:
            key = None
    if key is None:
        return build()
    if key not in _ENGINE_CACHE:
        while len(_ENGINE_CACHE) >= _ENGINE_CACHE_MAX:
            _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
        _ENGINE_CACHE[key] = build()
    return _ENGINE_CACHE[key]


def _cached_engine(kind, cfg, fed, loss_kwargs, build, algorithm=None):
    return cached_engine(
        _engine_key(kind, cfg, fed, loss_kwargs, algorithm), build)


def make_client_run(cfg: ModelConfig, fed: FedConfig,
                    loss_kwargs=None, algorithm=None) -> ClientRun:
    """The scan engine replacing per-iteration ``step(...)`` dispatch.

    Memoized on the client-relevant config fields (+ the algorithm's
    ``cache_key``) so repeated simulator runs (hyperparameter sweeps,
    benchmarks) reuse compiled programs. Stateful callers should pass
    ``server_ctx``/``states`` explicitly — the memoized engine may be
    bound to a different (behaviorally identical) algorithm instance.
    """
    return _cached_engine(
        "client", cfg, fed, loss_kwargs,
        lambda: ClientRun(cfg, fed, loss_kwargs, algorithm=algorithm),
        algorithm=algorithm)


def _weighted_params(w_news, weights, params_global):
    """einsum over the client axis, accumulated in f32, cast back."""
    return jax.tree_util.tree_map(
        lambda l, p: jnp.einsum(
            "c,c...->...", weights,
            l.astype(jnp.float32)).astype(p.dtype),
        w_news, params_global)


class SyncRound:
    """vmap-over-clients FedAvg round: one batched program per round.

    ``round(params_global, client_stacks, weights, mask=None, iters=None)``
    -> ``(new_global, losses (n_clients, H))``. ``client_stacks`` is either
    a sequence of per-client stacked batch pytrees (stacked — or, when
    their H^k differ, padded — here) or an already client-stacked pytree
    with leading (n_clients, H) axes. With ``iters`` the padded masked-scan
    program runs: per-client H^k as a traced vector, one compile per round
    shape, NaN losses past each client's budget.

    ``donate_params=True`` additionally donates the old global params —
    the new global aliases their buffers exactly — and must only be set
    by callers that will never touch the passed-in params again.
    """

    def __init__(self, cfg: ModelConfig, fed: FedConfig, loss_kwargs=None,
                 algorithm=None):
        # share the memoized ClientRun (it is stateless): async dispatches
        # and the sync round's inner scan then reuse one trace cache
        self.client = make_client_run(cfg, fed, loss_kwargs,
                                      algorithm=algorithm)
        self.algorithm = self.client.algorithm
        self.fed = fed
        self._jits = _JitCache()

    def _reduce(self, out, params_global, weights, server_ctx):
        """The round's server half: algorithm prepare → weighted fold →
        algorithm finish. Stateless algorithms keep the legacy
        ``(new_global, losses)`` output exactly."""
        alg = self.algorithm
        if not alg.stateful:
            w_news, losses = out
            return _weighted_params(w_news, weights, params_global), losses
        w_news, new_states, msgs, losses = out
        w_eff = alg.reduce_prepare(w_news, params_global, new_states,
                                   server_ctx)
        avg = _weighted_params(w_eff, weights, params_global)
        msg_sum = algorithms.weighted_state_sum(msgs, weights)
        new_global, new_ctx = alg.reduce_finish(avg, msg_sum, server_ctx,
                                                params_global)
        return new_global, new_ctx, new_states, losses

    def _rnd(self, params_global, stacked_clients, weights, mask,
             server_ctx=(), states=()):
        # anchor (and mask) broadcast; batch stacks are per-client
        out = jax.vmap(
            lambda s, st: self.client._run(params_global, s, mask,
                                           server_ctx, st)
        )(stacked_clients, states)
        return self._reduce(out, params_global, weights, server_ctx)

    def _rnd_padded(self, params_global, stacked_clients, weights, iters,
                    mask, server_ctx=(), states=()):
        out = self.client._run_padded_batch(
            params_global, stacked_clients, iters, mask, server_ctx,
            states)
        return self._reduce(out, params_global, weights, server_ctx)

    @property
    def num_compiled(self) -> int:
        """Distinct traced programs — one per (n_clients, H) round shape
        (the padded path's H^k vector is traced, not a compile key)."""
        return self._jits.num_compiled

    def _prep(self, params_global, client_stacks, weights, mask, iters,
              donate):
        if isinstance(client_stacks, (list, tuple)):
            try:
                client_stacks = stack_client_batches(client_stacks)
            except ValueError:
                client_stacks, lens = pad_client_batches(
                    client_stacks, H_max=_pad_H(self.fed, client_stacks))
                if iters is None:   # caller-supplied H^k wins over lens
                    iters = lens
            if donate is None:
                donate = True    # the stack was built here; caller never
        n = _batch_len(client_stacks)    # sees it, so XLA may reuse it
        if weights is None:
            weights = jnp.full((n,), 1.0 / n, jnp.float32)
        else:
            weights = jnp.asarray(weights, jnp.float32)
        if mask is None:
            mask = trainable_mask(params_global, self.fed.trainable)
        return client_stacks, weights, mask, iters, bool(donate), n

    @staticmethod
    def _donated(donate, donate_params):
        return ((0,) if donate_params else ()) + ((1,) if donate else ())

    def __call__(self, params_global, client_stacks, weights=None,
                 mask=None, iters=None, donate=None,
                 donate_params: bool = False, server_ctx=None, states=None,
                 client_ids=None):
        client_stacks, weights, mask, iters, donate, n = self._prep(
            params_global, client_stacks, weights, mask, iters, donate)
        server_ctx, states = self.client._alg_inputs(
            params_global, server_ctx, states,
            ids=(client_ids if client_ids is not None else range(n)))
        argnums = self._donated(donate, donate_params)
        _count_clip_steps(client_stacks, iters)
        if iters is None:
            return self._jits.call(
                "rnd", self._rnd, argnums,
                (params_global, client_stacks, weights, mask, server_ctx,
                 states))
        return self._jits.call(
            "pad", self._rnd_padded, argnums,
            (params_global, client_stacks, weights,
             jnp.asarray(iters, jnp.int32), mask, server_ctx, states))


def make_sync_round(cfg: ModelConfig, fed: FedConfig,
                    loss_kwargs=None, algorithm=None) -> SyncRound:
    """The vmap engine replacing fedavg's per-client Python loop.

    Memoized like ``make_client_run``.
    """
    return _cached_engine(
        "sync", cfg, fed, loss_kwargs,
        lambda: SyncRound(cfg, fed, loss_kwargs, algorithm=algorithm),
        algorithm=algorithm)


class ShardedSyncRound(SyncRound):
    """Padded sync round sharded over a device mesh with ``shard_map``.

    The client axis splits across the mesh's client axis (or axes —
    ``launch.mesh.make_fleet_mesh``; specs from
    ``sharding.specs.fed_round_specs``): each shard scans its local
    clients under ``vmap``, reduces its weight-scaled parameter sum, and
    the global weighted average forms with ``psum``. Params and mask
    replicate; batch stacks, weights, and the H^k vector shard on the
    leading client axis. When n_clients does not divide the axis size the
    round pads with zero-weight, zero-iteration dummy clients and slices
    their losses back off.

    On a two-level ``('edge', 'clients')`` mesh the reduction is the
    *hierarchical edge-aggregator tree*: each shard's weight-scaled
    partial first psums over ``'clients'`` (clients → their edge
    aggregator), then the edge partials psum over ``'edge'`` (edge
    aggregators → server). Since every weight-scaled client model is
    added exactly once either way, the nested reduction equals the flat
    psum weighted average — Σ_e Σ_{k∈e} w_k·θ_k = Σ_k w_k·θ_k — which
    the fleet property tests assert (bit-identical on a single-shard
    mesh, float32-close under real sharding where reduction order is
    XLA's choice).
    """

    def __init__(self, cfg: ModelConfig, fed: FedConfig, mesh,
                 loss_kwargs=None, algorithm=None):
        from repro.sharding import specs as sh
        super().__init__(cfg, fed, loss_kwargs, algorithm=algorithm)
        self.mesh = mesh
        self._specs = sh.fed_round_specs(mesh)
        axis = self._specs["axis"]
        # hierarchy levels, innermost (leaf) first: a 1-D mesh reduces in
        # one psum; ('edge', 'clients') reduces clients-within-edge, then
        # across edges
        levels = tuple(reversed(axis)) if isinstance(axis, tuple) \
            else (axis,)

        def _psum_levels(tree):
            if not jax.tree_util.tree_leaves(tree):
                return tree
            for level in levels:     # nested: leaf aggregators upward
                tree = jax.lax.psum(tree, level)
            return tree

        def shard_fn(params_global, stacked_shard, w_shard, it_shard, mask,
                     server_ctx, states_shard):
            alg = self.algorithm
            out = self.client._run_padded_batch(
                params_global, stacked_shard, it_shard, mask, server_ctx,
                states_shard)
            if not alg.stateful:
                w_news, losses = out
                partial = jax.tree_util.tree_map(
                    lambda l: jnp.einsum("c,c...->...", w_shard,
                                         l.astype(jnp.float32)), w_news)
                partial = _psum_levels(partial)
                new = jax.tree_util.tree_map(
                    lambda t, p: t.astype(p.dtype), partial, params_global)
                return new, losses
            w_news, new_states, msgs, losses = out
            # per-client prepare (low-rank reconstruction, ...) is
            # elementwise on the client axis, so shard-local prepare +
            # the nested psum equals the global prepare + flat fold
            w_eff = alg.reduce_prepare(w_news, params_global, new_states,
                                       server_ctx)
            partial = jax.tree_util.tree_map(
                lambda l: jnp.einsum("c,c...->...", w_shard,
                                     l.astype(jnp.float32)), w_eff)
            partial = _psum_levels(partial)
            msg_sum = _psum_levels(
                algorithms.weighted_state_sum(msgs, w_shard))
            avg = jax.tree_util.tree_map(
                lambda t, p: t.astype(p.dtype), partial, params_global)
            new_global, new_ctx = alg.reduce_finish(
                avg, msg_sum, server_ctx, params_global)
            return new_global, new_ctx, new_states, losses

        c, r = self._specs["clients"], self._specs["replicated"]
        out_specs = (r, r, c, c) if self.algorithm.stateful else (r, c)
        self._sharded_rnd = jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(r, c, c, c, r, r, c),
            out_specs=out_specs)

    def _n_shards(self) -> int:
        axis = self._specs["axis"]
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    def __call__(self, params_global, client_stacks, weights=None,
                 mask=None, iters=None, donate=None,
                 donate_params: bool = False, server_ctx=None, states=None,
                 client_ids=None):
        client_stacks, weights, mask, iters, donate, n = self._prep(
            params_global, client_stacks, weights, mask, iters, donate)
        if iters is None:        # homogeneous: every client runs full H
            iters = _full_iters(client_stacks)
        iters = np.asarray(iters, np.int32)
        ids = client_ids if client_ids is not None else range(n)
        server_ctx, states = self.client._alg_inputs(
            params_global, server_ctx, states, ids=ids)
        n_shards = self._n_shards()
        pad = (-n) % n_shards
        if pad:                  # zero-weight dummies round the axis up
            client_stacks = jax.tree_util.tree_map(
                lambda l: jnp.concatenate([l] + [l[:1]] * pad),
                client_stacks)
            weights = jnp.concatenate(
                [weights, jnp.zeros((pad,), jnp.float32)])
            iters = np.concatenate([iters, np.zeros((pad,), np.int32)])
            states = jax.tree_util.tree_map(
                lambda l: jnp.concatenate([l] + [l[:1]] * pad), states)
        # the stack was assembled on one device: lay it over the mesh
        client_stacks = jax.device_put(
            client_stacks, NamedSharding(self.mesh, self._specs["clients"]))
        _count_clip_steps(client_stacks, iters)
        out = self._jits.call(
            "shard", self._sharded_rnd,
            self._donated(donate, donate_params),
            (params_global, client_stacks, weights,
             jnp.asarray(iters, jnp.int32), mask, server_ctx, states))
        if not self.algorithm.stateful:
            new, losses = out
            return new, losses[:n]
        new, new_ctx, new_states, losses = out
        new_states = jax.tree_util.tree_map(lambda l: l[:n], new_states)
        return new, new_ctx, new_states, losses[:n]


def make_sharded_sync_round(cfg: ModelConfig, fed: FedConfig, mesh=None,
                            loss_kwargs=None,
                            algorithm=None) -> ShardedSyncRound:
    """Sync-round engine whose client axis is split over ``mesh`` (default:
    this host's whole device set as a 1-D ``('clients',)`` mesh).

    Memoized like ``make_sync_round`` with the mesh folded into the key.
    """
    if mesh is None:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh()
    return _cached_engine(
        ("shard", mesh), cfg, fed, loss_kwargs,
        lambda: ShardedSyncRound(cfg, fed, mesh, loss_kwargs,
                                 algorithm=algorithm),
        algorithm=algorithm)


def make_hierarchical_sync_round(cfg: ModelConfig, fed: FedConfig,
                                 mesh=None, edges: int | None = None,
                                 loss_kwargs=None,
                                 algorithm=None) -> ShardedSyncRound:
    """Sync-round engine over a two-level ``('edge', 'clients')`` mesh:
    the hierarchical edge-aggregator tree (clients → edge aggregators →
    server as nested psums — provably the flat weighted average; see
    ``ShardedSyncRound``).

    Default mesh: this host's devices factored into
    ``launch.mesh.make_fleet_mesh(edges=...)`` (a 1-device host runs the
    identical program on a degenerate (1, 1) tree). Memoized like
    ``make_sharded_sync_round`` with the mesh folded into the key.
    """
    if mesh is None:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(edges=edges if edges is not None else 0)
    if not {"edge", "clients"} <= set(mesh.axis_names):
        raise ValueError(
            f"hierarchical round needs a ('edge', 'clients') mesh, got "
            f"axes {mesh.axis_names}")
    return _cached_engine(
        ("hier", mesh), cfg, fed, loss_kwargs,
        lambda: ShardedSyncRound(cfg, fed, mesh, loss_kwargs,
                                 algorithm=algorithm),
        algorithm=algorithm)
