"""Plain federated fine-tuning (the reference of the ``sync`` and
``async`` driver kinds), following the paper (arXiv:2107.12147 §III-D,
Algorithm 1) and the FedProx objective.

Client k, handed the global model w_t, runs H^k local steps of SGD with
heavy-ball momentum (fresh momentum per visit) on
CE(f(w; batch)) + (theta/2)·||w - w_t||²:
g = grad CE + theta·(w - w_t); m = mu·m + g; w -= lr·m.

Sync round: every client from the same w_t, the new global the average of
the clients' models weighted by their data shares (equal here).

Async (window 0, Algorithm 1): all clients start from w_0 at time 0; a
client's update arrives at virtual time dispatch + epoch_seconds·H^k +
upload_seconds (the Jetson profile), ties in dispatch order. On each
arrival at global epoch t with the model the client was handed at epoch
tau: s = min(max(t - tau, 0), K), beta_t = beta·(1 + s)^-a,
w = (1 - beta_t)·w + beta_t·w_new, t += 1, and the client is handed the
new w at once.
"""
from __future__ import annotations

import functools
import heapq

import jax
import jax.numpy as jnp

from reference import resnet3d as rn


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "theta",
                                             "precision", "rows"))
def _local_step(w, m, anchor, clips, labels, *, lr, momentum, theta,
                precision, rows):
    if rows:
        clips, labels = clips[:rows], labels[:rows]

    def loss(p):
        return jnp.mean(rn.cross_entropy_rows(
            rn.forward(p, clips, precision), labels))

    val, g = jax.value_and_grad(loss)(w)
    g = jax.tree_util.tree_map(lambda x, p, a: x + theta * (p - a), g, w,
                               anchor)
    m = jax.tree_util.tree_map(lambda mm, x: momentum * mm + x, m, g)
    w = jax.tree_util.tree_map(lambda p, mm: p - lr * mm, w, m)
    return w, m, val


def client_run(w_t, batches, fed: dict, precision, rows=None):
    """H^k = len(batches) local steps from w_t. Returns (w, losses)."""
    dt = jax.tree_util.tree_leaves(w_t)[0].dtype
    w, m = w_t, jax.tree_util.tree_map(jnp.zeros_like, w_t)
    losses = []
    for b in batches:
        w, m, val = _local_step(
            w, m, w_t, jnp.asarray(b["clips"], dt), jnp.asarray(b["labels"]),
            lr=fed["lr"], momentum=fed["momentum"], theta=fed["prox_theta"],
            precision=precision, rows=rows)
        losses.append(float(val))
    return w, losses


@jax.jit
def _average(models):
    n = len(models)
    return jax.tree_util.tree_map(lambda *ls: sum(ls) / n, *models)


def sync_rounds(w0, rounds, fed: dict, precision, rows=None):
    """``rounds``: per round, per client, the list of its batches. Returns
    (per-round losses = mean over clients of their last local loss, the
    global after each round)."""
    w, losses, models = w0, [], []
    for clients in rounds:
        outs = [client_run(w, bl, fed, precision, rows) for bl in clients]
        w = _average([o[0] for o in outs])
        losses.append(sum(o[1][-1] for o in outs) / len(outs))
        models.append(w)
    return losses, models


@jax.jit
def _mix(w, w_new, beta):
    return jax.tree_util.tree_map(
        lambda a, b: ((1 - beta) * a + beta * b).astype(a.dtype), w, w_new)


def async_receives(w0, profiles, batches_for, receives: int,
                   fed: dict, precision, rows=None):
    """Algorithm 1 for ``receives`` arrivals. ``profiles[k]`` is (epoch
    seconds, upload seconds) and ``batches_for(k)`` returns client k's
    batches for its next visit, H^k of them. Returns (per-arrival
    losses = the arriving client's last local loss, the global after each
    arrival, the arriving clients)."""
    events, seq, t = [], 0, 0
    w = w0

    def dispatch(k, now, tau):
        nonlocal seq
        batches = batches_for(k)
        w_new, ls = client_run(w, batches, fed, precision, rows)
        done = now + profiles[k][0] * len(batches) + profiles[k][1]
        heapq.heappush(events, (done, seq, k, tau, w_new, ls[-1]))
        seq += 1

    for k in range(len(profiles)):
        dispatch(k, 0.0, 0)
    losses, models, who = [], [], []
    while t < receives:
        now, _, k, tau, w_new, loss = heapq.heappop(events)
        s = min(max(t - tau, 0), fed["max_staleness"])
        beta = fed["mixing_beta"] * (1.0 + s) ** (-fed["staleness_a"])
        w = _mix(w, w_new, jnp.float32(beta))
        t += 1
        losses.append(loss)
        models.append(w)
        who.append(k)
        if t < receives:
            dispatch(k, now, t)
    return losses, models, who
