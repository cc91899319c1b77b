"""Plain KD training steps (the reference of the ``kd`` driver kind).

One step on a batch: teacher logits t (no gradient), student logits s,
targets y = argmax t (the teacher's hard predictions), per-clip loss
alpha·CE(s, y) + (1 - alpha)·sum(((s - t) / T)²), averaged over the batch;
the student's gradient is clipped to a global norm of ``clip_norm``, then
SGD with weight decay and heavy-ball momentum: g += wd·p, m = mu·m + g,
p -= lr·m. Batches run in blocks of ``row_block`` clips whose gradients
add up to the batch's, so that the reference fits beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import resnet3d as rn


@functools.partial(jax.jit, static_argnames=("alpha", "temperature",
                                             "batch", "precision"))
def _block_grad(teacher, student, clips, *, alpha, temperature, batch,
                precision):
    t = jax.lax.stop_gradient(rn.forward(teacher, clips, precision))

    def loss(p):
        s = rn.forward(p, clips, precision)
        y = jnp.argmax(t, axis=-1)
        rows = alpha * rn.cross_entropy_rows(s, y) + (1 - alpha) * jnp.sum(
            ((s - t) / temperature) ** 2, axis=-1)
        return jnp.sum(rows) / batch

    return jax.value_and_grad(loss)(student)


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "wd",
                                             "clip_norm"))
def _update(p, m, g, *, lr, momentum, wd, clip_norm):
    leaves = jax.tree_util.tree_leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in leaves))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gn, 1e-9))
    g = jax.tree_util.tree_map(lambda x, w: (x * scale).astype(x.dtype)
                               + wd * w, g, p)
    m = jax.tree_util.tree_map(lambda mm, x: momentum * mm + x, m, g)
    p = jax.tree_util.tree_map(lambda w, mm: w - lr * mm, p, m)
    return p, m


def follow(teacher, student, batches, dist: dict, row_block: int,
           precision=jax.lax.Precision.HIGHEST, rows=None):
    """Run ``len(batches)`` KD steps from ``student`` with zero momentum.

    ``batches``: list of {"clips"} host arrays. ``rows``: when given, the
    number of leading clips of each batch that the step uses (the
    half-batch fault). Returns (losses, final student, final momentum).
    """
    dt = jax.tree_util.tree_leaves(student)[0].dtype
    m = jax.tree_util.tree_map(jnp.zeros_like, student)
    p = student
    losses = []
    for b in batches:
        clips = b["clips"][:rows] if rows else b["clips"]
        n = clips.shape[0]
        loss, g = 0.0, None
        for i in range(0, n, row_block):
            li, gi = _block_grad(
                teacher, p, jnp.asarray(clips[i:i + row_block], dt),
                alpha=dist["alpha"], temperature=dist["temperature"],
                batch=n, precision=precision)
            loss = loss + li
            g = gi if g is None else jax.tree_util.tree_map(jnp.add, g, gi)
        p, m = _update(p, m, g, lr=dist["lr"], momentum=dist["momentum"],
                       wd=dist["weight_decay"], clip_norm=dist["clip_norm"])
        losses.append(float(loss))
    return losses, p, m
