"""Seeded clip traffic: a pool of synthetic action clips rendered once in
set-up, then batches drawn from it by a seeded permutation.

The pool is cut into blocks of one batch, and a draw hands out the next
block of a seeded permutation of the blocks: a view of the pool, no copy.
So the window pays for the host copies the program makes (stacking,
padding, transfer) and not for the benchmark's own: on a TPU v5e host a
fresh 77 MB copy of one KD batch took about 0.3 s, which would otherwise
count against the program.

The clip generator is the program's ``data/synthetic.SyntheticActionDataset``
(a Gaussian blob moving along a per-class direction and speed over a
per-class texture, plus pixel noise), restated in ``jax.numpy`` so that a
whole pool renders in one jitted call on the device: the numpy original
costs about 12 ms per 8x112x112 clip on the host, too slow for set-up.

The pixel noise is the generator's default, ``NOISE``; labels range over
the configuration's classes.

Every draw is logged (which update it fed, which client, which pool rows),
so that the reference replays the rows the program received.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

# the pixel noise of ``SyntheticActionDataset`` by default
NOISE = 0.35


def key_for(seed: int, salt: int = 0):
    """A JAX key from a seed of any size (the low and high 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, salt)


@functools.partial(jax.jit,
                   static_argnames=("n", "classes", "frames", "size"))
def _render(key, *, n: int, classes: int, frames: int, size: int):
    kc, kl, ks, kd, kv, kn = jax.random.split(key, 6)
    # per-class motion programs
    k1, k2, k3, k4 = jax.random.split(kc, 4)
    dirs = jax.random.normal(k1, (classes, 2))
    dirs = dirs / (jnp.linalg.norm(dirs, axis=1, keepdims=True) + 1e-9)
    speeds = jax.random.uniform(k2, (classes,), minval=0.5, maxval=2.5)
    widths = jax.random.uniform(k3, (classes,), minval=1.5, maxval=3.5)
    textures = 0.3 * jax.random.normal(k4, (classes, size, size, 3))
    # per-clip draws
    labels = jax.random.randint(kl, (n,), 0, classes)
    start = jax.random.uniform(ks, (n, 2), minval=0.25 * size,
                               maxval=0.75 * size)
    d = dirs[labels] + 0.15 * jax.random.normal(kd, (n, 2))
    sp = speeds[labels] * jax.random.uniform(kv, (n,), minval=0.8,
                                             maxval=1.2)
    w = widths[labels]
    t = jnp.arange(frames, dtype=jnp.float32)
    centre = start[:, None, :] + d[:, None, :] * (sp[:, None] * t)[..., None]
    yy, xx = jnp.mgrid[0:size, 0:size].astype(jnp.float32)
    dx = (xx[None, None] - centre[..., 0, None, None]) % size
    dy = (yy[None, None] - centre[..., 1, None, None]) % size
    blob = jnp.exp(-(dx ** 2 + dy ** 2) / (2 * w[:, None, None, None] ** 2))
    clips = blob[..., None] + textures[labels][:, None]
    clips = clips + NOISE * jax.random.normal(kn, clips.shape)
    return clips.astype(jnp.float32), labels.astype(jnp.int32)


class Pool:
    """``n`` clips of shape (frames, size, size, 3) with labels in
    [0, classes), rendered on the device from ``seed`` and held on the
    host, in blocks of ``batch`` clips. ``draw()`` hands out the next
    block of a seeded permutation stream of the blocks, a fresh
    permutation each time every block has been handed out once."""

    def __init__(self, seed: int, n: int, batch: int, classes: int,
                 frames: int, size: int):
        if n % batch:
            raise ValueError(f"pool of {n} clips is not whole batches of "
                             f"{batch}")
        clips, labels = _render(key_for(seed, 1), n=n, classes=classes,
                                frames=frames, size=size)
        self.clips, self.labels = jax.device_get((clips, labels))
        self.batch = batch
        self.blocks = n // batch
        self._rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                           2])
        self._perm: list = []
        self.log: list = []          # (update, client, rows)
        self.update = 0              # the harness sets this at each update
        self.span = contextlib.nullcontext

    def draw(self, client: int = -1) -> dict:
        with self.span("bench.draw"):
            if not self._perm:
                self._perm = list(self._rng.permutation(self.blocks))
            i = int(self._perm.pop(0))
            rows = slice(i * self.batch, (i + 1) * self.batch)
            self.log.append((self.update, client, rows))
            return self.batch_of(rows)

    def stream(self, client: int = -1):
        """Endless batches for one consumer."""
        while True:
            yield self.draw(client)

    def client_data(self, client: int, iters: int):
        """A fresh-iterator factory that yields ``iters`` batches per visit
        (the fleet's per-client data contract)."""
        def factory():
            for _ in range(iters):
                yield self.draw(client)
        return factory

    def batch_of(self, rows) -> dict:
        return {"clips": self.clips[rows], "labels": self.labels[rows]}
