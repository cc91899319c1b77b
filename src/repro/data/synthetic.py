"""Synthetic datasets standing in for Kinetics / HMDB51 / UCF101.

The repro gate (DESIGN.md): the real video datasets (400 GB) and the Jetson
testbed are unavailable, and the paper's claims are about *relative*
behaviour (KD > scratch, async ≈ sync accuracy at lower wall-clock). The
synthetic action dataset is constructed so those relative effects are
reproducible:

- each class c has a latent "motion program" (direction, speed, texture seed)
  rendering short clips of a moving Gaussian blob over structured noise;
- class manifolds overlap (configurable noise) so a large teacher separates
  them better than a small student trained from scratch on few samples —
  the regime where KD transfers dark knowledge;
- a "small" dataset (HMDB51 stand-in) is a low-sample, higher-noise split
  and a "large" one (Kinetics stand-in) has many samples per class.

The LM dataset is an order-k Markov chain over a small vocab for the
transformer-family architectures (used by FL integration tests).
"""
from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs


@dataclass
class SyntheticActionDataset:
    """Procedural video-clip classification."""
    num_classes: int
    samples_per_class: int
    frames: int = 4
    size: int = 16
    noise: float = 0.35
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        C = self.num_classes
        # latent motion programs
        self.dirs = rng.normal(size=(C, 2))
        self.dirs /= np.linalg.norm(self.dirs, axis=1, keepdims=True) + 1e-9
        self.speeds = rng.uniform(0.5, 2.5, size=(C,))
        self.widths = rng.uniform(1.5, 3.5, size=(C,))
        self.textures = rng.normal(size=(C, self.size, self.size, 3)) * 0.3

    def __len__(self):
        return self.num_classes * self.samples_per_class

    def render(self, cls: int, rng: np.random.Generator) -> np.ndarray:
        T, S = self.frames, self.size
        yy, xx = np.mgrid[0:S, 0:S].astype(np.float32)
        start = rng.uniform(S * 0.25, S * 0.75, size=(2,))
        clip = np.empty((T, S, S, 3), np.float32)
        d = self.dirs[cls] + rng.normal(scale=0.15, size=2)
        sp = self.speeds[cls] * rng.uniform(0.8, 1.2)
        w = self.widths[cls]
        for t in range(T):
            cx, cy = start + d * sp * t
            blob = np.exp(-(((xx - cx) % S) ** 2 + ((yy - cy) % S) ** 2)
                          / (2 * w * w))
            frame = blob[..., None] + self.textures[cls]
            clip[t] = frame
        clip += rng.normal(scale=self.noise, size=clip.shape)
        return clip

    def batches(self, batch_size: int, steps: int, seed: int = 0,
                indices: np.ndarray | None = None):
        """Yields dicts {clips, labels}. ``indices`` restricts to a client
        shard (see partition.py)."""
        rng = np.random.default_rng((self.seed, seed))
        n = len(self) if indices is None else len(indices)
        for _ in range(steps):
            if indices is None:
                labels = rng.integers(0, self.num_classes, size=batch_size)
            else:
                pick = rng.integers(0, n, size=batch_size)
                labels = (indices[pick] % self.num_classes).astype(np.int64)
            clips = np.stack([self.render(int(c), rng) for c in labels])
            yield {"clips": clips.astype(np.float32),
                   "labels": labels.astype(np.int32)}


@dataclass
class SyntheticLMDataset:
    """Order-1 Markov chain token stream with class-like modes."""
    vocab: int
    seq_len: int
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        raw = rng.dirichlet(np.full(self.vocab, 0.05), size=self.vocab)
        self.T = raw / raw.sum(axis=1, keepdims=True)

    def sample(self, rng: np.random.Generator, batch: int) -> np.ndarray:
        out = np.empty((batch, self.seq_len + 1), np.int64)
        out[:, 0] = rng.integers(0, self.vocab, size=batch)
        for i in range(self.seq_len):
            probs = self.T[out[:, i]]
            cum = probs.cumsum(axis=1)
            u = rng.random((batch, 1))
            out[:, i + 1] = (u > cum).sum(axis=1)
        return out

    def batches(self, batch_size: int, steps: int, seed: int = 0,
                indices=None):
        rng = np.random.default_rng((self.seed, seed))
        for _ in range(steps):
            toks = self.sample(rng, batch_size)
            yield {"tokens": toks[:, :-1].astype(np.int32),
                   "labels": toks[:, 1:].astype(np.int32)}


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *trees)


# The stacks are assembled on the device by the two programs below. Their
# compile key is the number of trees (rows and columns for ``stack_rows``),
# the tree structure and the leaf shapes and dtypes: never which slots hold
# padding, so a new H^k draw compiles nothing. They stay outside JitCache:
# one module-level jit each, keyed by the shapes the engines already
# compile for, and no ``engine.*`` span counting a stack as an engine call.
# repro-lint: disable=R1
@jax.jit
def stack_trees(trees):
    """A sequence of equal pytrees -> one pytree, each leaf stacked on a
    new leading axis."""
    return _stack_trees(trees)


# repro-lint: disable=R1
@jax.jit
def stack_rows(rows):
    """n rows of H equal pytrees -> one pytree of (n, H, ...) leaves."""
    return _stack_trees([_stack_trees(row) for row in rows])


# made under the transfer guard too: no host scalar goes to the device
_zeros = jax.jit(jnp.zeros, static_argnums=(0, 1))  # repro-lint: disable=R1


@functools.lru_cache(maxsize=64)
def device_zeros(shape: tuple, dtype) -> jax.Array:
    """One zero array per (shape, dtype), made on the device once and
    shared by every pad slot of that shape. Never donate it."""
    return _zeros(tuple(shape), np.dtype(dtype))


def count_stacked(tree) -> None:
    """``device_stacked_bytes``: the bytes of a stack assembled on the
    device. ``staged_bytes`` (host bytes built in fresh arrays for
    transfer) is counted with it and stays 0: no stacking site builds a
    host array."""
    if obs.enabled():
        obs.count("device_stacked_bytes", sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)))
        obs.count("staged_bytes", 0)


def stack_batches(batches, limit: int | None = None):
    """Stack an iterable of dict batches into one pytree with leading axis H.

    This is the wire format of the scan client engine
    (``repro.core.fed_engine``): H per-iteration batches become arrays of
    shape (H, batch, ...) so local training compiles to a single
    ``lax.scan``. ``limit`` caps H (the simulator's per-client budget).
    Returns None when the iterable is empty (legacy loop semantics: the
    client returns the global model unchanged).

    The batches go to the device as they are, in one ``jax.device_put``
    (a leaf already on the device stays where it is), and one jitted
    program stacks them there: the leaves returned are device arrays, and
    the host builds no stack. The transfer is asynchronous, so a batch
    must not be written to after it is handed in. Batches whose shapes
    disagree raise ValueError.
    """
    with obs.span("data.stack"):
        # islice, not enumerate+break: the latter would pull (and waste)
        # one batch past the limit, breaking consumption parity with the
        # legacy ``zip(range(H), batches)`` loop on shared iterators
        out = list(itertools.islice(batches, limit))
        if not out:
            return None
        stacked = stack_trees(jax.device_put(out))
        count_stacked(stacked)
        return stacked


def make_dataset_for(cfg, *, small: bool = True, seed: int = 0):
    """Dataset stand-in appropriate for a model family.

    small=True  -> HMDB51-like (few samples, noisy; clients' fine-tune data)
    small=False -> Kinetics-like (many samples; server-side distillation)

    Clips render at the model's input shape: the paper's 8×112×112 for a
    published-width resnet3d, 4×16×16 for its ``.reduced()`` preset.
    """
    if cfg.family == "resnet3d":
        from repro.models.resnet3d import input_shape
        frames, size = input_shape(cfg, 1)[1:3]
        return SyntheticActionDataset(
            num_classes=min(cfg.num_classes, 16 if small else 32),
            samples_per_class=8 if small else 64,
            frames=frames, size=size,
            noise=0.5 if small else 0.3,
            seed=seed)
    return SyntheticLMDataset(vocab=cfg.vocab_size,
                              seq_len=64, seed=seed)
